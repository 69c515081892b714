"""Device milliseconds a call under one of the program's phases
(`scope_reader.PHASES`: fwd, bwd, opt, prefill, first_token, decode),
optionally one block of it (embed, attn, ffn, head): seconds of the
operations that carry the scope, collectives left out, mean over the
chips. `per` is `call` (a train step, a request) or `decode_step` (the
`n_new - 1` scanned positions of each request). Nothing under the phase
in the trace (a program without the scopes): nothing returned."""

from perfbench import scope_reader


def read(context, phase: str, block: str | None = None, per: str = "call"):
    scopes = scope_reader.of(context)
    if not scopes:
        return None
    if block is None:
        seconds = scopes["phase_s"].get(phase)
    else:
        seconds = scopes["phase_block_s"].get(f"{phase}/{block}")
    if seconds is None:
        return None
    if per == "call":
        units = scopes["calls"]
    elif per == "decode_step":
        units = scopes["calls"] * (context["cell"].traffic["n_new"] - 1)
    else:
        raise SystemExit(f"scope_ms: unknown per {per!r}")
    return 1e3 * seconds / units
