"""A part's share of its roofline in the command-a-plus-05-2026 session
cell: the least time the chip could take for the operations and bytes
that the traced turns need (`counts_cmdaplus`; the larger of FLOPs over
peak FLOP/s and bytes over peak bytes/s) over the device time it took,
as `work` says:

- `kv`: every key and value row that a scanned position's query may
  see, once, in every layer (the window layers' last 4096, the full
  layer's all), over the summed time of the trace's events matching
  `match` (the decode kernel's pinned name, whatever layer calls it);
- `moe`: the weights of the held experts a step is expected to touch
  under uniform routing and of the four shared experts, every layer,
  over the device time under the program's scopes `marks`.

The same work whatever implements it, so a kernel that read a row
twice reads lower. Nothing found in the trace: nothing returned."""

import sys

from perfbench import counts_cmdaplus, harness, trace_reader
from perfbench.readers import session_step_ms_less


def read(context, work: str, marks: list = (), match: list = ()):
    if match:
        seconds = trace_reader.matching_seconds(context.get("trace") or {},
                                                list(match)) or 0.0
    else:
        seconds = session_step_ms_less.seconds(context, list(marks))
    if seconds <= 0:
        return None
    cell = context["cell"]
    t, cfg = cell.traffic, cell.config
    turn = (t["batch"], t["context_len"], t["n_new"])
    if work == "kv":
        flops = counts_cmdaplus.attn_flops(cfg, *turn)
        nbytes = counts_cmdaplus.attn_bytes(cfg, *turn)
    elif work == "moe":
        flops = 0.0
        nbytes = counts_cmdaplus.moe_bytes(cfg, t["batch"], t["n_new"])
    else:
        raise SystemExit(f"cmdaplus_roofline: unknown work {work!r}")
    peaks = harness.peaks_of(context["device"])
    by_flops = flops / peaks["bf16_flops_per_s"]
    by_bytes = nbytes / peaks["hbm_bytes_per_s"]
    least = max(by_flops, by_bytes) * context["calls"]
    print(f"cmdaplus_roofline {work}: bound by "
          f"{'FLOPs' if by_flops >= by_bytes else 'bytes'} (FLOPs "
          f"{by_flops * context['calls']:.6f} s, bytes "
          f"{by_bytes * context['calls']:.6f} s), least {least:.6f} s, "
          f"measured {seconds:.6f} s", file=sys.stderr)
    return 100.0 * least / seconds
