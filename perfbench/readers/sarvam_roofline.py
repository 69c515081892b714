"""A layer's share of its roofline in the sarvam-105b session cell: the
least time the chip could take for the operations and bytes that the
traced turns need (`counts_sarvam`: for latent attention every cached
row up to the position once, and both matmuls over it; for the expert
layer the bytes of the experts a step is expected to touch under
uniform routing), the larger of FLOPs over peak FLOP/s and bytes over
peak bytes/s, over the device time under the program's scopes `marks`
less that under `less` (`lm.attn` holds the projections' `lm.mla`; what
is left is attention over the cache, whatever implements it). Beside
it on standard error, the summed time of the trace's events matching
`match` (the kernel alone), so that the two can be told apart. Nothing
under the scopes in the trace: nothing returned."""

import sys

from perfbench import counts_sarvam, harness, trace_reader
from perfbench.readers import session_step_ms_less


def read(context, work: str, marks: list, less: list = (), match: list = ()):
    seconds = session_step_ms_less.seconds(context, marks, less)
    if seconds <= 0:
        return None
    cell = context["cell"]
    t, cfg = cell.traffic, cell.config
    turn = (t["batch"], t["context_len"], t["n_new"])
    if work == "latent_attn":
        flops = counts_sarvam.latent_attn_flops(cfg, *turn)
        nbytes = counts_sarvam.latent_attn_bytes(cfg, *turn)
    elif work == "moe_experts":
        flops = 0.0
        nbytes = counts_sarvam.moe_expert_bytes(cfg, t["batch"], t["n_new"])
    else:
        raise SystemExit(f"sarvam_roofline: unknown work {work!r}")
    peaks = harness.peaks_of(context["device"])
    by_flops = flops / peaks["bf16_flops_per_s"]
    by_bytes = nbytes / peaks["hbm_bytes_per_s"]
    least = max(by_flops, by_bytes) * context["calls"]
    kernel = trace_reader.matching_seconds(context.get("trace") or {},
                                           list(match)) if match else None
    print(f"sarvam_roofline {work}: bound by "
          f"{'FLOPs' if by_flops >= by_bytes else 'bytes'} (FLOPs "
          f"{by_flops * context['calls']:.6f} s, bytes "
          f"{by_bytes * context['calls']:.6f} s), least {least:.6f} s, "
          f"measured {seconds:.6f} s"
          + (f", events matching {list(match)} {kernel:.6f} s"
             if kernel else ""), file=sys.stderr)
    return 100.0 * least / seconds
