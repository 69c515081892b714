"""A layer's or kernel's share of its roofline in a session cell: the
least time the chip could take for the operations and bytes that the
traced turns need (the larger of FLOPs over peak FLOP/s and bytes over
peak bytes/s; `counts_dsv32`; for the expert layer the bytes of the
experts that a step is expected to touch under uniform routing) over
the device time under the program's scopes `marks`, or of the trace's
events matching `match`. The same work whatever implements it and
whatever it counted. Nothing found in the trace: nothing
returned."""

import sys

from perfbench import counts_dsv32, harness, trace_reader


def read(context, work: str, marks: list = (), match: list = ()):
    if match:
        seconds = trace_reader.matching_seconds(context.get("trace") or {},
                                                match)
    else:
        sub = context.get("sub_scopes") or {}
        seconds = sum(sub.get(m, 0.0) for m in marks)
    if not seconds:
        return None
    cell = context["cell"]
    t, cfg = cell.traffic, cell.config
    turn = (t["batch"], t["context_len"], t["n_new"])
    if work == "moe_experts":
        flops = 0.0
        nbytes = counts_dsv32.moe_expert_bytes(cfg, t["batch"], t["n_new"])
    elif work == "sparse_attn":
        flops = counts_dsv32.sparse_attn_flops(cfg, *turn)
        nbytes = counts_dsv32.sparse_attn_bytes(cfg, *turn)
    elif work == "gqa_decode_kernel":
        flops = counts_dsv32.gqa_kernel_flops(cfg, *turn)
        nbytes = counts_dsv32.gqa_kernel_bytes(cfg, *turn)
    else:
        raise SystemExit(f"session_roofline: unknown work {work!r}")
    peaks = harness.peaks_of(context["device"])
    by_flops = flops / peaks["bf16_flops_per_s"]
    by_bytes = nbytes / peaks["hbm_bytes_per_s"]
    least = max(by_flops, by_bytes) * context["calls"]
    print(f"session_roofline {work}: bound by "
          f"{'FLOPs' if by_flops >= by_bytes else 'bytes'}, least "
          f"{least:.6f} s, measured {seconds:.6f} s", file=sys.stderr)
    return 100.0 * least / seconds
