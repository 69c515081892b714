"""Attention over the caches in the Phi-4-mini-flash-reasoning session
cell, from the traced turns: the summed device time of the trace's
events matching `match` (the attention kernel's pinned name, whatever
layer calls it), as `report` says:

- `kernel_ms`: milliseconds of them a scanned position;
- `roofline_pct`: the least time the chip could take for what a turn's
  attention needs (`counts_phi4flash`: every key and value row up to the
  position once for each reader of the shared cache, the window's rows
  for each window layer; the larger of FLOPs over peak FLOP/s and bytes
  over peak bytes/s) over that time: the same work whatever implements
  it, so a kernel that reads a cache twice reads lower;
- `rest_ms`: the device time under the program's scopes `marks` less
  those events, a scanned position: the mixers' projections, the
  state-space step, the gated units, the norms.

No such event in the trace (a program without the kernel): nothing
returned."""

import sys

from perfbench import counts_phi4flash, harness, trace_reader
from perfbench.readers import session_step_ms_less


def read(context, report: str, match: list, marks: list = ()):
    kernel = trace_reader.matching_seconds(context.get("trace") or {},
                                           list(match))
    if not kernel:
        return None
    cell = context["cell"]
    t, cfg = cell.traffic, cell.config
    steps = context["calls"] * t["n_new"]
    if report == "kernel_ms":
        return 1e3 * kernel / steps
    if report == "rest_ms":
        under = session_step_ms_less.seconds(context, list(marks))
        return 1e3 * (under - kernel) / steps if under > kernel else None
    if report != "roofline_pct":
        raise SystemExit(f"phi4flash_attn: unknown report {report!r}")
    turn = (t["batch"], t["context_len"], t["n_new"])
    peaks = harness.peaks_of(context["device"])
    by_flops = counts_phi4flash.attn_flops(cfg, *turn) \
        / peaks["bf16_flops_per_s"]
    by_bytes = counts_phi4flash.attn_bytes(cfg, *turn) \
        / peaks["hbm_bytes_per_s"]
    least = max(by_flops, by_bytes) * context["calls"]
    print(f"phi4flash_attn: bound by "
          f"{'FLOPs' if by_flops >= by_bytes else 'bytes'} (FLOPs "
          f"{by_flops * context['calls']:.6f} s, bytes "
          f"{by_bytes * context['calls']:.6f} s), least {least:.6f} s, "
          f"events matching {list(match)} {kernel:.6f} s", file=sys.stderr)
    return 100.0 * least / kernel
