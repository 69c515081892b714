"""The KDA decode kernel in the Kimi-Linear-48B-A3B-Instruct session
cell, from the traced turns: the summed device time of the trace's
events matching `match` (the kernel's pinned name, whatever layer calls
it), as `report` says:

- `kernel_ms`: milliseconds of them a scanned position;
- `roofline_pct`: the least time the chip could take for what a turn's
  delta rule needs (`counts_kimilinear`: every (session, head) state
  read once and written once in every KDA layer at every step, and its
  rows; the larger of FLOPs over peak FLOP/s and bytes over peak
  bytes/s) over that time: the same work whatever implements it, so a
  kernel that read a state twice reads lower.

No such event in the trace (a program without the kernel): nothing
returned."""

import sys

from perfbench import counts_kimilinear, harness, trace_reader


def read(context, report: str, match: list):
    kernel = trace_reader.matching_seconds(context.get("trace") or {},
                                           list(match))
    if not kernel:
        return None
    cell = context["cell"]
    t, cfg = cell.traffic, cell.config
    if report == "kernel_ms":
        return 1e3 * kernel / (context["calls"] * t["n_new"])
    if report != "roofline_pct":
        raise SystemExit(f"kimilinear_kda: unknown report {report!r}")
    peaks = harness.peaks_of(context["device"])
    by_flops = (counts_kimilinear.kda_flops(cfg, t["batch"], t["n_new"])
                / peaks["bf16_flops_per_s"])
    by_bytes = (counts_kimilinear.kda_bytes(cfg, t["batch"], t["n_new"])
                / peaks["hbm_bytes_per_s"])
    least = max(by_flops, by_bytes) * context["calls"]
    print(f"kimilinear_kda: bound by "
          f"{'FLOPs' if by_flops >= by_bytes else 'bytes'} (FLOPs "
          f"{by_flops * context['calls']:.6f} s, bytes "
          f"{by_bytes * context['calls']:.6f} s), least {least:.6f} s, "
          f"events matching {list(match)} {kernel:.6f} s", file=sys.stderr)
    return 100.0 * least / kernel
