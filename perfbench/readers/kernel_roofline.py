"""A kernel's share of its roofline: the least time the chip could take
for the operations and bytes that the traced calls need (the larger of
FLOPs over peak FLOP/s and bytes over peak bytes/s), over the summed
device time of the kernel's events in the trace. Nothing found in the
trace: nothing returned."""

import sys

from perfbench import counts, harness, trace_reader


def read(context, work: str, match: list):
    reduced = context.get("trace") or {}
    seconds = trace_reader.matching_seconds(reduced, match)
    if not seconds:
        return None
    cell = context["cell"]
    t = cell.traffic
    if work == "flash_train":
        per_chip_rows = t["batch"] / context["chips"]
        flops = counts.flash_train_flops(cell.config, per_chip_rows,
                                         t["seq_len"])
        nbytes = counts.flash_train_bytes(cell.config, per_chip_rows,
                                          t["seq_len"])
    elif work == "decode_kernel":
        flops = counts.decode_kernel_flops(cell.config, t["batch"],
                                           t["prompt_len"], t["n_new"])
        nbytes = counts.decode_kernel_bytes(cell.config, t["batch"],
                                            t["prompt_len"], t["n_new"])
    else:
        raise SystemExit(f"kernel_roofline: unknown work {work!r}")
    peaks = harness.peaks_of(context["device"])
    by_flops = flops / peaks["bf16_flops_per_s"]
    by_bytes = nbytes / peaks["hbm_bytes_per_s"]
    least = max(by_flops, by_bytes) * context["calls"]
    print(f"kernel_roofline {work}: bound by "
          f"{'FLOPs' if by_flops >= by_bytes else 'bytes'}, least "
          f"{least:.6f} s, kernel {seconds:.6f} s", file=sys.stderr)
    return 100.0 * least / seconds
