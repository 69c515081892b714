"""The Kimi-Linear-48B-A3B-Instruct session cell's share of the chip's
peak: model FLOPs of the turns that the window completed
(`counts_kimilinear.turn_flops`: the scanned positions only, nothing of
the caches that were there) over the window's time and the peak of
every chip used."""

from perfbench import counts_kimilinear, harness


def read(context):
    cell, loop = context["cell"], context["loop"]
    t = cell.traffic
    per_call = counts_kimilinear.turn_flops(cell.config, t["batch"],
                                            t["context_len"], t["n_new"])
    peak = harness.peaks_of(context["device"])["bf16_flops_per_s"]
    return (100.0 * per_call * context["calls"]
            / (loop["window_s"] * context["chips"] * peak))
