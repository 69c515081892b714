"""The whole call's share of the chips' peak: model FLOPs of the calls
that the window completed, over the window's time and the peak of every
chip used. Recomputed operations are not counted."""

from perfbench import counts, harness


def read(context, work: str):
    cell, loop = context["cell"], context["loop"]
    t = cell.traffic
    if work == "train_step":
        per_call = (t["batch"] * t["seq_len"]
                    * counts.train_flops_per_token(cell.config, t["seq_len"]))
    elif work == "decode_request":
        per_call = counts.decode_request_flops(cell.config, t["batch"],
                                               t["prompt_len"], t["n_new"])
    else:
        raise SystemExit(f"model_mfu: unknown work {work!r}")
    peak = harness.peaks_of(context["device"])["bf16_flops_per_s"]
    return (100.0 * per_call * context["calls"]
            / (loop["window_s"] * context["chips"] * peak))
