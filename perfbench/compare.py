"""The comparison that decides `correct`: what the timed path produced
against what the plain reference gives, one number each with a limit of
its own (`limits/<workload>.json`; `PERF.md` has the readings that each
was set from)."""

from __future__ import annotations

import statistics

import numpy as np

# a leaf whose first gradient in the reference is under this share of the
# median leaf's moves under Adam by round-off alone: left out of the change
NOUGHT_GRADIENT = 1e-3


def worst_leaf(prog: dict, ref: dict, skip=()) -> tuple:
    """The widest gap between the program's norm and the reference's over
    the leaves, each measured against the reference's norm of that leaf
    or of the median leaf, whichever is larger. Returns (gap, leaf)."""
    if set(prog) != set(ref):
        raise ValueError(f"leaves differ: {sorted(set(prog) ^ set(ref))}")
    median = statistics.median(ref.values())
    gaps = {k: abs(prog[k] - ref[k]) / max(ref[k], median)
            for k in ref if k not in skip}
    leaf = max(gaps, key=gaps.get)
    return gaps[leaf], leaf


def worst_leaf_error(prog: dict, ref: dict) -> tuple:
    """The largest norm of the difference between the program's sampled
    rows of a leaf and the reference's, against the norm of the
    reference's rows of that leaf or of the median leaf. Returns (error,
    leaf). Unlike a gap of norms this grows in proportion to the noise
    that a lower precision adds."""
    norm = lambda a: float(np.linalg.norm(a.astype(np.float64)))  # noqa: E731
    size = {k: norm(v) for k, v in ref.items()}
    median = statistics.median(size.values())
    errors = {k: norm(prog[k].astype(np.float64) - ref[k])
              / max(size[k], median) for k in ref}
    leaf = max(errors, key=errors.get)
    return errors[leaf], leaf


def train(prog: dict, ref: dict) -> tuple:
    """Readings of a training cell: of the first gradient the worst
    leaf's gap of norms and the worst leaf's error over the sampled rows,
    and of the parameters' change over the followed steps the worst
    leaf's gap of norms. The followed steps' losses go into the notes:
    the program returns them in bfloat16, whose spacing at 11 is 0.6%,
    so no limit on them could fail anything but a sound run. Returns
    (readings, notes)."""
    readings = {}
    loss_gaps = [abs(lp - lr) / abs(lr)
                 for lp, lr in zip(prog["loss"], ref["loss"])]
    median = statistics.median(ref["grad1"].values())
    nought = sorted(k for k, g in ref["grad1"].items()
                    if g < NOUGHT_GRADIENT * median)
    readings["grad1_worst_leaf"], g_leaf = worst_leaf(prog["grad1"],
                                                      ref["grad1"])
    readings["grad1_error_worst_leaf"], e_leaf = worst_leaf_error(
        prog["sample1"], ref["sample1"])
    readings["delta_worst_leaf"], d_leaf = worst_leaf(prog["delta"],
                                                      ref["delta"], nought)
    return readings, {"grad1_leaf": g_leaf, "grad1_error_leaf": e_leaf,
                      "delta_leaf": d_leaf, "left_out_of_delta": nought,
                      "loss_gaps": loss_gaps}


def decode(gaps) -> dict:
    """Readings of a serving cell, over the sampled tokens: the widest gap
    by which a served token's logit lies below the reference's best, which
    swings from seed to seed by its nature, and the mean gap, which is
    steady and grows with the square of the logits' noise."""
    return {"served_logit_gap": float(gaps.max()),
            "served_logit_gap_mean": float(gaps.mean())}
