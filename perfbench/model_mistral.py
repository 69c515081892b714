"""What the `session` driver asks of a model family, for the dense
grouped-query configurations (`model_type: mistral`): the files the
benchmark had. A configuration names another family's module under
`session_model`; without the key it is this one."""

from __future__ import annotations

import numpy as np

from perfbench import compare, reference, weights
from perfbench.drivers.train import program_config  # noqa: F401

make_params = weights.make_params
# `decode_from(stats=True)` has nothing to count here
COUNTERS = False


def judge(cell, seed: int, session, picks: list) -> dict:
    """`reference.decode_logit_gaps` over context + fed id + served
    tokens of every checked (request, row)."""
    t = cell.traffic
    context = session.context()
    rows = np.stack([np.concatenate([context[row], session.fed(r)[row:row + 1],
                                     session.outputs[r][row]])
                     for r, row in picks])
    gaps = reference.decode_logit_gaps(cell.config, seed, rows,
                                       t["context_len"] + 1)
    return compare.decode(gaps["served"])
