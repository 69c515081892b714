"""The indexer's selection alone, on the chip: `ops/sparse_mla.select_top_k`
over (rows, 32832) float32 scores, top 2048, by way. The readings in
`ops/sparse_mla.py`'s comments and in PERF.md section 6 (PR 33) are this
script's.

A reading is the device's busy time a call: every device operation of a
scan of N calls over N different score arrays, from the profiler's
trace. Ways: `few` (no sort, no gather: what a decode step runs),
`top_k` (`lax.top_k`), `few_cumsum` (`few` with the chip's own running
sums in place of the triangle products) and, with `--old FILE`, the
few-rows way of an earlier `sparse_mla.py` (say `git show
1f7cfe2:lua_mapreduce_tpu/ops/sparse_mla.py > FILE`). Every way is
checked to give the first one's set.

Usage (TPU only): python benchmarks/selection_bench.py [--old FILE]
    [--ways few,top_k] [--calls 32] [ROWS ...]     # eights of rows: 8 64
Prints one JSON line a (rows, way) and its largest device operations.
"""

from __future__ import annotations

import argparse
import importlib.util
import json
import os
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from lua_mapreduce_tpu.ops import sparse_mla
from perfbench import harness, trace_reader

S, K, SEEN = 32832, 2048, 32768


def scores_of(rows: int, calls: int):
    """(calls, 8, rows / 8, S), the session cell's layouts (8 sessions:
    one query each in a decode step, 8 in a block of the prefill; the
    sort's time follows the layout): query j of call i sees SEEN + i + j
    positions."""
    q = rows // 8
    s = jax.random.normal(jax.random.PRNGKey(0), (calls, 8, q, S))
    last = SEEN + jnp.arange(calls)[:, None, None] + jnp.arange(q)[:, None]
    return jnp.where(jnp.arange(S) <= last[:, None], s, -jnp.inf)


def few_of(module):
    """`module.select_top_k`'s few-rows way at any number of rows."""
    def select(scores, k):
        saved, module._FEW_ROWS = module._FEW_ROWS, scores.size
        try:
            return module.select_top_k(scores, k)
        finally:
            module._FEW_ROWS = saved
    return select


def with_cumsum(scores, k):
    def counts(flags):
        rows = flags.reshape(-1, flags.shape[-1])
        rows = jnp.pad(rows, ((0, 0), (0, -rows.shape[-1] % 128)))
        inside = jnp.cumsum(rows.reshape(rows.shape[0], -1, 128), axis=-1,
                            dtype=jnp.float32)
        return inside, jnp.cumsum(inside[..., -1], axis=-1)
    saved, sparse_mla._running_counts = sparse_mla._running_counts, counts
    try:
        return few_of(sparse_mla)(scores, k)
    finally:
        sparse_mla._running_counts = saved


def sorted_way(scores, k):
    vals, idx = lax.top_k(scores, k)
    return idx.astype(jnp.int32), vals > -jnp.inf


def reading(select, scores, work: str):
    """(result, us a call, the largest device operations in us a call)."""
    run = jax.jit(lambda x: lax.scan(lambda c, s: (c, select(s, K)), 0, x)[1])
    out = jax.block_until_ready(run(scores))

    def call():
        with jax.profiler.TraceAnnotation("pb.call"):
            return jax.block_until_ready(run(scores)), None
    _, trace_file = harness.traced(call, work)
    reduced = trace_reader.reduce(trace_reader.load(trace_file))
    ops = sorted(trace_reader.grouped(reduced["ops_s"]).items(),
                 key=lambda kv: -kv[1])[:8]
    n = scores.shape[0]
    return (out, 1e6 * reduced["busy_s"] / n,
            [(name, round(1e6 * s / n, 2)) for name, s in ops])


def as_sets(result):
    idx, valid = map(np.asarray, result)
    return np.sort(np.where(valid, idx, -1), axis=-1)


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("rows", nargs="*", type=int, default=[8, 64])
    parser.add_argument("--old")
    parser.add_argument("--ways", default="few,top_k,few_cumsum")
    parser.add_argument("--calls", type=int, default=32)
    args = parser.parse_args()
    if jax.devices()[0].platform != "tpu":
        sys.exit("a device time comes from the chip: run this on a TPU")
    ways = {"few": few_of(sparse_mla), "top_k": sorted_way,
            "few_cumsum": with_cumsum}
    names = args.ways.split(",")
    if args.old:
        spec = importlib.util.spec_from_file_location("old_sparse", args.old)
        old = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(old)
        ways["old"] = few_of(old)
        names.append("old")
    for rows in args.rows:
        scores, first = scores_of(rows, args.calls), None
        for name in names:
            jax.clear_caches()
            with tempfile.TemporaryDirectory() as work:
                out, us, ops = reading(ways[name], scores, work)
            first = as_sets(out) if first is None else first
            print(json.dumps({
                "rows": rows, "way": name, "us_a_call": round(us, 1),
                "same_set_as_first": bool(np.array_equal(as_sets(out),
                                                         first)),
                "ops_us_a_call": ops}), flush=True)


if __name__ == "__main__":
    main()
