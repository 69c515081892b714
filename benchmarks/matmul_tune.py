"""Block-size sweep for the Pallas MXU matmul (ops/matmul.py).

The default (256, 256, 256) schedule is HBM-bandwidth-bound at large
sizes: per-tile traffic scales as m·n·k·itemsize·(1/bm + 1/bn), so at
8192³ bf16 the 256-blocks move ~8.6 GB — a ~64 TF/s roofline on a v5e
(~820 GB/s), well under the 197 TF/s MXU peak. Wider M/N blocks raise
arithmetic intensity until the kernel is compute-bound. This sweep times
candidate (bm, bn, bk) schedules on the real chip across the sizes
kernel_bench.py reports, prints a table, and is the evidence for the
defaults baked into ops/matmul.py.

Usage: python benchmarks/matmul_tune.py [--sizes 4096,8192]
"""

from __future__ import annotations

import argparse
import itertools
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from benchmarks.kernel_bench import _call_overhead, _measure_op  # noqa: E402


def candidates():
    """Candidate (bm, bn, bk) schedules under the ~14 MB VMEM budget
    (double-buffered bf16 A/B tiles + f32 accumulator + out tile) —
    module-level so tests/test_tpu_lowering.py exports every one and an
    illegal candidate can never burn a hardware window."""
    out = []
    for bm, bn in itertools.product((256, 512, 768, 1024), repeat=2):
        for bk in (256, 512, 1024, 2048):
            vmem = (2 * (bm * bk + bk * bn) * 2        # A,B bf16 ×2 buffers
                    + bm * bn * 4 + bm * bn * 2)       # acc f32 + out
            if vmem <= 14 * 2**20:
                out.append((bm, bn, bk))
    return out


def time_config(n, bm, bn, bk, target_s=0.35):
    """Per-op seconds for an n³ bf16 matmul with the given blocks —
    measured through kernel_bench._measure_op, the single implementation
    of the overhead-subtracted / elision-proof discipline (no second
    hand-rolled timing loop to drift out of sync)."""
    import jax
    import jax.numpy as jnp

    from lua_mapreduce_tpu.ops.matmul import _matmul_pallas
    from lua_mapreduce_tpu.utils.roofline import peak_flops_per_s

    a = jax.random.normal(jax.random.PRNGKey(0), (n, n), jnp.bfloat16)
    b = jax.random.normal(jax.random.PRNGKey(1), (n, n), jnp.bfloat16)
    flops = 2.0 * n**3
    inner_cap = max(16, int(2.0 * target_s * peak_flops_per_s() / flops))

    def run(a, b):
        return _matmul_pallas(a, b, block_m=bm, block_n=bn, block_k=bk)

    per_op, _ = _measure_op(run, (a, b), 0, inner_cap, target_s,
                            _call_overhead())
    return per_op


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--sizes", default="4096,8192")
    args = ap.parse_args()

    from lua_mapreduce_tpu.utils.jax_env import (place_compile_cache,
                                                 require_tpu)
    place_compile_cache()
    require_tpu("matmul_tune.py")

    sizes = [int(s) for s in args.sizes.split(",")]
    # candidate schedules: (bm, bn, bk); VMEM budget ~16 MB on v5e with
    # double-buffered A/B tiles + f32 accumulator + out tile
    cands = candidates()

    results = {}
    for n in sizes:
        best = None
        rows = []
        for bm, bn, bk in cands:
            if bm > n or bn > n or bk > n:
                continue
            try:
                dt = time_config(n, bm, bn, bk)
            except Exception as e:                     # OOM/compile fail
                rows.append({"blocks": [bm, bn, bk], "error": str(e)[:80]})
                continue
            tf = 2 * n**3 / dt / 1e12
            rows.append({"blocks": [bm, bn, bk], "ms": round(dt * 1e3, 3),
                         "tflops": round(tf, 1)})
            print(f"n={n} ({bm:4d},{bn:4d},{bk:4d}) "
                  f"{dt * 1e3:8.3f} ms  {tf:6.1f} TF/s", flush=True)
            if best is None or dt < best[1]:
                best = ((bm, bn, bk), dt)
        if best is None:                # all candidates skipped or failed
            results[n] = {"error": "no runnable block config", "all": rows}
            continue
        results[n] = {"best_blocks": best[0], "best_ms": round(best[1] * 1e3, 3),
                      "best_tflops": round(2 * n**3 / best[1] / 1e12, 1),
                      "all": rows}
    print(json.dumps({str(k): {kk: vv for kk, vv in v.items() if kk != "all"}
                      for k, v in results.items()}))


if __name__ == "__main__":
    main()
