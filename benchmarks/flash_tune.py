"""Block-size sweep for the fused flash-attention kernels.

The forward and backward default to (block_q, block_k) = (128, 128);
this sweep times candidate schedules on the real chip for the shapes
the LM family actually runs — forward AND fwd+bwd (the training path
exercises the dq/dkv kernels, whose best blocks need not match the
forward's). Same elision-proof measurement discipline as
kernel_bench._measure_op; evidence goes to stdout as JSON for baking
winners into ops/attention.py defaults.

Usage: python benchmarks/flash_tune.py [--seqs 2048,4096]
"""

from __future__ import annotations

import argparse
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# the sweep's candidate (block_q, block_k) schedules — module-level so
# tests/test_tpu_lowering.py exports every one (fwd AND grad) and an
# illegal candidate can never burn a hardware window
CANDIDATES = [(64, 128), (128, 128), (128, 256), (256, 128), (256, 256),
              (128, 512), (512, 128), (256, 512), (512, 256), (512, 512),
              # round-3 sweep: (512, 512) won everywhere; probe whether
              # the trend continues (1 MB→2 MB f32 score tile)
              (512, 1024), (1024, 512)]
sys.path.insert(0, REPO)

from benchmarks.kernel_bench import _call_overhead, _measure_op  # noqa: E402


def time_config(seq, bq, bk, grad, target_s=0.35, b=4, heads=8, d=128):
    import jax
    import jax.numpy as jnp

    from lua_mapreduce_tpu.ops.attention import flash_attention
    from lua_mapreduce_tpu.utils.roofline import peak_flops_per_s

    q = jax.random.normal(jax.random.PRNGKey(0), (b, seq, heads, d),
                          jnp.bfloat16)
    k = jax.random.normal(jax.random.PRNGKey(1), (b, seq, heads, d),
                          jnp.bfloat16)
    v = jax.random.normal(jax.random.PRNGKey(2), (b, seq, heads, d),
                          jnp.bfloat16)
    mult = 14.0 if grad else 4.0          # bwd ≈ 2.5x fwd matmul work
    flops = mult * b * heads * seq * seq * d * 0.5     # causal
    inner_cap = max(16, int(2.0 * target_s * peak_flops_per_s() / flops))

    if grad:
        def loss(q, k, v):
            out = flash_attention(q, k, v, causal=True, backend="pallas",
                                  block_q=bq, block_k=bk)
            return jnp.sum(out.astype(jnp.float32) ** 2)

        def run(q, k, v):
            g = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
            return sum(x.astype(jnp.float32).sum() for x in g).reshape(1)
    else:
        def run(q, k, v):
            return flash_attention(q, k, v, causal=True,
                                   backend="pallas", block_q=bq,
                                   block_k=bk)

    per_op, _ = _measure_op(run, (q, k, v), 0, inner_cap, target_s,
                            _call_overhead())
    return per_op, flops


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--seqs", default="2048,4096")
    ap.add_argument("--install", action="store_true",
                    help="write results/flash_tune.json (full rows + "
                         "provenance) instead of leaving installation "
                         "to the caller; only a real-TPU run installs")
    args = ap.parse_args()

    from lua_mapreduce_tpu.utils.jax_env import (place_compile_cache,
                                                 require_tpu)
    place_compile_cache()
    require_tpu("flash_tune.py")
    import jax

    cands = CANDIDATES
    results = {}
    for seq in (int(s) for s in args.seqs.split(",")):
        for grad in (False, True):
            tag = f"s{seq}_{'fwdbwd' if grad else 'fwd'}"
            best, rows = None, []
            for bq, bk in cands:
                try:
                    dt, flops = time_config(seq, bq, bk, grad)
                except Exception as e:
                    rows.append({"blocks": [bq, bk],
                                 "error": str(e)[:80]})
                    continue
                tf = flops / dt / 1e12
                rows.append({"blocks": [bq, bk],
                             "ms": round(dt * 1e3, 3),
                             "tflops": round(tf, 1)})
                print(f"{tag} ({bq:4d},{bk:4d}) {dt * 1e3:8.3f} ms "
                      f"{tf:6.1f} TF/s", flush=True)
                if best is None or dt < best[1]:
                    best = ((bq, bk), dt)
            results[tag] = ({"best_blocks": best[0],
                             "best_ms": round(best[1] * 1e3, 3),
                             "all": rows} if best else
                            {"error": "no runnable config", "all": rows})
    print(json.dumps({k: {kk: vv for kk, vv in v.items() if kk != "all"}
                      for k, v in results.items()}))
    if args.install:
        import time
        results["provenance"] = (
            "benchmarks/flash_tune.py --install, "
            + jax.devices()[0].device_kind + ", "
            + time.strftime("%Y-%m-%d %H:%M UTC", time.gmtime())
            + "; candidates swept fwd AND fwdbwd per sequence length; "
            "ops/attention.py's _DEFAULT_BLOCK_Q/K must match the "
            "winners (tests/test_policy_artifact.py).")
        dest = os.path.join(REPO, "benchmarks", "results",
                            "flash_tune.json")
        with open(dest + ".tmp", "w") as f:
            json.dump(results, f, indent=1)
            f.write("\n")
        os.replace(dest + ".tmp", dest)
        print(f"installed {dest}", file=sys.stderr)


if __name__ == "__main__":
    main()
