"""Sprint phase G: what bounds the LeNet-5/CIFAR train step? (VERDICT
r4 weak-4: 33.64 ms/step at b=1024 — 0.06% MFU — has no ceiling
statement.)

The step's model FLOPs are ~4.0e9 (b=1024 × 3.91e6 flops/example):
0.02 ms at peak MXU rate. Its unpadded activation traffic is a few
hundred MB/s-equivalent: well under 1 ms at HBM bandwidth. Neither
roofline explains 33.6 ms, so the time must live in the structural
mismatch between LeNet's geometry and the hardware's tiles — c_out of
6/16 against 128 MXU columns (≤5-13% systolic fill even with a perfect
schedule), channel counts of 3/6/16 against 128-lane vector layouts
(up to 21× padded bandwidth), and the long chain of tiny fused ops.
This script measures each stage of the training step separately
on-chip, with XLA's compiled per-program bytes/FLOPs accounting next
to each timing, so DESIGN can state WHICH of those mismatches owns the
milliseconds and what the architecture's ceiling actually is. Writes
benchmarks/results/lenet_roofline.json.

Usage: python benchmarks/lenet_roofline.py
"""

from __future__ import annotations

import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from benchmarks.kernel_bench import _call_overhead, _measure_op  # noqa: E402

OUT = os.path.join(REPO, "benchmarks", "results", "lenet_roofline.json")


def profile(batch=1024, dtype_name="bfloat16", target_s=0.35) -> dict:
    import jax
    import jax.numpy as jnp

    from lua_mapreduce_tpu.models import lenet
    from lua_mapreduce_tpu.ops.conv import conv2d
    from lua_mapreduce_tpu.ops.pool import maxpool2d

    dtype = jnp.dtype(dtype_name)
    params = lenet.init_lenet(jax.random.PRNGKey(0), dtype=dtype)
    x = jax.random.normal(jax.random.PRNGKey(1),
                          (batch, 32, 32, 3), dtype)
    y = jax.random.randint(jax.random.PRNGKey(2), (batch,), 0, 10)
    overhead = _call_overhead()
    # CPU smoke runs exercise the Pallas path through the interpreter
    # (the compiled kernel only lowers on TPU)
    pallas = ("pallas" if jax.default_backend() == "tpu"
              else "pallas_interpret")
    results = {"device_kind": jax.devices()[0].device_kind,
               "config": f"lenet5_cifar b{batch} {dtype_name}",
               "flops_per_step": batch * lenet.flops_per_example()}

    def timed(name, fn, args, i0=0, cost=True):
        def run(*a):
            return jnp.asarray(fn(*a), jnp.float32).reshape(-1)[:1]
        row = {}
        try:
            per_op, _ = _measure_op(run, args, i0, 512, target_s, overhead)
            row["ms"] = round(per_op * 1e3, 4)
        except Exception as e:
            row["error"] = f"{type(e).__name__}: {e}"[:200]
        if cost and "ms" in row:
            try:
                ca = jax.jit(fn).lower(*args).compile().cost_analysis()
                row["xla_flops"] = float(ca.get("flops", 0.0))
                row["xla_bytes"] = float(ca.get("bytes accessed", 0.0))
                if row["ms"] > 0:
                    row["achieved_GBps"] = round(
                        row["xla_bytes"] / (row["ms"] / 1e3) / 1e9, 1)
            except Exception as e:
                row["cost_error"] = f"{type(e).__name__}: {e}"[:120]
        results[name] = row
        print(f"{name}: {row}", file=sys.stderr)
        return row

    # --- the full training step's pieces ---
    def loss_fn(params, x, y):
        return lenet.nll_loss(params, x, y)

    timed("fwd_loss", loss_fn, (params, x, y), i0=1)
    timed("fwdbwd", lambda p, x, y: jax.tree_util.tree_reduce(
        lambda a, b: a + b.astype(jnp.float32).sum(),
        jax.grad(loss_fn)(p, x, y), jnp.float32(0)), (params, x, y),
        i0=1)

    # --- stage by stage (fwd) ---
    w1, b1 = params["c1_W"], params["c1_b"]
    timed("conv1_5x5_3to6", lambda x: conv2d(x, w1, b1, padding="VALID"),
          (x,))
    a1 = jnp.tanh(conv2d(x, w1, b1, padding="VALID"))
    timed("tanh_28x28x6", jnp.tanh, (a1,))
    timed("pool1_pallas", lambda a: maxpool2d(a, window=2,
                                              backend=pallas), (a1,))
    timed("pool1_xla", lambda a: maxpool2d(a, window=2,
                                           backend="xla"), (a1,))
    p1 = maxpool2d(a1, window=2)
    w2, b2 = params["c2_W"], params["c2_b"]
    timed("conv2_5x5_6to16", lambda p: conv2d(p, w2, b2,
                                              padding="VALID"), (p1,))
    a2 = jnp.tanh(conv2d(p1, w2, b2, padding="VALID"))
    timed("pool2_pallas", lambda a: maxpool2d(a, window=2,
                                              backend=pallas), (a2,))
    p2 = maxpool2d(a2, window=2)
    flat = p2.reshape(p2.shape[0], -1)

    def fc_stack(flat):
        h = flat
        for name, _d in lenet._FCS[:-1]:
            h = jnp.tanh(h @ params[f"{name}_W"] + params[f"{name}_b"])
        last = lenet._FCS[-1][0]
        return h @ params[f"{last}_W"] + params[f"{last}_b"]
    timed("fc_stack_400_120_84_10", fc_stack, (flat,))

    # --- remedies to test on-chip ---
    # 1) pool backend is policy "pallas"; is that right at c=6?
    #    (pool1_pallas vs pool1_xla above answers directly)
    # 2) wide-channel control: the SAME conv shape-class at c_in/c_out
    #    = 128 fills lanes and MXU columns — the gap to conv1/conv2 is
    #    the price of LeNet's geometry, not of the conv lowering
    xw = jax.random.normal(jax.random.PRNGKey(3),
                           (batch // 8, 28, 28, 128), dtype)
    ww = jax.random.normal(jax.random.PRNGKey(4),
                           (5, 5, 128, 128), dtype) * 0.05
    timed("control_conv_5x5_128to128_b128",
          lambda x: conv2d(x, ww, None, padding="VALID"), (xw,))
    return results


def main() -> int:
    from lua_mapreduce_tpu.utils.jax_env import (place_compile_cache,
                                                 require_tpu)
    place_compile_cache()
    require_tpu("lenet_roofline.py")

    results = profile()
    results["note"] = (
        "Per-stage decomposition of the lenet5_cifar_train_b1024 step "
        "(kernels.json: 33.64 ms). Stages are timed in isolation with "
        "XLA's compiled bytes/FLOPs next to each, so the DESIGN "
        "section can attribute the step to MXU-column underfill "
        "(c_out 6/16 vs 128), lane-padding bandwidth (c 3/6/16 vs 128 "
        "lanes), or small-op overhead — and state the geometry's "
        "ceiling. The 128-channel control conv is the same shape class "
        "with filled lanes/columns: the per-MAC gap between it and "
        "conv1/conv2 is LeNet's geometry tax, not the conv lowering's.")
    print(json.dumps(results, indent=1))
    with open(OUT, "w") as f:
        json.dump(results, f, indent=1)
        f.write("\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
