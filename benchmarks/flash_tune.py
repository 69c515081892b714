"""Block-size sweep for the fused flash-attention kernels.

Times every candidate (block_q, block_k) schedule on the real chip at
the shapes the benchmark's training cells run, the forward and the
gradient apart, and the three kernels apart (``flash_pallas``,
``flash_bwd_pallas_dq``, ``flash_bwd_pallas_dkv``): device time of each
kernel's events in a profiler trace, so the transposes and the loss
around a call are not in it. Beside every timing stands the tile
schedule it ran (``ops.attention.tile_classes``: dead tiles not visited,
interior tiles folded with no mask, edge tiles masked). The table is
the evidence ``ops/attention._resolve_blocks`` is written from
(``tests/test_policy_artifact.py`` holds the two together).

Usage: python benchmarks/flash_tune.py [--shapes train-1chip,ring-hop1]
           [--install]
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import sys
import tempfile

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# the sweep's candidate (block_q, block_k) schedules — module-level so
# tests/test_tpu_lowering.py exports every one (fwd AND grad) and an
# illegal candidate can never burn a hardware window
CANDIDATES = [(64, 128), (128, 128), (128, 256), (256, 128), (256, 256),
              (128, 512), (512, 128), (256, 512), (512, 256), (512, 512),
              (512, 1024), (1024, 512)]

# what the training cells hand the kernels (Mistral-7B: 32 heads over 8
# kv heads of 128, window 4096): name -> (batch, length, q_offset, lse).
# One chip folds 3 x 4096 in one call; a chip of the 2x2 (dp 2, sp 2)
# folds its 4 x 2048 against its own keys (hop 0) and against its ring
# neighbour's (hop 1, every tile interior), both through the lse path.
SHAPES = {"train-1chip": (3, 4096, 0, False),
          "ring-hop0": (4, 2048, 0, True),
          "ring-hop1": (4, 2048, 2048, True)}
HEADS, KV_HEADS, HEAD_DIM, WINDOW = 32, 8, 128, 4096
KERNELS = ("flash_pallas", "flash_bwd_pallas_dq", "flash_bwd_pallas_dkv")
CALLS = 5
sys.path.insert(0, REPO)


def kernel_us(run, args, calls: int = CALLS) -> dict:
    """Device microseconds a call of each of KERNELS inside ``run``,
    from a trace of ``calls`` calls (the first device's XLA Ops)."""
    import jax
    from jax.profiler import ProfileData

    jax.block_until_ready(run(*args))               # compile + warm
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with tempfile.TemporaryDirectory() as d:
        jax.profiler.start_trace(d, profiler_options=opts)
        try:
            for _ in range(calls):
                jax.block_until_ready(run(*args))
        finally:
            jax.profiler.stop_trace()
        path, = glob.glob(os.path.join(d, "plugins", "profile", "*",
                                       "*.xplane.pb"))
        planes = [p for p in ProfileData.from_file(path).planes
                  if p.name == "/device:TPU:0"]
    spent = dict.fromkeys(KERNELS, 0.0)
    for line in planes[0].lines:
        if line.name != "XLA Ops":
            continue
        for ev in line.events:
            # "%flash_bwd_pallas_dq.3 = ..." -> the kernel's own name
            name = ev.name.split(" ")[0].lstrip("%").split(".")[0]
            if name in spent:
                spent[name] += ev.duration_ns / 1e3 / calls
    return {k: v for k, v in spent.items() if v}


def time_config(shape: str, bq, bk) -> dict:
    """One candidate at one shape: the forward's kernel alone and the
    gradient's three, us a call, and the tiles a (batch, head) row is
    made of at these blocks."""
    import jax
    import jax.numpy as jnp

    from lua_mapreduce_tpu.ops.attention import (flash_attention,
                                                 tile_classes)

    b, l, q_offset, lse = SHAPES[shape]
    q = jax.random.normal(jax.random.PRNGKey(0), (b, l, HEADS, HEAD_DIM),
                          jnp.bfloat16)
    k, v = (jax.random.normal(jax.random.PRNGKey(i),
                              (b, l, KV_HEADS, HEAD_DIM), jnp.bfloat16)
            for i in (1, 2))

    def attend(q, k, v):
        out = flash_attention(q, k, v, causal=True, backend="pallas",
                              block_q=bq, block_k=bk, window=WINDOW,
                              q_offset=q_offset, return_lse=lse)
        return out if lse else (out,)

    def loss(q, k, v):
        return sum(jnp.sum(x.astype(jnp.float32) ** 2)
                   for x in attend(q, k, v))

    fwd = kernel_us(jax.jit(attend), (q, k, v))
    grad = kernel_us(jax.jit(jax.grad(loss, argnums=(0, 1, 2))),
                     (q, k, v))
    dead, interior, edge = tile_classes(
        l, l, bq, bk, True, WINDOW, q_offset)
    live = (interior + edge) * b * HEADS
    row = {"blocks": [bq, bk], "tiles": [dead, interior, edge],
           "fwd_us": round(fwd["flash_pallas"], 1),
           "grad_us": {k: round(v, 1) for k, v in grad.items()},
           "fwdbwd_us": round(sum(grad.values()), 1)}
    if live:
        row["us_a_live_tile"] = {k: round(v / live, 3)
                                 for k, v in grad.items()}
    return row


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--shapes", default=",".join(SHAPES))
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

    results = {}
    for shape in args.shapes.split(","):
        rows = []
        for bq, bk in CANDIDATES:
            try:
                row = time_config(shape, bq, bk)
            except Exception as e:
                row = {"blocks": [bq, bk], "error": str(e)[:80]}
            rows.append(row)
            print(shape, json.dumps(row), flush=True)
        ran = [r for r in rows if "error" not in r]
        results[shape] = {"all": rows}
        if ran:
            for key in ("fwd_us", "fwdbwd_us"):
                best = min(ran, key=lambda r: r[key])
                results[shape]["best_" + key] = {"blocks": best["blocks"],
                                                 key: best[key]}
    print(json.dumps({k: {kk: vv for kk, vv in v.items() if kk != "all"}
                      for k, v in results.items()}))
    if args.install:
        import time
        results["provenance"] = (
            "benchmarks/flash_tune.py --install, "
            + jax.devices()[0].device_kind + ", "
            + time.strftime("%Y-%m-%d %H:%M UTC", time.gmtime())
            + "; device us a call of each kernel from a trace of "
            f"{CALLS} calls; tiles = (dead, interior, edge) a row; "
            "ops/attention._resolve_blocks must give each shape its "
            "fwdbwd winner or stand level with it "
            "(tests/test_policy_artifact.py).")
        dest = os.path.join(REPO, "benchmarks", "results",
                            "flash_tune.json")
        with open(dest + ".tmp", "w") as f:
            json.dump(results, f, indent=1)
            f.write("\n")
        os.replace(dest + ".tmp", dest)
        print(f"installed {dest}", file=sys.stderr)


if __name__ == "__main__":
    main()
