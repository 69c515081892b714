"""The held expert layer of a decode step, alone on the chip.

Times ``ops/moe_held.moe_held`` at the two latent cells' shapes (tokens,
held experts, d, f: sarvam-105b (16, 32, 4096, 2048), DeepSeek-V3.2-Exp
(8, 16, 7168, 2048)): the grouped kernel by tile width beside the
conditionals it replaced, with the expected number of experts touched
(20.6 of 32, 3.6 of 16; both neighbours are run) and with 0, 1 and all.
A timing is the wall clock of one program that makes ``CALLS`` calls one
after the other, each on what the last returned (as a decode scan's
layers do), over ``CALLS``; beside it the least time for the touched
experts' bytes and the tiles the kernel walked. The table is what
``ops/moe_held._tiles`` is written from.

Usage: python benchmarks/moe_held_bench.py [--shapes sarvam,dsv32]
           [--install]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

# name -> (tokens, held experts, d, f, touched at the expectation: the
# whole numbers on both sides of counts_*.experts_touched_expected)
SHAPES = {"sarvam": (16, 32, 4096, 2048, (20, 21)),
          "dsv32": (8, 16, 7168, 2048, (3, 4))}
TILES = (128, 256, 512)
CALLS = 16
REPEATS = 5


def layer(shape: str, seed: int = 0):
    import jax
    import jax.numpy as jnp
    t, count, d, f, _ = SHAPES[shape]
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    draw = lambda k, s, scale: (  # noqa: E731
        scale * jax.random.normal(k, s, jnp.float32)).astype(jnp.bfloat16)
    return (draw(ks[0], (t, d), 1.0), draw(ks[1], (count, d, f), d ** -0.5),
            draw(ks[2], (count, d, f), d ** -0.5),
            draw(ks[3], (count, f, d), f ** -0.5))


def routing(shape: str, touched: int, seed: int = 0):
    """(combine, load) with exactly ``touched`` experts chosen, each by
    every token: the work does not depend on which tokens chose."""
    import jax
    import jax.numpy as jnp
    t, count = SHAPES[shape][:2]
    k1, k2 = jax.random.split(jax.random.PRNGKey(100 + seed))
    chosen = jnp.zeros((count,), bool).at[
        jax.random.permutation(k1, count)[:touched]].set(True)
    weight = jax.random.uniform(k2, (t, count), jnp.float32, 0.1, 0.5)
    return (jnp.where(chosen[None, :], weight, 0.0),
            jnp.where(chosen, t, 0).astype(jnp.int32))


def us_a_call(call, x, combine, load, wg, wu, wd) -> float:
    import jax
    import jax.numpy as jnp

    @jax.jit
    def program(x, combine, load, wg, wu, wd):
        def one(_, x):
            y = call(x, combine, load, wg, wu, wd)
            return (x + 0.01 * y).astype(x.dtype)
        return jax.lax.fori_loop(0, CALLS, one, x)

    args = (x, combine, load, wg, wu, wd)
    jax.block_until_ready(program(*args))
    best = float("inf")
    for _ in range(REPEATS):
        t0 = time.perf_counter()
        jax.block_until_ready(program(*args))
        best = min(best, time.perf_counter() - t0)
    assert bool(jnp.all(jnp.isfinite(program(*args).astype(jnp.float32))))
    return best / CALLS * 1e6


def hbm_bytes_per_s() -> float:
    """The chip's published bandwidth (perfbench/peaks.json); a device
    that is not in the table is an error."""
    import jax
    with open(os.path.join(REPO, "perfbench", "peaks.json")) as f:
        peaks = json.load(f)["device_kind"]
    return peaks[jax.devices()[0].device_kind]["hbm_bytes_per_s"]


def time_shape(shape: str, tiles) -> list:
    import functools

    from lua_mapreduce_tpu.ops import moe_held as M

    t, count, d, f, expected = SHAPES[shape]
    x, wg, wu, wd = layer(shape)
    bandwidth = hbm_bytes_per_s()
    rows = []
    for touched in (*expected, 0, 1, count):
        combine, load = routing(shape, touched)
        row = {"touched": touched,
               "floor_us": round(touched * 3 * d * f * 2
                                 / bandwidth * 1e6, 1),
               "conditionals_us": round(us_a_call(
                   M._moe_held_xla, x, combine, load, wg, wu, wd), 1),
               "kernel_us": {}, "tiles_walked": {}}
        for tile_f in tiles:
            call = functools.partial(M._moe_held_pallas, tile_f=tile_f)
            try:
                row["kernel_us"][tile_f] = round(us_a_call(
                    call, x, combine, load, wg, wu, wd), 1)
            except Exception as e:
                row["kernel_us"][tile_f] = str(e)[:80]
            # the loop ends at the last touched expert's last tile: no
            # step is made for an untouched one
            row["tiles_walked"][tile_f] = {"live": touched * (f // tile_f),
                                           "dead": 0}
        rows.append(row)
        print(shape, json.dumps(row), flush=True)
    return rows


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--shapes", default=",".join(SHAPES))
    ap.add_argument("--tiles", default=",".join(map(str, TILES)))
    ap.add_argument("--install", action="store_true",
                    help="write results/moe_held_bench.json; only a "
                         "real-TPU run installs")
    args = ap.parse_args()

    from lua_mapreduce_tpu.utils.jax_env import (place_compile_cache,
                                                 require_tpu)
    place_compile_cache()
    require_tpu("moe_held_bench.py")
    import jax

    from lua_mapreduce_tpu.ops import moe_held as M

    tiles = [int(w) for w in args.tiles.split(",")]
    results = {}
    for shape in args.shapes.split(","):
        t, count, d, f, _ = SHAPES[shape]
        results[shape] = {"tokens": t, "held": count, "d": d, "f": f,
                          "tile_f": M._tiles(t, d, f, count, 2),
                          "rows": time_shape(shape, tiles)}
    if args.install:
        results["provenance"] = (
            "benchmarks/moe_held_bench.py --install, "
            + jax.devices()[0].device_kind + ", "
            + time.strftime("%Y-%m-%d %H:%M UTC", time.gmtime())
            + f"; us a call: the least of {REPEATS} wall-clock readings of "
            f"one program of {CALLS} dependent calls, over {CALLS}; "
            "floor_us: the touched experts' bytes at perfbench/peaks.json's "
            "bandwidth (819 GB/s); tile_f: "
            "what ops/moe_held._tiles chooses.")
        dest = os.path.join(REPO, "benchmarks", "results",
                            "moe_held_bench.json")
        with open(dest + ".tmp", "w") as f:
            json.dump(results, f, indent=1)
            f.write("\n")
        os.replace(dest + ".tmp", dest)
        print(f"installed {dest}", file=sys.stderr)


if __name__ == "__main__":
    main()
