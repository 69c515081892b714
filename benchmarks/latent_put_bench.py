"""The write of a latent decode step's row, alone on the chip.

Times the two ways of putting one row ``[c_kv | k_rope]`` a session into
a latent cache at the two latent cells' shapes (sessions, slots, row:
Kimi-Linear-48B-A3B-Instruct (32, 4160, 576), sarvam-105b (16, 32832,
576)), with the position in the last 128-position tile, which hangs
over the cache's end: XLA's ``lax.dynamic_update_slice`` and
``ops/mla_decode._latent_row_put`` by rows a grid step. A timing is the
wall clock of one program that makes ``CALLS`` puts one after the
other into a donated cache, each at the next position of that tile and
with another row (as a decode scan's steps do), over ``CALLS``; beside
it the same loop that makes the rows and writes none, and the least
time for the tile columns the put reads and writes. Each way's cache
after the program is compared with the others' (``equal``).

Usage: python benchmarks/latent_put_bench.py [--shapes kimi,sarvam]
           [--rows 1,2,4,8,16] [--install]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

# name -> (sessions, slots, row width): the cells' caches
SHAPES = {"kimi": (32, 4160, 576), "sarvam": (16, 32832, 576)}
ROWS = (1, 2, 4, 8, 16)
CALLS = 64
REPEATS = 5


def program(put):
    """``CALLS`` dependent puts, the position walking the last tile
    (the first position of the last 128-position column, then on up to
    the cache's last slot and round again)."""
    import jax

    def run(cache, row):
        s_len = cache.shape[1]
        first = (s_len - 1) // 128 * 128

        def one(i, cache):
            t = first + i % (s_len - first)
            return put(cache, (row + i.astype(row.dtype)).astype(row.dtype),
                       t)
        return jax.lax.fori_loop(0, CALLS, one, cache)

    return jax.jit(run, donate_argnums=0)


def us_a_call(put, cache, row) -> tuple:
    """(us a put, the cache after one program from ``cache``)."""
    import jax
    import jax.numpy as jnp
    run = program(put)
    first = run(jnp.copy(cache), row)
    work = jnp.copy(cache)
    work = jax.block_until_ready(run(work, row))
    best = float("inf")
    for _ in range(REPEATS):
        t0 = time.perf_counter()
        work = jax.block_until_ready(run(work, row))
        best = min(best, time.perf_counter() - t0)
    return best / CALLS * 1e6, first


def hbm_bytes_per_s() -> float:
    """The chip's published bandwidth (perfbench/peaks.json); a device
    that is not in the table is an error."""
    import jax
    with open(os.path.join(REPO, "perfbench", "peaks.json")) as f:
        peaks = json.load(f)["device_kind"]
    return peaks[jax.devices()[0].device_kind]["hbm_bytes_per_s"]


def time_shape(shape: str, rows) -> dict:
    import functools

    import jax
    import jax.numpy as jnp
    from jax import lax

    from lua_mapreduce_tpu.ops import mla_decode as M

    b, s_len, width = SHAPES[shape]
    k1, k2 = jax.random.split(jax.random.PRNGKey(0))
    cache = jax.random.normal(k1, (b, s_len, width), jnp.bfloat16)
    row = jax.random.normal(k2, (b, 1, width), jnp.bfloat16)
    column = b * width * 128 * cache.dtype.itemsize
    out = {"sessions": b, "slots": s_len, "width": width,
           "rows_chosen": M._put_rows(b, width, cache.dtype.itemsize),
           "floor_us": round(2 * column / hbm_bytes_per_s() * 1e6, 2)}
    dus = lambda c, r, t: lax.dynamic_update_slice(c, r, (0, t, 0))  # noqa
    out["dus_us"], want = us_a_call(dus, cache, row)
    out["dus_us"] = round(out["dus_us"], 2)
    # the loop and the rows alone: a row's first value summed into a
    # scalar that stands in for the cache
    none = lambda c, r, t: c.at[0, 0, 0].add(r[0, 0, 0])  # noqa: E731
    out["loop_us"] = round(us_a_call(none, cache, row)[0], 2)
    out["put_us"], out["equal"] = {}, {}
    for r in rows:
        if b % r:
            continue
        put = functools.partial(M._latent_row_put, rows=r)
        try:
            us, got = us_a_call(put, cache, row)
            out["put_us"][r] = round(us, 2)
            out["equal"][r] = bool(jnp.array_equal(got, want))
        except Exception as e:
            out["put_us"][r] = str(e)[:80]
    print(shape, json.dumps(out), flush=True)
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--shapes", default=",".join(SHAPES))
    ap.add_argument("--rows", default=",".join(map(str, ROWS)))
    ap.add_argument("--install", action="store_true",
                    help="write results/latent_put_bench.json; only a "
                         "real-TPU run installs")
    args = ap.parse_args()

    from lua_mapreduce_tpu.utils.jax_env import (place_compile_cache,
                                                 require_tpu)
    place_compile_cache()
    require_tpu("latent_put_bench.py")
    import jax

    rows = [int(r) for r in args.rows.split(",")]
    results = {shape: time_shape(shape, rows)
               for shape in args.shapes.split(",")}
    if args.install:
        results["provenance"] = (
            "benchmarks/latent_put_bench.py --install, "
            + jax.devices()[0].device_kind + ", "
            + time.strftime("%Y-%m-%d %H:%M UTC", time.gmtime())
            + f"; us a put: the least of {REPEATS} wall-clock readings of "
            f"one program of {CALLS} dependent puts into a donated cache, "
            f"over {CALLS}, the loop's own cost (loop_us) included; "
            "floor_us: the tile columns read and written at "
            "perfbench/peaks.json's bandwidth; rows_chosen: what "
            "ops/mla_decode._put_rows chooses; equal: the cache after a "
            "program of puts is the dynamic-update-slice's, bit for bit.")
        dest = os.path.join(REPO, "benchmarks", "results",
                            "latent_put_bench.json")
        with open(dest + ".tmp", "w") as f:
            json.dump(results, f, indent=1)
            f.write("\n")
        os.replace(dest + ".tmp", dest)
        print(f"installed {dest}", file=sys.stderr)


if __name__ == "__main__":
    main()
