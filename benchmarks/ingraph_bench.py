"""In-graph engine bench (DESIGN §26): interpreted vs compiled plane.

Paired-rounds median protocol (benchmarks/bench_common.py — the shared
de-biasing rules of sort/coord/segment bench): each round runs the SAME
task once per engine leg back-to-back with the order alternated between
rounds, the per-round paired wall ratio carries the meaning on a
drifting shared host, and the MEDIAN paired ratio is the headline.

Two iterative numeric workloads, both the "loop"-protocol shape the
compiled plane was built for (ROADMAP item 3):

- **digits** — examples/digits/mr_sgd.py data-parallel SGD (the
  in-graph packaging of the APRIL-ANN digits workload); headline is
  images/sec and the per-run wall speedup over the interpreted store
  plane running the IDENTICAL module.
- **kmeans** — examples/kmeans/mr_kmeans.py Lloyd iterations with
  centroids threaded through the job values.

Both legs' final model state must agree (allclose, atol/rtol 1e-4 —
the two planes may reassociate float folds; the integer byte-identity
legs live in tests/test_ingraph.py) or no speedup number matters.

The compiled leg's first iteration carries the ONE trace+compile of the
whole run (the no-retrace loop contract); it is included in the wall
(end-to-end honesty) and ALSO reported separately as
``ingraph_compile_s`` next to the steady-state per-iteration ratio —
on CPU the compile is the dominant fixed cost, so the end-to-end
speedup grows with iteration count while the steady-state ratio is the
asymptote.

Usage: python benchmarks/ingraph_bench.py [rounds] [--smoke]
Artifact: benchmarks/results/ingraph.json
Acceptance: median end-to-end speedup >= 3.0 on BOTH workloads, states
allclose, compiled leg actually ran in-graph every iteration.
"""

from __future__ import annotations

import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

RESULTS = os.path.join(REPO, "benchmarks", "results", "ingraph.json")

from benchmarks.bench_common import leg_order, median, paired_speedup

DIGITS_ARGS = {"dim": 16, "hidden": 8, "n_shards": 8, "bunch": 128,
               "seed": 1}
KMEANS_ARGS = {"k": 8, "n": 1024, "dim": 16, "n_shards": 4, "tol": 0.0,
               "seed": 0, "coord": "mem"}


def _cpu_env() -> None:
    # the virtual 8-device CPU mesh of tests/conftest.py: this bench
    # compares the two planes on host CPUs and says so in its artifact
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=8")
    os.environ.setdefault("JAX_NUM_CPU_DEVICES", "8")
    import jax
    jax.config.update("jax_platforms", "cpu")


def _run(mod: str, engine: str, tag: str, init_args: dict,
         max_iter: int) -> dict:
    from lua_mapreduce_tpu.engine.contract import TaskSpec
    from lua_mapreduce_tpu.engine.local import LocalExecutor
    spec = TaskSpec(taskfn=mod, mapfn=mod, partitionfn=mod, reducefn=mod,
                    finalfn=mod, init_args=init_args,
                    storage=f"mem:igb-{tag}")
    ex = LocalExecutor(spec, engine=engine, max_iterations=max_iter + 5)
    t0 = time.perf_counter()
    ex.run()
    wall = time.perf_counter() - t0
    iters = [it.wall_time for it in ex.stats.iterations]
    compiled = sum(it.ingraph_iterations for it in ex.stats.iterations)
    return {"wall_s": wall, "iter_walls": iters, "compiled": compiled,
            "fallbacks": sum(it.ingraph_fallbacks
                             for it in ex.stats.iterations)}


def _digits_leg(engine: str, tag: str, steps: int) -> dict:
    from examples.digits import mr_sgd
    row = _run("examples.digits.mr_sgd", engine, tag,
               {**DIGITS_ARGS, "max_steps": steps}, steps)
    st = mr_sgd.read_state()
    row["params"] = {k: v.copy() for k, v in st["params"].items()}
    row["images_per_s"] = mr_sgd.images_seen() / row["wall_s"]
    return row


def _kmeans_leg(engine: str, tag: str, iters: int) -> dict:
    from examples.kmeans import mr_kmeans
    row = _run("examples.kmeans.mr_kmeans", engine, tag,
               {**KMEANS_ARGS, "max_iters": iters}, iters)
    import numpy as np
    row["centroids"] = np.asarray(
        mr_kmeans.read_state("mem")["centroids"])
    return row


def _allclose(a, b) -> bool:
    import numpy as np
    return bool(np.allclose(a, b, rtol=1e-4, atol=1e-4))


# -- hybrid legs (DESIGN §28) -------------------------------------------------
#
# Two loop-protocol workloads on the stage-granular plane, store vs
# hybrid under the same paired-rounds protocol (the one compile
# amortises over ITERS iterations exactly as digits/kmeans do):
#
# - **hybrid_sort** — benchmarks/hybrid_task.py, the extsort shape the
#   rung exists for: compiled map+combine batch, host blake2b
#   partition, interpreted shuffle tail. Integer dtype: the two legs'
#   result.P files must be BYTE-identical. Acceptance: median paired
#   speedup >= 1.5.
# - **hybrid_fold** — benchmarks/hybrid_fold_task.py, the mirror split:
#   host-bound map, compiled reduce fold. float32, results compared
#   allclose (atol 1e-4 — the jitted fold may reassociate). Measured,
#   not gated: on CPU the host accumulator over small decoded floats is
#   already near-free, the number documents where the split's win
#   actually lives (the map leg).

def _result_docs(tag: str) -> dict:
    from lua_mapreduce_tpu.store.router import get_storage_from
    store = get_storage_from(f"mem:igb-{tag}")
    return {n: "".join(store.lines(n)) for n in store.list("result.P*")}


def _result_rows(tag: str):
    """Decoded (key, values) rows in deterministic order — the float
    twin compare (allclose, not bytes)."""
    from lua_mapreduce_tpu.engine.local import iter_results
    from lua_mapreduce_tpu.store.router import get_storage_from
    rows = list(iter_results(get_storage_from(f"mem:igb-{tag}"), "result"))
    rows.sort(key=lambda r: str(r[0]))
    return rows


def _hybrid_leg(mod: str, engine: str, tag: str) -> dict:
    from lua_mapreduce_tpu.engine.contract import TaskSpec
    from lua_mapreduce_tpu.engine.local import LocalExecutor
    spec = TaskSpec(taskfn=mod, mapfn=mod, partitionfn=mod, reducefn=mod,
                    combinerfn=mod if mod.endswith("hybrid_task") else None,
                    finalfn=mod, storage=f"mem:igb-{tag}")
    ex = LocalExecutor(spec, engine=engine, max_iterations=192)
    t0 = time.perf_counter()
    ex.run()
    wall = time.perf_counter() - t0
    its = ex.stats.iterations
    return {"wall_s": wall,
            "results": _result_docs(tag),
            "rows": _result_rows(tag),
            "map_legs": sum(it.hybrid_map_legs for it in its),
            "reduce_legs": sum(it.hybrid_reduce_legs for it in its),
            "fallbacks": sum(it.hybrid_fallbacks for it in its)}


def _hybrid_workload(name: str, mod: str, rounds: int,
                     float_fold: bool = False,
                     warmup: bool = True) -> dict:
    if warmup:
        # same eager-cache warmup rationale as _workload
        _hybrid_leg(mod, "store", f"{name}-warm-s")
        _hybrid_leg(mod, "hybrid", f"{name}-warm-h")
    store_rows, hy_rows = [], []
    agree = True
    for rnd in range(rounds):
        pair = {}
        for eng in leg_order(("store", "hybrid"), rnd):
            pair[eng] = _hybrid_leg(mod, eng, f"{name}-{eng}-{rnd}")
        store_rows.append(pair["store"])
        hy_rows.append(pair["hybrid"])
        if float_fold:
            a = pair["store"]["rows"]
            b = pair["hybrid"]["rows"]
            agree = agree and len(a) == len(b) and all(
                x[0] == y[0] and _allclose(x[1], y[1])
                for x, y in zip(a, b))
        else:
            agree = agree and (pair["store"]["results"]
                               == pair["hybrid"]["results"])
        # the hybrid leg must have RUN its compiled stage, fallback-free,
        # and the store leg must not have touched the hybrid plane
        if name == "hybrid_sort":
            assert pair["hybrid"]["map_legs"] >= 1, pair["hybrid"]
        else:
            assert pair["hybrid"]["reduce_legs"] >= 1, pair["hybrid"]
        assert pair["hybrid"]["fallbacks"] == 0
        assert pair["store"]["map_legs"] == 0
        assert pair["store"]["reduce_legs"] == 0
    sp = paired_speedup(store_rows, hy_rows, "wall_s")
    med = sp["median_round"]
    return {
        "speedup": sp["speedup"],
        "speedup_pairs": sp["per_round"],
        "wall_s_store": round(store_rows[med]["wall_s"], 3),
        "wall_s_hybrid": round(hy_rows[med]["wall_s"], 3),
        "hybrid_map_legs": hy_rows[med]["map_legs"],
        "hybrid_reduce_legs": hy_rows[med]["reduce_legs"],
        "hybrid_fallbacks": hy_rows[med]["fallbacks"],
        ("results_allclose" if float_fold else "results_identical"): agree,
    }


def _steady_ratio(store_row: dict, ig_row: dict) -> float:
    """Per-iteration medians, the compiled leg's compile-carrying first
    iteration excluded — the asymptotic ratio."""
    s = median(store_row["iter_walls"])
    i = median(ig_row["iter_walls"][1:] or ig_row["iter_walls"])
    return s / max(i, 1e-9)


def _workload(name: str, leg_fn, n_iter: int, rounds: int,
              warmup: bool = True) -> dict:
    if warmup:
        # one tiny throwaway run per leg: jax's EAGER op caches are
        # process-global, so without this the first store round pays
        # one-time op compilation the later rounds don't — an
        # unearned (and unrepeatable) ratio boost for round 0
        leg_fn("store", f"{name}-warm-s", 2)
        leg_fn("ingraph", f"{name}-warm-i", 2)
    store_rows, ig_rows = [], []
    agree = True
    for rnd in range(rounds):
        pair = {}
        for eng in leg_order(("store", "ingraph"), rnd):
            pair[eng] = leg_fn(eng, f"{name}-{eng}-{rnd}", n_iter)
        store_rows.append(pair["store"])
        ig_rows.append(pair["ingraph"])
        key = "params" if name == "digits" else "centroids"
        if name == "digits":
            agree = agree and all(
                _allclose(pair["store"][key][k], pair["ingraph"][key][k])
                for k in pair["store"][key])
        else:
            agree = agree and _allclose(pair["store"][key],
                                        pair["ingraph"][key])
        # the compiled leg must have COMPILED, once, and stayed there
        assert pair["ingraph"]["compiled"] == n_iter, pair["ingraph"]
        assert pair["ingraph"]["fallbacks"] == 0
        assert pair["store"]["compiled"] == 0
    sp = paired_speedup(store_rows, ig_rows, "wall_s")
    med = sp["median_round"]
    compile_s = [r["iter_walls"][0] - median(r["iter_walls"][1:]
                                             or r["iter_walls"])
                 for r in ig_rows]
    out = {
        "speedup": sp["speedup"],
        "speedup_pairs": sp["per_round"],
        "steady_state_speedup": round(median(
            [_steady_ratio(s, i) for s, i in zip(store_rows, ig_rows)]), 2),
        "compile_s": round(median(compile_s), 3),
        "wall_s_store": round(store_rows[med]["wall_s"], 3),
        "wall_s_ingraph": round(ig_rows[med]["wall_s"], 3),
        "iterations": n_iter,
        "state_allclose": agree,
    }
    if name == "digits":
        out["images_per_s_store"] = round(
            store_rows[med]["images_per_s"], 1)
        out["images_per_s_ingraph"] = round(
            ig_rows[med]["images_per_s"], 1)
    return out


def run(rounds: int = 3, digits_steps: int = 60,
        kmeans_iters: int = 200) -> dict:
    _cpu_env()
    digits = _workload("digits", _digits_leg, digits_steps, rounds)
    kmeans = _workload("kmeans", _kmeans_leg, kmeans_iters, rounds)
    hybrid_sort = _hybrid_workload(
        "hybrid_sort", "benchmarks.hybrid_task", rounds)
    hybrid_fold = _hybrid_workload(
        "hybrid_fold", "benchmarks.hybrid_fold_task", rounds,
        float_fold=True)
    return {
        "ingraph_speedup": min(digits["speedup"], kmeans["speedup"]),
        "ingraph_compile_s": max(digits["compile_s"],
                                 kmeans["compile_s"]),
        "hybrid_speedup": hybrid_sort["speedup"],
        "digits": digits,
        "kmeans": kmeans,
        "hybrid_sort": hybrid_sort,
        "hybrid_fold": hybrid_fold,
        "identical_state": digits["state_allclose"]
        and kmeans["state_allclose"]
        and hybrid_sort["results_identical"]
        and hybrid_fold["results_allclose"],
        "config": {"rounds": rounds, "digits": {**DIGITS_ARGS,
                                                "max_steps": digits_steps},
                   "kmeans": {**KMEANS_ARGS, "max_iters": kmeans_iters},
                   "platform": "cpu (JAX_PLATFORMS=cpu, 8 virtual devices)",
                   "protocol": "paired rounds, order alternated, median "
                               "end-to-end wall ratio headlined; compiled "
                               "leg includes its one compile (also "
                               "reported as ingraph_compile_s); tiny "
                               "per-leg warmup before round 0 so the "
                               "process-global eager-op caches don't "
                               "gift round 0 an unrepeatable ratio"},
    }


def smoke() -> int:
    """test.sh gate: one tiny paired round per workload — the compiled
    plane must select, compile once, agree with the interpreted twin."""
    _cpu_env()
    digits = _workload("digits", _digits_leg, 3, 1, warmup=False)
    kmeans = _workload("kmeans", _kmeans_leg, 3, 1, warmup=False)
    ok = digits["state_allclose"] and kmeans["state_allclose"]
    print(f"ingraph smoke: digits x{digits['speedup']} "
          f"(compile {digits['compile_s']}s) kmeans x{kmeans['speedup']} "
          f"(compile {kmeans['compile_s']}s) "
          f"state_allclose={ok} -> {'PASS' if ok else 'FAIL'}")
    return 0 if ok else 1


def smoke_hybrid() -> int:
    """test.sh gate (DESIGN §28): one tiny paired round per hybrid
    split — the negotiated stage legs must run compiled,
    fallback-free, byte-identical (int) / allclose (float) to the
    interpreted twin."""
    _cpu_env()
    hs = _hybrid_workload("hybrid_sort", "benchmarks.hybrid_task", 1,
                          warmup=False)
    hf = _hybrid_workload("hybrid_fold", "benchmarks.hybrid_fold_task",
                          1, float_fold=True, warmup=False)
    ok = hs["results_identical"] and hf["results_allclose"]
    print(f"hybrid smoke: sort x{hs['speedup']} "
          f"(map_legs={hs['hybrid_map_legs']}) "
          f"fold x{hf['speedup']} "
          f"(reduce_legs={hf['hybrid_reduce_legs']}) "
          f"bytes/allclose={ok} -> {'PASS' if ok else 'FAIL'}")
    return 0 if ok else 1


def main() -> None:
    if "--smoke" in sys.argv:
        raise SystemExit(smoke())
    if "--smoke-hybrid" in sys.argv:
        raise SystemExit(smoke_hybrid())
    rounds = int(sys.argv[1]) if len(sys.argv) > 1 else 3
    out = run(rounds=rounds)
    out["timestamp"] = time.strftime("%Y-%m-%dT%H:%M:%S")
    os.makedirs(os.path.dirname(RESULTS), exist_ok=True)
    with open(RESULTS, "w") as f:
        json.dump(out, f, indent=2)
    print(json.dumps(out, indent=2))
    ok = (out["ingraph_speedup"] >= 3.0 and out["hybrid_speedup"] >= 1.5
          and out["identical_state"])
    print(f"acceptance: speedup {out['ingraph_speedup']} >= 3.0 "
          f"(digits {out['digits']['speedup']}, steady "
          f"{out['digits']['steady_state_speedup']}; kmeans "
          f"{out['kmeans']['speedup']}, steady "
          f"{out['kmeans']['steady_state_speedup']}), "
          f"hybrid_sort {out['hybrid_speedup']} >= 1.5 "
          f"(fold leg {out['hybrid_fold']['speedup']} measured), "
          f"state allclose={out['identical_state']} -> "
          f"{'PASS' if ok else 'FAIL'}")


if __name__ == "__main__":
    main()
