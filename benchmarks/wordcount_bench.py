"""Europarl-scale wordcount benchmark — the reference's headline numbers.

Reference (README.md:43-113, one 4-core machine): 47.37s cluster /
49.23s server wall with 4 workers; 26.1s single-core naive Lua; 141.3s
shell pipeline. This script reproduces the same experiment on the
synthetic corpus of examples/wordcount_big (same shape: 197 splits,
49.25M words) against this framework's true multi-process pool, and
records the result as a machine-readable artifact
(benchmarks/results/wordcount.json, committed per round).

Usage: python benchmarks/wordcount_bench.py [n_workers] [corpus_dir]
"""

from __future__ import annotations

import hashlib
import json
import os
import shutil
import subprocess
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

RESULTS = os.path.join(REPO, "benchmarks", "results", "wordcount.json")


def corpus_hash(corpus_dir: str, n_splits: int) -> str:
    """Cheap deterministic corpus fingerprint: sizes + first split bytes."""
    from examples.wordcount_big import corpus
    h = hashlib.sha256()
    for i in range(n_splits):
        h.update(str(os.path.getsize(corpus.split_path(corpus_dir, i)))
                 .encode())
    with open(corpus.split_path(corpus_dir, 0), "rb") as f:
        h.update(f.read(65536))
    return h.hexdigest()[:16]


def _native_map_active(corpus_dir: str) -> bool:
    """True only if the native kernel ACTUALLY serves this corpus: run
    one real native map over split 0 into a scratch store (the runtime
    gate also checks store type, input presence, and ASCII content —
    availability alone would mislabel the artifact's provenance)."""
    from examples.wordcount_big import bigtask, corpus
    from lua_mapreduce_tpu.core import native_wcmap
    from lua_mapreduce_tpu.store.sharedfs import SharedStore

    tag = getattr(bigtask.mapfn, "native_map", None)
    if tag is None or not native_wcmap.native_available():
        return False
    scratch = tempfile.mkdtemp(prefix="wcb-nmprobe")
    try:
        return native_wcmap.run_native_map(
            SharedStore(scratch), tag, corpus.split_path(corpus_dir, 0),
            "probe", "0")
    except OSError:
        # probe trouble must not discard the already-measured run —
        # label provenance unconfirmed instead
        return False
    finally:
        shutil.rmtree(scratch, ignore_errors=True)


def run(n_workers: int = 4, corpus_dir: str = "/tmp/wc_corpus") -> dict:
    from examples.wordcount_big import corpus
    from lua_mapreduce_tpu.coord.filestore import FileJobStore
    from lua_mapreduce_tpu.engine.contract import TaskSpec
    from lua_mapreduce_tpu.engine.server import Server

    corpus.build(corpus_dir, log=lambda m: print(m, flush=True))
    coord = tempfile.mkdtemp(prefix="wcb-coord")
    spill = tempfile.mkdtemp(prefix="wcb-spill")

    worker_code = (
        "import sys\n"
        f"sys.path.insert(0, {REPO!r})\n"
        "from lua_mapreduce_tpu.coord.filestore import FileJobStore\n"
        "from lua_mapreduce_tpu.engine.worker import Worker\n"
        f"w = Worker(FileJobStore({coord!r})).configure(\n"
        "    max_iter=100000, max_sleep=0.05, max_tasks=100000)\n"
        "w.execute()\n")
    # host-path workers: never reach for a chip the parent may hold
    env = dict(os.environ, PYTHONPATH=REPO, JAX_PLATFORMS="cpu")
    t0 = time.perf_counter()
    procs = [subprocess.Popen([sys.executable, "-c", worker_code], env=env)
             for _ in range(n_workers)]
    try:
        mod = "examples.wordcount_big.bigtask"
        spec = TaskSpec(taskfn=mod, mapfn=mod, partitionfn=mod,
                        reducefn=mod,
                        init_args={"corpus_dir": corpus_dir},
                        storage=f"shared:{spill}")
        server = Server(FileJobStore(coord),
                        poll_interval=0.1).configure(spec)
        stats = server.loop()
        wall = time.perf_counter() - t0
    finally:
        # wall time is already measured — kill the pool outright instead
        # of waiting out each worker's poll loop (ADVICE r1: the old
        # wait(60) serialized into minutes of teardown)
        for p in procs:
            p.kill()
        for p in procs:
            try:
                p.wait(timeout=10)
            except Exception:
                pass
    it = stats.iterations[-1]
    from examples.wordcount_big import bigtask
    from lua_mapreduce_tpu.core import native_merge
    out = {
        "server_wall_s": round(wall, 1),
        "map_cluster_s": round(it.map.cluster_time, 1),
        "reduce_cluster_s": round(it.reduce.cluster_time, 1),
        "cluster_s": round(it.cluster_time, 1),
        "map_sum_cpu_s": round(it.map.sum_cpu_time, 1),
        "map_sum_real_s": round(it.map.sum_real_time, 1),
        "reduce_sum_cpu_s": round(it.reduce.sum_cpu_time, 1),
        "reduce_sum_real_s": round(it.reduce.sum_real_time, 1),
        "map_jobs": it.map.count,
        "reduce_jobs": it.reduce.count,
        "failed": it.map.failed + it.reduce.failed,
        "n_workers": n_workers,
        "n_cores": os.cpu_count(),
        "num_reducers": bigtask.NUM_REDUCERS,
        "combiner": "map-side Counter fold (one record per distinct word)",
        "native_merge": native_merge.native_available(),
        "native_map": _native_map_active(corpus_dir),
        "corpus_hash": corpus_hash(corpus_dir, corpus.N_SPLITS),
        "corpus": {"splits": corpus.N_SPLITS,
                   "words": corpus.total_words()},
        "reference_4core_4worker": {"cluster_s": 47.37, "wall_s": 49.23},
    }
    out["vs_reference_cluster"] = round(47.37 / it.cluster_time, 2)
    return out


if __name__ == "__main__":
    n = int(sys.argv[1]) if len(sys.argv) > 1 else 4
    d = sys.argv[2] if len(sys.argv) > 2 else "/tmp/wc_corpus"
    if len(sys.argv) > 3:       # scaled-pool runs keep their own artifact
        RESULTS = os.path.abspath(sys.argv[3])   # noqa: F811
    result = run(n, d)
    # second leg: same engine with the native layer killed
    # (LMR_DISABLE_NATIVE=1) — the honest within-framework measure of
    # what the C++ data path buys. Only meaningful when leg 1 actually
    # ran native (a no-g++ box would just record two identical runs).
    if (os.environ.get("LMR_SKIP_PYTHON_LEG") != "1"
            and result["native_map"] and result["native_merge"]):
        prev = os.environ.get("LMR_DISABLE_NATIVE")
        os.environ["LMR_DISABLE_NATIVE"] = "1"
        try:
            py_leg = run(n, d)
            result["python_engine_leg"] = {
                k: py_leg[k] for k in ("cluster_s", "server_wall_s",
                                       "map_cluster_s",
                                       "reduce_cluster_s")}
            result["native_layer_speedup"] = round(
                py_leg["cluster_s"] / result["cluster_s"], 2)
        except Exception as e:
            # leg-2 trouble must not discard leg 1's measurement
            result["python_engine_leg"] = {
                "error": f"{type(e).__name__}: {e}"[:200]}
        finally:
            if prev is None:
                del os.environ["LMR_DISABLE_NATIVE"]
            else:
                os.environ["LMR_DISABLE_NATIVE"] = prev
    print(json.dumps(result))
    os.makedirs(os.path.dirname(RESULTS), exist_ok=True)
    with open(RESULTS, "w") as f:
        json.dump(result, f, indent=1)
        f.write("\n")
