"""In-graph execution engine tests (engine/ingraph.py, DESIGN §26).

The compiled plane's whole contract, golden-diffed against the
interpreted store plane on both executors:

- byte-identical output for integer-keyed workloads (the wordcount
  sum-reducer shape and the extsort range-partition/identity-reduce
  singleton-fast-path shape),
- allclose output for float workloads (kmeans / ALS / digits SGD;
  atol 1e-4 — the two planes may reassociate float folds),
- the "loop" protocol compiling exactly ONCE per task (the no-retrace
  compile counter),
- oracle/runtime agreement: a task the static oracle verdicts in-graph
  but whose lowering raises at trace time degrades to the store plane
  under ``engine="auto"`` with ``ingraph_fallbacks`` bumped and
  byte-identical output — and RAISES under the ``engine="ingraph"``
  hard mode,
- the decision/fallback surfacing: ``lowering`` / ``ingraph.run`` /
  ``ingraph.fallback`` trace spans and the per-iteration engine map.
"""

import os
import sys
import threading

import numpy as np
import pytest

from lua_mapreduce_tpu.coord.jobstore import MemJobStore
from lua_mapreduce_tpu.engine.contract import TaskSpec
from lua_mapreduce_tpu.engine.ingraph import (LoweringError, resolve_engine,
                                              select_engine)
from lua_mapreduce_tpu.engine.local import LocalExecutor
from lua_mapreduce_tpu.engine.server import Server
from lua_mapreduce_tpu.engine.worker import Worker

# ---------------------------------------------------------------------------
# fixture task modules (materialized on sys.path so the STATIC oracle can
# resolve them — the in-graph selection path never imports, it parses)
# ---------------------------------------------------------------------------

# the wordcount sum-reducer shape with integer keys/values: mapfn buckets
# this shard's token ids, the REAL examples.wordcount.reducefn sums the
# counts — integer folds must be BYTE-identical across the planes
IG_SUM = """
import jax.numpy as jnp

def taskfn(emit):
    for j in range(6):
        emit(j, {"ids": [(j * 13 + i * 7) % 8 for i in range(32)]})

def mapfn(key, value, emit):
    ids = jnp.asarray(value["ids"], jnp.int32)
    for b in range(8):
        emit(b, jnp.sum(jnp.where(ids == b, 1, 0)))

def partitionfn(key):
    return int(key) % 3
"""

# the extsort shape: unique integer keys, range partitionfn monotone in
# the key, identity reducefn flagged ACI — every group is a singleton,
# exercising the merge fast path on both planes
IG_SORT = """
import jax.numpy as jnp

def taskfn(emit):
    for j in range(4):
        emit(j, {"vals": [(j * 16 + i) * 7 % 101 for i in range(16)]})

def mapfn(key, value, emit):
    v = jnp.asarray(value["vals"], jnp.int32)
    for i in range(16):
        emit(int(key) * 16 + i, {"v": v[i] * 2})

def partitionfn(key):
    return (int(key) * 4) // 64

def reducefn(key, values):
    return values[0]

reducefn.associative_reducer = True
reducefn.commutative_reducer = True
reducefn.idempotent_reducer = True
"""

# oracle/runtime disagreement: every call is inside the oracle's
# whitelisted surface (verdict: in-graph), but the emitted KEY is a
# traced value — the lowering refuses data-dependent key spaces at
# trace time, so engine=auto must degrade to the store plane (where a
# concrete jax scalar key is fine) and engine=ingraph must raise
IG_TRACED_KEY = """
import jax.numpy as jnp

def taskfn(emit):
    for j in range(4):
        emit(j, {"v": [float(j + 1), 2.0]})

def mapfn(key, value, emit):
    v = jnp.asarray(value["v"], jnp.float32)
    emit(jnp.sum(v), {"s": v[0]})

def partitionfn(key):
    return int(key) % 2

def reducefn(key, values):
    s = jnp.asarray(values[0]["s"])
    for i in range(1, len(values)):
        s = s + jnp.asarray(values[i]["s"])
    return {"s": s}
"""


@pytest.fixture(scope="module")
def igmod(tmp_path_factory):
    """Materialize fixture task sources as importable modules on
    sys.path (the oracle resolves module NAMES statically; tmp modules
    must be visible to both importlib and resolve_spec)."""
    root = tmp_path_factory.mktemp("igtasks")
    sys.path.insert(0, str(root))
    made = []

    def factory(name: str, src: str) -> str:
        path = root / f"{name}.py"
        path.write_text(src)
        made.append(name)
        return name

    yield factory
    sys.path.remove(str(root))
    for name in made:
        sys.modules.pop(name, None)


def _result_bytes(store, result_ns="result"):
    import re
    pat = re.compile(rf"^{re.escape(result_ns)}\.P(\d+)$")
    return {n: "".join(store.lines(n))
            for n in store.list(f"{result_ns}.P*") if pat.match(n)}


def _local(mod, engine, tag, *, reducefn=None, partitionfn=None,
           finalfn=None, init_args=None, **kw):
    spec = TaskSpec(taskfn=mod, mapfn=mod,
                    partitionfn=partitionfn or mod,
                    reducefn=reducefn or mod,
                    finalfn=finalfn, init_args=init_args,
                    storage=f"mem:ig-{tag}")
    ex = LocalExecutor(spec, engine=engine, **kw)
    ex.run()
    return ex


# ---------------------------------------------------------------------------
# golden diffs: integer byte-identity, LocalExecutor
# ---------------------------------------------------------------------------

def test_int_sum_reducer_byte_identical(igmod):
    mod = igmod("ig_sum_a", IG_SUM)
    ex_s = _local(mod, "store", "sum-s", reducefn="examples.wordcount.reducefn")
    ex_i = _local(mod, "ingraph", "sum-i", reducefn="examples.wordcount.reducefn")
    assert ex_i.engine_decision.verdict == "in-graph"
    assert ex_i.engine_decision.chosen == "ingraph"
    assert _result_bytes(ex_i.result_store) == _result_bytes(ex_s.result_store)
    assert ex_i._ingraph.engine.traces == 1
    assert ex_i.stats.iterations[-1].ingraph_iterations == 1
    assert ex_i.stats.iterations[-1].ingraph_fallbacks == 0
    # the store leg ran zero compiled iterations
    assert ex_s.stats.iterations[-1].ingraph_iterations == 0


def test_int_sum_auto_selects_ingraph(igmod):
    mod = igmod("ig_sum_b", IG_SUM)
    ex_a = _local(mod, "auto", "sum-auto",
                  reducefn="examples.wordcount.reducefn")
    assert ex_a.engine_decision.requested == "auto"
    assert ex_a.engine_decision.chosen == "ingraph"
    assert ex_a.stats.iterations[-1].ingraph_iterations == 1


def test_int_sort_singleton_fastpath_byte_identical(igmod):
    mod = igmod("ig_sort", IG_SORT)
    ex_s = _local(mod, "store", "sort-s")
    ex_i = _local(mod, "ingraph", "sort-i")
    out_s, out_i = (_result_bytes(ex_s.result_store),
                    _result_bytes(ex_i.result_store))
    assert out_i == out_s
    # range partition: 4 partitions, 16 unique singleton keys each
    assert len(out_i) == 4
    assert sum(o.count("\n") for o in out_i.values()) == 64


# ---------------------------------------------------------------------------
# golden diffs: float allclose (kmeans / ALS / digits), loop no-retrace
# ---------------------------------------------------------------------------

def _run_kmeans(engine, tag, **args):
    from examples.kmeans import mr_kmeans
    mod = "examples.kmeans.mr_kmeans"
    init_args = {"k": 8, "n": 512, "dim": 8, "n_shards": 4,
                 "max_iters": 4, "tol": 0.0, "seed": 11, "coord": "mem",
                 **args}
    spec = TaskSpec(taskfn=mod, mapfn=mod, partitionfn=mod, reducefn=mod,
                    finalfn=mod, init_args=init_args,
                    storage=f"mem:igkm-{tag}")
    ex = LocalExecutor(spec, engine=engine, max_iterations=10)
    ex.run()
    return ex, mr_kmeans.read_state("mem")


def test_kmeans_allclose_and_compile_once(capsys):
    ex_s, st_s = _run_kmeans("store", "s")
    ex_i, st_i = _run_kmeans("ingraph", "i")
    # int(shard) on a traced key: the collective tier refuses the mapfn
    # and the jit tier runs — on one device, so the reason is logged
    # once and kept, not dropped
    assert ex_i._ingraph.engine.mode == "jit"
    assert "int()" in ex_i._ingraph.engine.collective_error \
        or "Concretization" in ex_i._ingraph.engine.collective_error
    err = capsys.readouterr().err
    assert err.count("collective tier refused") == 1
    assert st_i["iter"] == st_s["iter"] == 4
    np.testing.assert_allclose(st_i["centroids"], st_s["centroids"],
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(st_i["sse"], st_s["sse"], rtol=1e-4)
    # the "loop" protocol threads fresh centroid arrays through the SAME
    # compiled program: one trace across all 4 iterations
    assert ex_i._ingraph.engine.traces == 1
    assert sum(it.ingraph_iterations for it in ex_i.stats.iterations) == 4


def test_als_allclose():
    from examples.als import mr_als
    mod = "examples.als.mr_als"

    def run(engine, tag):
        args = {"n_users": 64, "n_items": 16, "rank": 4, "density": 0.4,
                "reg": 0.1, "n_shards": 4, "max_iters": 3, "seed": 9,
                "coord": "mem"}
        spec = TaskSpec(taskfn=mod, mapfn=mod, partitionfn=mod,
                        reducefn=mod, finalfn=mod, init_args=args,
                        storage=f"mem:igals-{tag}")
        ex = LocalExecutor(spec, engine=engine, max_iterations=5)
        ex.run()
        return ex, mr_als.read_state("mem")

    ex_s, st_s = run("store", "s")
    ex_i, st_i = run("ingraph", "i")
    np.testing.assert_allclose(st_i["item_factors"], st_s["item_factors"],
                               rtol=1e-4, atol=1e-4)
    assert ex_i._ingraph.engine.traces == 1


def test_digits_sgd_allclose_collective_tier():
    from examples.digits import mr_sgd
    mod = "examples.digits.mr_sgd"

    def run(engine, tag):
        spec = TaskSpec(taskfn=mod, mapfn=mod, partitionfn=mod,
                        reducefn=mod, finalfn=mod,
                        init_args={"max_steps": 5, "seed": 2},
                        storage=f"mem:igsgd-{tag}")
        ex = LocalExecutor(spec, engine=engine, max_iterations=10)
        ex.run()
        st = mr_sgd.read_state()
        return ex, ({k: v.copy() for k, v in st["params"].items()},
                    st["val_loss"])

    ex_s, (p_s, val_s) = run("store", "s")
    ex_i, (p_i, val_i) = run("ingraph", "i")
    # numeric keys + uniform per-job emission: the COLLECTIVE tier
    # (shard_map over the mesh's dp axis) must carry this workload
    assert ex_i._ingraph.engine.mode == "shard_map"
    assert ex_i._ingraph.engine.traces == 1
    # reduce = gradient sum: every fold must lower to psum (they fell
    # back to all_gather, silently, when the sum proof could not run)
    assert set(ex_i._ingraph.engine._plan.folds.values()) == {"psum"}
    assert ex_i._ingraph.engine.collective_error is None
    for k in p_s:
        np.testing.assert_allclose(p_i[k], p_s[k], rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(val_i, val_s, rtol=1e-4)


def _sgd_reducer_probe():
    from examples.digits import mr_sgd
    mod = "examples.digits.mr_sgd"
    spec = TaskSpec(taskfn=mod, mapfn=mod, partitionfn=mod, reducefn=mod,
                    finalfn=mod, init_args={"max_steps": 1},
                    storage="mem:igsgd-probe")
    template = {"g": np.zeros((16, 8), np.float32),
                "count": np.zeros((), np.int32)}
    return spec, mr_sgd.W1, template


def test_sum_fold_proves_the_gradient_sum():
    from lua_mapreduce_tpu.engine.ingraph import (_singleton_passthrough,
                                                  _sum_fold)
    spec, key, template = _sgd_reducer_probe()
    assert _sum_fold(spec, key, template, 4) is True
    assert _singleton_passthrough(spec, key, template) is True


def test_sum_fold_says_no_only_to_a_reducer_that_is_not_a_sum(monkeypatch):
    """A reducer that needs concrete values is "not a sum" (False); an
    AttributeError out of JAX is API drift and must surface — swallowed,
    it once turned every psum fold into an all_gather without a word."""
    import types

    import jax

    from lua_mapreduce_tpu.engine import ingraph
    spec, key, template = _sgd_reducer_probe()

    def concretizing(k, values):
        return {"g": values[0]["g"] * float(values[1]["count"]),
                "count": values[0]["count"]}
    needs_values = types.SimpleNamespace(
        associative=True, commutative=True, reducefn=concretizing)
    assert ingraph._sum_fold(needs_values, key, template, 2) is False

    def drifted(*a, **kw):
        raise AttributeError("module 'jax' has no attribute 'make_jaxpr'")
    monkeypatch.setattr(jax, "make_jaxpr", drifted)
    with pytest.raises(AttributeError, match="make_jaxpr"):
        ingraph._sum_fold(spec, key, template, 2)
    with pytest.raises(AttributeError, match="make_jaxpr"):
        ingraph._singleton_passthrough(spec, key, template)


# ---------------------------------------------------------------------------
# both executors: the Server runs the compiled plane itself
# ---------------------------------------------------------------------------

def _server_store_pool(spec, n_workers=2):
    store = MemJobStore()
    server = Server(store, poll_interval=0.02, engine="store").configure(spec)
    workers = [Worker(store).configure(max_iter=400, max_sleep=0.05)
               for _ in range(n_workers)]
    threads = [threading.Thread(target=w.execute, daemon=True)
               for w in workers]
    for t in threads:
        t.start()
    stats = server.loop()
    for t in threads:
        t.join(timeout=30)
    return server, stats


def test_server_int_sum_byte_identical(igmod):
    mod = igmod("ig_sum_srv", IG_SUM)

    def spec(tag):
        return TaskSpec(taskfn=mod, mapfn=mod, partitionfn=mod,
                        reducefn="examples.wordcount.reducefn",
                        storage=f"mem:igsrv-{tag}")

    # in-graph: the server computes the data plane itself — NO workers
    sp_i = spec("i")
    server = Server(MemJobStore(), poll_interval=0.02,
                    engine="ingraph").configure(sp_i)
    stats_i = server.loop()
    assert server._ingraph.engine.traces == 1
    assert stats_i.iterations[-1].ingraph_iterations == 1
    # the engine knob is task-doc deployed (sticky on resume)
    assert server.store.get_task()["engine"] == "ingraph"

    _, stats_s = _server_store_pool(spec("s"))
    from lua_mapreduce_tpu.store.router import get_storage_from
    assert _result_bytes(get_storage_from("mem:igsrv-i")) == \
        _result_bytes(get_storage_from("mem:igsrv-s"))
    assert stats_s.iterations[-1].ingraph_iterations == 0


def test_server_kmeans_loop_matches_local_store():
    """Server-compiled kmeans ≡ LocalExecutor-interpreted kmeans
    (allclose), with the multi-iteration loop compiling once."""
    from examples.kmeans import mr_kmeans
    mod = "examples.kmeans.mr_kmeans"
    args = {"k": 4, "n": 256, "dim": 4, "n_shards": 4, "max_iters": 3,
            "tol": 0.0, "seed": 13, "coord": "mem"}
    _, st_local = _run_kmeans("store", "twin", **args)

    spec = TaskSpec(taskfn=mod, mapfn=mod, partitionfn=mod, reducefn=mod,
                    finalfn=mod, init_args=args, storage="mem:igkmsrv")
    server = Server(MemJobStore(), poll_interval=0.02,
                    engine="auto").configure(spec)
    stats = server.loop()
    st_srv = mr_kmeans.read_state("mem")
    assert st_srv["iter"] == 3
    np.testing.assert_allclose(st_srv["centroids"], st_local["centroids"],
                               rtol=1e-4, atol=1e-4)
    assert server._ingraph.engine.traces == 1
    assert sum(it.ingraph_iterations for it in stats.iterations) == 3


# ---------------------------------------------------------------------------
# oracle/runtime agreement: trace-time failure degrades (auto) / raises
# (forced) — the DESIGN §26 never-crash ladder
# ---------------------------------------------------------------------------

def test_auto_fallback_on_trace_failure_byte_identical(igmod):
    mod = igmod("ig_traced_key", IG_TRACED_KEY)
    ex_s = _local(mod, "store", "fb-s")
    ex_a = _local(mod, "auto", "fb-a")
    # the static oracle accepted it...
    assert ex_a.engine_decision.verdict == "in-graph"
    assert ex_a.engine_decision.chosen == "ingraph"
    # ...the lowering refused it at trace time, and the iteration
    # re-ran on the store plane: counted, engine retired, bytes equal
    it = ex_a.stats.iterations[-1]
    assert it.ingraph_fallbacks == 1
    assert it.ingraph_iterations == 0
    assert ex_a._ingraph.engine is None
    assert _result_bytes(ex_a.result_store) == _result_bytes(ex_s.result_store)


def test_auto_fallback_server_degrades_to_store_plane(igmod):
    """The server-side degrade: workers carry the re-run store phases,
    the task doc records engine=store (sticky for any resume)."""
    mod = igmod("ig_traced_key_srv", IG_TRACED_KEY)
    spec = TaskSpec(taskfn=mod, mapfn=mod, partitionfn=mod, reducefn=mod,
                    storage="mem:igfbsrv")
    store = MemJobStore()
    server = Server(store, poll_interval=0.02, engine="auto").configure(spec)
    workers = [Worker(store).configure(max_iter=400, max_sleep=0.05)
               for _ in range(2)]
    threads = [threading.Thread(target=w.execute, daemon=True)
               for w in workers]
    for t in threads:
        t.start()
    stats = server.loop()
    for t in threads:
        t.join(timeout=30)
    it = stats.iterations[-1]
    assert it.ingraph_fallbacks == 1 and it.ingraph_iterations == 0
    assert store.get_task()["engine"] == "store"
    # the degraded run still produced the store plane's exact bytes
    ex_s = _local(mod, "store", "fbsrv-twin")
    from lua_mapreduce_tpu.store.router import get_storage_from
    assert _result_bytes(get_storage_from("mem:igfbsrv")) == \
        _result_bytes(ex_s.result_store)


def test_hard_mode_raises_instead_of_falling_back(igmod):
    mod = igmod("ig_traced_key_hard", IG_TRACED_KEY)
    spec = TaskSpec(taskfn=mod, mapfn=mod, partitionfn=mod, reducefn=mod,
                    storage="mem:ig-hard")
    ex = LocalExecutor(spec, engine="ingraph")
    with pytest.raises(LoweringError):
        ex.run()


def test_hard_mode_forces_store_plane_task(igmod):
    """engine=ingraph on a host-bound task (oracle verdict store-plane)
    still tries — and raises at trace time instead of silently running
    the store plane: the CI mode must not mask a lost lowering."""
    src = IG_SUM.replace('emit(b, jnp.sum(jnp.where(ids == b, 1, 0)))',
                         'emit(b, sorted(value["ids"])[0])')
    mod = igmod("ig_hostbound", src)
    spec = TaskSpec(taskfn=mod, mapfn=mod, partitionfn=mod,
                    reducefn="examples.wordcount.reducefn",
                    storage="mem:ig-hard2")
    dec = select_engine(spec, "ingraph")
    assert dec.requested == "ingraph" and dec.chosen == "ingraph"
    assert dec.verdict == "store-plane"
    ex = LocalExecutor(spec, engine="ingraph")
    with pytest.raises(LoweringError):
        ex.run()


def test_auto_store_plane_task_never_crashes(igmod):
    """engine=auto on a store-plane-verdicted task never compiles the
    whole plane: the offending function is named in the reason, zero
    whole-task compiled iterations, normal output. Since DESIGN §28 the
    ladder may still take the stage-granular hybrid rung for any
    per-function leg that qualifies (tests/test_hybrid.py owns that
    surface) — what it must NOT do is choose ingraph or crash."""
    src = IG_SUM.replace('emit(b, jnp.sum(jnp.where(ids == b, 1, 0)))',
                         'emit(str(b), 1)')
    mod = igmod("ig_storeplane", src)
    ex = _local(mod, "auto", "sp-auto",
                reducefn="examples.wordcount.reducefn")
    assert ex.engine_decision.chosen in ("store", "hybrid")
    assert ex.engine_decision.verdict == "store-plane"
    assert "mapfn" in ex.engine_decision.reason
    it = ex.stats.iterations[-1]
    assert it.ingraph_iterations == 0 and it.ingraph_fallbacks == 0
    assert len(_result_bytes(ex.result_store)) > 0


def test_auto_unresolvable_spec_degrades():
    """Dict/callable module specs can't be statically checked: auto
    degrades to the store plane with a reason, never a crash."""
    spec = TaskSpec(taskfn={"taskfn": lambda e: e(0, {"v": [1.0]})},
                    mapfn={"mapfn": lambda k, v, e: e(0, v["v"][0])},
                    partitionfn={"partitionfn": lambda k: 0},
                    reducefn={"reducefn": lambda k, vs: sum(vs)},
                    storage="mem:ig-dicts")
    ex = LocalExecutor(spec, engine="auto")
    ex.run()
    assert ex.engine_decision.chosen == "store"
    assert len(_result_bytes(ex.result_store)) == 1


# ---------------------------------------------------------------------------
# knob resolution + observability
# ---------------------------------------------------------------------------

def test_engine_env_resolution(monkeypatch):
    monkeypatch.setenv("LMR_ENGINE", "store")
    assert resolve_engine(None) == "store"
    monkeypatch.setenv("LMR_ENGINE", "ingraph")
    assert resolve_engine(None) == "ingraph"
    assert resolve_engine("store") == "store"   # explicit arg wins
    monkeypatch.delenv("LMR_ENGINE")
    assert resolve_engine(None) == "auto"
    with pytest.raises(ValueError):
        resolve_engine("tpu")


def test_cli_engine_flags():
    from lua_mapreduce_tpu.cli.execute_server import \
        build_parser as server_parser
    from lua_mapreduce_tpu.cli.execute_worker import \
        build_parser as worker_parser
    args = server_parser().parse_args(
        ["mem", "t", "m", "p", "r", "--engine", "ingraph"])
    assert args.engine == "ingraph"
    assert server_parser().parse_args(["mem", "t", "m", "p", "r"]).engine \
        is None                       # None → LMR_ENGINE env → "auto"
    assert worker_parser().parse_args(
        ["mem", "--engine", "store"]).engine == "store"
    with pytest.raises(SystemExit):
        server_parser().parse_args(
            ["mem", "t", "m", "p", "r", "--engine", "gpu"])


def test_lowering_spans_and_engine_report(igmod):
    from lua_mapreduce_tpu.store.router import get_storage_from
    from lua_mapreduce_tpu.trace.collect import TraceCollection
    from lua_mapreduce_tpu.trace.span import Tracer, install_tracer

    mod = igmod("ig_sum_traced", IG_SUM)
    install_tracer(Tracer())
    try:
        ex = _local(mod, "auto", "span-i",
                    reducefn="examples.wordcount.reducefn")
    finally:
        install_tracer(None)
    col = TraceCollection.from_store(get_storage_from("mem:ig-span-i"))
    decs = col.lowering_decisions()
    assert decs and decs[0]["span"] == "lowering"
    assert decs[0]["engine"] == "ingraph"
    assert decs[0]["requested"] == "auto"
    assert decs[0]["verdict"] == "in-graph"
    assert "fn.mapfn" in decs[0]
    assert col.engines_by_iteration() == {1: "ingraph"}
    assert any(s["name"] == "ingraph.run" for s in col.spans)


def test_fallback_span_and_engine_report(igmod):
    from lua_mapreduce_tpu.store.router import get_storage_from
    from lua_mapreduce_tpu.trace.collect import TraceCollection
    from lua_mapreduce_tpu.trace.span import Tracer, install_tracer

    mod = igmod("ig_traced_key_span", IG_TRACED_KEY)
    install_tracer(Tracer())
    try:
        _local(mod, "auto", "span-fb")
    finally:
        install_tracer(None)
    col = TraceCollection.from_store(get_storage_from("mem:ig-span-fb"))
    decs = col.lowering_decisions()
    spans = [d["span"] for d in decs]
    assert spans[0] == "lowering" and "ingraph.fallback" in spans
    fb = decs[spans.index("ingraph.fallback")]
    assert "traced" in fb.get("reason", "") or "key" in fb.get("reason", "")
    # the iteration's results came from the store plane — the engine
    # map must say so (the fallback is visible above, not silent)
    assert col.engines_by_iteration() == {1: "store"}


# the review-hardening regressions: combiner normalization, int32
# overflow refusal, and the collective tier's key-value-free signature

IG_COMBINER = """
import jax.numpy as jnp

def taskfn(emit):
    for j in range(4):
        emit(j, {"v": [float(j + 1), 2.0]})

def mapfn(key, value, emit):
    v = jnp.asarray(value["v"], jnp.float32)
    emit(0, {"s": jnp.sum(v)})
    emit(0, {"s": v[0] * 2.0})

def partitionfn(key):
    return int(key) % 2

def reducefn(key, values):
    s = jnp.asarray(values[0]["s"])
    for i in range(1, len(values)):
        s = s + jnp.asarray(values[i]["s"])
    return {"s": s}

combinerfn = reducefn
reducefn.associative_reducer = True
reducefn.commutative_reducer = True
"""

IG_KEY_LOOP = """
import jax.numpy as jnp

STEP = [0]

def taskfn(emit):
    for i in range(8):
        emit(STEP[0] * 8 + i, {"v": [float(i + 1), 2.0]})

def mapfn(key, value, emit):
    v = jnp.asarray(value["v"], jnp.float32)
    emit(0, {"s": jnp.sum(v) + 0.0 * key})

def partitionfn(key):
    return 0

def reducefn(key, values):
    s = jnp.asarray(values[0]["s"])
    for i in range(1, len(values)):
        s = s + jnp.asarray(values[i]["s"])
    return {"s": s}

reducefn.associative_reducer = True
reducefn.commutative_reducer = True

def finalfn(pairs):
    STEP[0] += 1
    return False if STEP[0] >= 3 else "loop"
"""


def test_array_combiner_normalized_on_store_plane(igmod):
    """An array-returning combinerfn must serialize on the store plane
    exactly like emitted values do (to_plain at the combine sites) —
    and agree with the compiled plane that traces the same combiner."""
    mod = igmod("ig_combiner", IG_COMBINER)

    def run(engine, tag):
        spec = TaskSpec(taskfn=mod, mapfn=mod, partitionfn=mod,
                        reducefn=mod, combinerfn=mod,
                        storage=f"mem:ig-comb-{tag}")
        ex = LocalExecutor(spec, engine=engine)
        ex.run()
        return ex

    ex_s = run("store", "s")
    ex_i = run("ingraph", "i")
    out_s = _result_bytes(ex_s.result_store)
    assert out_s and out_s == _result_bytes(ex_i.result_store)


def test_int64_job_values_degrade_to_store(igmod):
    """Integers outside int32 range must NOT silently wrap on the
    compiled plane: auto degrades to the store plane (counted) and the
    exact values survive."""
    src = """
def taskfn(emit):
    for j in range(4):
        emit(j, {"ids": [3_000_000_000 + j]})

def mapfn(key, value, emit):
    emit(0, value["ids"][0])
    emit(1, value["ids"][0] % 97)

def partitionfn(key):
    return int(key) % 2
"""
    mod = igmod("ig_bigint", src)
    ex_s = _local(mod, "store", "big-s",
                  reducefn="examples.wordcount.reducefn")
    ex_a = _local(mod, "auto", "big-a",
                  reducefn="examples.wordcount.reducefn")
    assert ex_a.engine_decision.chosen == "ingraph"   # oracle accepted
    assert ex_a.stats.iterations[-1].ingraph_fallbacks == 1
    assert _result_bytes(ex_a.result_store) == _result_bytes(ex_s.result_store)


def test_collective_tier_no_retrace_on_key_values(igmod):
    """On the shard_map tier job keys ride as a traced argument: a loop
    emitting iteration-dependent NUMERIC keys must still compile once
    (the jit tier, which bakes keys, legitimately recompiles)."""
    mod = igmod("ig_key_loop", IG_KEY_LOOP)
    spec = TaskSpec(taskfn=mod, mapfn=mod, partitionfn=mod, reducefn=mod,
                    finalfn=mod, storage="mem:ig-keyloop")
    ex = LocalExecutor(spec, engine="ingraph", max_iterations=5)
    ex.run()
    assert ex._ingraph.engine.mode == "shard_map"
    assert ex._ingraph.engine.traces == 1
    assert sum(it.ingraph_iterations for it in ex.stats.iterations) == 3


def test_counter_schema():
    from lua_mapreduce_tpu.utils.stats import COUNTER_FOLD, IterationStats
    assert "ingraph_iterations" in COUNTER_FOLD
    assert "ingraph_fallbacks" in COUNTER_FOLD
    d = IterationStats(iteration=1).as_dict()
    assert d["ingraph_iterations"] == 0 and d["ingraph_fallbacks"] == 0
