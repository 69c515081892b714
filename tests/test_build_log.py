"""The build log (utils/profiling.BuildLog): one row for every program
the process builds, from JAX's monitoring events, with the trace, the
lowering and the compile (or the persistent cache's fetch) timed by
program name. Each test but the subprocess ones reads a log of its own,
registered beside the process's: that one keeps its first rows only."""

import json
import os
import subprocess
import sys
import threading
import time

import jax
import jax.numpy as jnp
import pytest

from lua_mapreduce_tpu.trace.span import Tracer, install_tracer
from lua_mapreduce_tpu.utils import profiling

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def log():
    log = profiling.BuildLog().register()
    yield log
    jax.monitoring.unregister_event_time_span_listener(log._span)
    jax.monitoring.unregister_event_listener(log._event)


def nested(scale):
    """A fresh jitted ``outer`` that calls a jitted ``inner``."""
    @jax.jit
    def inner(x):
        return jnp.tanh(x * scale) + 1.0

    @jax.jit
    def outer(x):
        return inner(x).sum() + inner(x + 1.0).sum()
    return outer


def only(rows, program):
    (row,) = [r for r in rows if r["program"] == program]
    return row


def test_an_enclosed_jit_makes_no_row_of_its_own(log):
    nested(2.0)(jnp.ones((4, 4)))
    rows = log.rows()
    row = only(rows, "outer")
    assert [r["program"] for r in rows].count("inner") == 0
    assert "inner" in [e["program"] for e in row["enclosed"]]
    assert len(row["enclosed"]) <= 3
    longest = [e["trace_s"] for e in row["enclosed"]]
    assert longest == sorted(longest, reverse=True)
    assert all(0 < e["trace_s"] < row["trace_s"] for e in row["enclosed"])


def test_the_three_times_are_positive_and_lie_inside_the_row(log):
    nested(3.0)(jnp.ones((4, 4)))
    row = only(log.rows(), "outer")
    assert row["trace_s"] > 0 and row["lower_s"] > 0 and row["build_s"] > 0
    whole = row["t1"] - row["t0"]
    assert row["trace_s"] + row["lower_s"] + row["build_s"] <= whole
    assert time.time() - 60 < row["t0"] < row["t1"] <= time.time()
    assert set(row) == {"program", "trace_s", "lower_s", "build_s", "cache",
                        "t0", "t1", "enclosed", "after_s"}


def test_a_call_that_builds_nothing_adds_no_row(log):
    f, x = nested(4.0), jnp.ones((4, 4))
    f(x)
    before, counts = log.rows(), log.counts()
    for _ in range(3):
        f(x).block_until_ready()
    assert log.rows() == before and log.counts() == counts
    assert log.programs == len(before)


def test_after_s_runs_to_the_next_rows_start(log):
    x = jnp.ones((4, 4))
    nested(5.0)(x)
    time.sleep(0.05)
    nested(6.0)(x)
    first, second = [r for r in log.rows() if r["program"] == "outer"]
    assert second["after_s"] is None
    rows = log.rows()
    for row, nxt in zip(rows, rows[1:]):
        assert row["after_s"] == nxt["t0"] - row["t1"]
    gaps = sum(r["after_s"] for r in rows[rows.index(first):-1])
    assert gaps >= 0.05
    # rows from a number on: what a loop asks for after a compare
    assert log.rows(log.programs - 1) == [second]


def test_registering_twice_adds_no_second_listener():
    from jax._src import monitoring
    first = profiling.build_log()
    spans = monitoring.get_event_time_span_listeners()
    events = monitoring.get_event_listeners()
    assert profiling.build_log() is first
    assert monitoring.get_event_time_span_listeners() == spans
    assert monitoring.get_event_listeners() == events
    assert spans.count(first._span) == 1 and events.count(first._event) == 1


def test_a_build_records_no_span(log, monkeypatch):
    """The log is the builds' one sink: no lmr-trace span is made for a
    row, with a Tracer installed or without (no LM launcher drains one),
    and the LM path's host spans are as they were."""
    monkeypatch.delenv("LMR_TRACE", raising=False)
    tracer, x = Tracer(), jnp.ones((4, 4))
    install_tracer(tracer)
    try:
        nested(7.0)(x)
    finally:
        install_tracer(None)
    nested(8.0)(x)
    assert len([r for r in log.rows() if r["program"] == "outer"]) == 2
    assert tracer.drain() == []
    assert profiling.LM_HOST_SPANS == ("lm.shard_batch",)
    assert not hasattr(profiling, "LM_BUILD_SPANS")


def test_a_trace_nothing_lowered_is_not_paired_rows_later(log):
    """``eval_shape`` traces and lowers nothing; JAX serves the later
    call's trace from its cache. The old trace must not pull the row's
    ``t0`` back over the rows built in between."""
    @jax.jit
    def shaped(x):
        return jnp.sin(x).sum()
    x = jnp.ones((4, 4))
    jax.eval_shape(shaped, x)
    assert [t["program"] for t in log._tls.traces].count("shaped") == 1
    nested(9.5)(x)
    shaped(x)
    rows = log.rows()
    between, row = only(rows, "outer"), only(rows, "shaped")
    assert row["t0"] >= between["t1"]
    assert row["lower_s"] > 0 and row["build_s"] > 0
    assert row["trace_s"] + row["lower_s"] + row["build_s"] <= (
        row["t1"] - row["t0"])
    assert all(r["after_s"] >= 0 for r in rows[:-1])
    # and where JAX fires no event for the trace it serves (the events
    # by hand, on a log no listener feeds)
    quiet, (tr, lo, bu) = profiling.BuildLog(), (
        profiling._TRACE_EVENT, profiling._LOWER_EVENT,
        profiling._BUILD_EVENT)
    quiet._span(tr, 1.0, 2.0, fun_name="f")
    quiet._span(tr, 3.0, 4.0, fun_name="g")
    quiet._span(lo, 4.0, 5.0, fun_name="jit(g)")
    quiet._span(bu, 5.0, 6.0, fun_name="jit(g)")
    quiet._span(lo, 7.0, 8.0, fun_name="jit(f)")
    quiet._span(bu, 8.0, 9.0, fun_name="jit(f)")
    g, f = quiet.rows()
    assert (g["t0"], g["trace_s"], g["after_s"]) == (3.0, 1.0, 1.0)
    assert (f["t0"], f["trace_s"], f["lower_s"]) == (7.0, 0.0, 1.0)
    assert quiet._tls.traces == []


def test_the_kept_traces_have_a_cap_of_their_own(log, monkeypatch):
    monkeypatch.setattr(profiling.BuildLog, "MAX_KEPT", 2)
    assert profiling.BuildLog.MAX_ROWS == 4096
    x = jnp.ones((4, 4))
    for k in range(5):
        jax.eval_shape(jax.jit(lambda x, k=k: x * k), x)
    assert len(log._tls.traces) == 2
    nested(9.7)(x)
    row = only(log.rows(), "outer")
    assert row["trace_s"] > 0 and row["enclosed"]


def test_an_earlier_lowering_takes_the_trace(log):
    """``lower()`` ahead of time, another program built in between, then
    the call: the row holds the compile, and the phases that ran for
    another row's sake read 0."""
    @jax.jit
    def ahead(x):
        return jnp.cos(x).sum()
    x = jnp.ones((4, 4))
    lowered = ahead.lower(x)
    nested(9.0)(x)
    lowered.compile()
    row = only(log.rows(), "ahead")
    assert row["trace_s"] == 0.0 and row["lower_s"] == 0.0
    assert row["build_s"] == row["t1"] - row["t0"] > 0
    assert only(log.rows(), "outer")["trace_s"] > 0


def test_builds_on_other_threads_pair_their_own_events(log):
    x = jnp.ones((4, 4))

    def build(k):
        nested(10.0 + k)(x)
    threads = [threading.Thread(target=build, args=(k,)) for k in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    rows = [r for r in log.rows() if r["program"] == "outer"]
    assert len(rows) == 4
    for row in rows:
        assert row["trace_s"] > 0 and row["lower_s"] > 0
        assert (row["trace_s"] + row["lower_s"] + row["build_s"]
                <= row["t1"] - row["t0"])
        assert "inner" in [e["program"] for e in row["enclosed"]]
    assert log.counts()["programs"] == log.programs == len(log.rows())


def test_the_log_keeps_its_first_rows_and_counts_the_rest(log, monkeypatch):
    monkeypatch.setattr(profiling.BuildLog, "MAX_ROWS", 2)
    x = jnp.ones((4, 4))
    before = log.programs
    for k in range(3):
        nested(20.0 + k)(x)
    assert len(log.rows()) == 2
    assert log.programs - before >= 3
    assert log.dropped == log.programs - 2


def test_process_start(monkeypatch):
    start = profiling.BuildLog().process_start
    assert start is not None and 0 < time.time() - start < 24 * 3600
    # the process began before this module was imported
    assert start < os.stat(f"/proc/{os.getpid()}").st_ctime + 1.0
    import builtins
    real = builtins.open

    def no_proc(path, *a, **kw):
        if str(path).startswith("/proc/"):
            raise FileNotFoundError(path)
        return real(path, *a, **kw)
    monkeypatch.setattr(builtins, "open", no_proc)
    assert profiling.BuildLog().process_start is None


def test_the_table_the_launchers_print(log):
    nested(30.0)(jnp.ones((4, 4)))
    rows = log.rows()
    text = profiling.build_table(rows, log.process_start).splitlines()
    assert all(line.startswith("build log: ") for line in text)
    assert "process start to first build" in text[0]
    assert text[1].split()[2:] == ["at_s", "trace_s", "lower_s", "build_s",
                                   "cache", "after_s", "program"]
    assert len(text) == 2 + len(rows)
    (line,) = [t for t in text if " outer" in t]
    assert "traces inside: inner" in line
    # with no start on record the first row is the origin
    text = profiling.build_table(rows).splitlines()
    assert len(text) == 1 + len(rows) and " 0.000 " in text[1]
    assert profiling.build_table([]).count("\n") == 0


_CHILD = """
import json, sys
import jax, jax.numpy as jnp
if sys.argv[1] != "none":
    jax.config.update("jax_compilation_cache_dir", sys.argv[1])
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
import lua_mapreduce_tpu.models.transformer   # and nothing else of the repo
from lua_mapreduce_tpu.utils import profiling

@jax.jit
def cached_program(x):
    return jnp.tanh(x @ x).sum()

cached_program(jnp.ones((8, 8))).block_until_ready()
log = profiling._build_log
print(json.dumps({"rows": log.rows(), "counts": log.counts(),
                  "start": log.process_start}))
"""


def child(cache_dir):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    out = subprocess.run(
        [sys.executable, "-c", _CHILD, str(cache_dir)], cwd=REPO, env=env,
        capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    return json.loads(out.stdout.splitlines()[-1])


@pytest.fixture(scope="module")
def cold_then_warm(tmp_path_factory):
    cache_dir = tmp_path_factory.mktemp("jax_cache")
    return child(cache_dir), child(cache_dir)


def test_importing_the_model_alone_makes_the_log_live(cold_then_warm):
    """What the benchmark's three drivers rely on: they import
    ``models.transformer`` before they build a program."""
    cold, _ = cold_then_warm
    row = only(cold["rows"], "cached_program")
    assert row["trace_s"] > 0 and row["build_s"] > 0
    assert cold["counts"]["programs"] == len(cold["rows"])
    assert 0 < cold["rows"][0]["t0"] - cold["start"] < 300


def test_a_cold_build_reads_miss_and_a_warm_one_hit(cold_then_warm):
    cold, warm = cold_then_warm
    assert only(cold["rows"], "cached_program")["cache"] == "miss"
    assert only(warm["rows"], "cached_program")["cache"] == "hit"
    assert cold["counts"]["persistent_cache_misses"] >= 1
    assert warm["counts"]["persistent_cache_hits"] >= 1
    assert warm["counts"]["persistent_cache_misses"] == 0
    assert all(r["cache"] == "hit" for r in warm["rows"])


def test_with_no_persistent_cache_a_row_reads_off():
    off = child("none")
    assert only(off["rows"], "cached_program")["cache"] == "off"
    assert {r["cache"] for r in off["rows"]} == {"off"}
    assert off["counts"]["persistent_cache_hits"] == 0
    assert off["counts"]["persistent_cache_misses"] == 0
