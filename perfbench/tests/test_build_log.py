"""`readers/build_log.py` over two recorded logs (`data/build_log_*.json`:
one run each of `mistral7b-train-1chip` and `mistral7b-decode-longctx-1chip`
on the chip, `--trace 1`, warm compile cache; PR 36), and over the
process's own log."""

import glob
import json
import os

import pytest

from conftest import HERE, ROOT
from perfbench import harness
from perfbench.readers import build_log

OWN = ["lm_train_step", "greedy_decode", "decode_from", "session_prefill"]
METRICS = {"start_s.setup": {"part": "start"},
           "trace_lower_s.setup": {"part": "trace_lower", "programs": OWN},
           "build_s.setup": {"part": "build", "programs": OWN},
           "prefill_run_s.setup": {"part": "after",
                                   "programs": ["session_prefill"]}}
SESSION_CELLS = ["dsv32-session-decode-32k-1chip",
                 "mistral7b-decode-longctx-1chip",
                 "sarvam105b-session-decode-32k-1chip",
                 "phi4flash-reason-decode-16k-1chip"]


def recorded(name):
    with open(os.path.join(HERE, "data", f"build_log_{name}.json")) as f:
        return json.load(f)


@pytest.fixture(params=["train", "session"])
def log(request):
    return recorded(request.param)


def set_up(log):
    """The rows before the reference's first program."""
    names = [r["program"] for r in log["rows"]]
    first = min(names.index(n) for n in build_log.reference_programs()
                if n in names)
    return log["rows"][:first]


def only(log, program):
    return [r for r in set_up(log) if r["program"] == program]


def test_start_is_the_process_start_to_the_first_build(log):
    got = build_log.read({"build_log": log}, "start")
    assert got == log["rows"][0]["t0"] - log["process_start"]
    assert 1.0 < got < 30.0


def test_trace_lower_and_build_sum_the_named_programs(log):
    context = {"build_log": log}
    rows = [r for r in set_up(log) if r["program"] in OWN]
    assert rows
    assert build_log.read(context, "trace_lower", programs=OWN) == sum(
        r["trace_s"] + r["lower_s"] for r in rows)
    assert build_log.read(context, "build", programs=OWN) == sum(
        r["build_s"] for r in rows)
    one = rows[0]["program"]
    assert build_log.read(context, "build", programs=[one]) == sum(
        r["build_s"] for r in only(log, one))


def test_the_train_cells_own_program_is_the_step():
    log = recorded("train")
    (step,) = only(log, "lm_train_step")
    context = {"build_log": log}
    assert build_log.read(context, "trace_lower", programs=OWN) == \
        step["trace_s"] + step["lower_s"] > 1.0
    assert build_log.read(context, "build", programs=OWN) == step["build_s"]
    assert step["cache"] == "hit"
    assert build_log.read(context, "after", ["session_prefill"]) is None


def test_the_session_cells_prefill_run_is_after_its_build():
    log = recorded("session")
    (prefill,) = only(log, "session_prefill")
    assert len(only(log, "decode_from")) >= 1
    context = {"build_log": log}
    assert build_log.read(context, "after", ["session_prefill"]) == \
        prefill["after_s"] > 0.1
    both = only(log, "session_prefill") + only(log, "decode_from")
    assert build_log.read(context, "trace_lower", programs=OWN) == sum(
        r["trace_s"] + r["lower_s"] for r in sorted(
            both, key=lambda r: r["t0"]))


def test_the_log_is_read_up_to_the_references_first_program(log, capsys):
    """The reference is built after the window (and the readers' second
    trace of a train cell after it, which builds `lm_train_step` once
    more): none of that is set-up."""
    names = [r["program"] for r in log["rows"]]
    first = min(names.index(n) for n in build_log.reference_programs()
                if n in names)
    assert 0 < first < len(names) - 1, "the recording holds the reference"
    mine = log["rows"][:first]
    assert build_log.read({"build_log": log}, "build") == sum(
        r["build_s"] for r in mine)
    table = [line for line in capsys.readouterr().err.splitlines()
             if line.startswith("build log:  ")][1:]
    assert [line.split("  (traces")[0].split()[-1] for line in table] == \
        names[:first]
    later = [r for r in log["rows"][first:] if r["program"] in OWN]
    if later:       # the train cell's second trace: not counted
        assert build_log.read({"build_log": log}, "build", OWN) == sum(
            r["build_s"] for r in mine if r["program"] in OWN)


def jitted(path):
    """The functions a module decorates with `jax.jit`, plainly or
    through `functools.partial`."""
    import ast
    with open(path) as f:
        tree = ast.parse(f.read())
    return {node.name for node in ast.walk(tree)
            if isinstance(node, ast.FunctionDef)
            and any("jax.jit" in ast.unparse(d) for d in node.decorator_list)}


def reference_modules():
    return sorted(os.path.basename(p)[:-3] for p in glob.glob(
        os.path.join(ROOT, "perfbench", "reference*.py")))


@pytest.mark.parametrize("module", reference_modules())
def test_a_references_file_lists_every_function_the_module_jits(module):
    """A reference that jits under a name its file lacks would have its
    rows, and the readers' second trace after them, read as set-up."""
    spec = harness.load_json(ROOT, "perfbench", "reference_programs",
                             module + ".json")
    assert set(spec) == {"after_window", "in_setup_too"}
    assert not set(spec["after_window"]) & set(spec["in_setup_too"])
    assert jitted(os.path.join(ROOT, "perfbench", module + ".py")) == \
        set(spec["after_window"]) | set(spec["in_setup_too"])
    assert spec["after_window"], "a reference that ends no set-up"
    assert set(spec["after_window"]) <= build_log.reference_programs()


def test_what_a_driver_builds_in_set_up_too_ends_nothing():
    """The train driver reads the first gradient with the reference's
    own `leaf_norms` and `sample_rows`, before the window."""
    log = recorded("train")
    names = [r["program"] for r in set_up(log)]
    assert "leaf_norms" in names and "sample_rows" in names
    assert not {"leaf_norms", "sample_rows"} & build_log.reference_programs()
    assert [os.path.basename(p) for p in sorted(glob.glob(os.path.join(
        ROOT, "perfbench", "reference_programs", "*.json")))] == [
        m + ".json" for m in reference_modules()]


def test_the_table_is_printed_once_a_run(log, capsys):
    context = {"build_log": log}
    for part in ("start", "trace_lower", "build", "after"):
        build_log.read(context, part, programs=OWN)
    err = capsys.readouterr().err
    assert err.count("process start to first build") == 1
    assert err.count("more rows not kept") == 1
    assert err.count("hits, ") == 1
    program = "lm_train_step" if only(log, "lm_train_step") else "decode_from"
    assert f" {program}" in err
    build_log.read({"build_log": log}, "start")
    assert "process start to first build" in capsys.readouterr().err


def test_none_only_where_the_process_start_is_missing(log):
    context = {"build_log": dict(log, process_start=None)}
    assert build_log.read(context, "start") is None
    assert build_log.read(context, "trace_lower", programs=OWN) > 0
    assert build_log.read(context, "build", programs=OWN) > 0
    assert build_log.read(context, "build", programs=["no_such"]) is None
    assert build_log.read({"build_log": dict(log, rows=[])}, "start") is None
    with pytest.raises(ValueError, match="no part"):
        build_log.read({"build_log": log}, "nothing")


def test_a_program_without_the_log_gives_nothing(monkeypatch):
    """The parent's side of a pair: `utils/profiling.py` before PR 36."""
    from lua_mapreduce_tpu.utils import profiling
    monkeypatch.delattr(profiling, "build_log")
    for args in METRICS.values():
        assert build_log.read({}, **args) is None


def test_the_processes_own_log_is_read_where_none_is_handed_in(capsys):
    import jax
    import jax.numpy as jnp

    import lua_mapreduce_tpu.models.transformer  # noqa: F401  (as a driver)

    @jax.jit
    def session_prefill(x):
        return jnp.tanh(x).sum()
    session_prefill(jnp.ones((4, 4))).block_until_ready()
    context = {}
    assert build_log.read(context, "start") > 0
    assert build_log.read(context, "trace_lower", programs=OWN) > 0
    assert build_log.read(context, "build", programs=OWN) > 0
    assert context["build_log_printed"] is True
    assert " session_prefill" in capsys.readouterr().err


def test_the_four_metrics_are_the_last_entries_and_files_beside_the_old():
    manifest = harness.load_json(ROOT, "BENCHMARK.json")
    cells = [w["name"] for w in manifest["workloads"]]
    last = manifest["per_layer"][-4:]
    assert [m["name"] for m in last] == list(METRICS)
    for m in last:
        assert (m["unit"], m["better"], m["source"], m["moves"]) == \
            ("s", "lower", "program_counter", "setup_s")
        spec = harness.load_json(ROOT, "perfbench", "layer_metrics",
                                 m["name"] + ".json")
        assert spec == {"reader": "build_log", "args": METRICS[m["name"]]}
    assert [m["layer"] for m in last] == 3 * ["launchers and bootstrap"] + [
        "model"]
    assert [m["workloads"] for m in last] == 3 * [cells] + [SESSION_CELLS]


@pytest.mark.parametrize("cell,recording", [
    ("mistral7b-train-1chip", "train"),
    ("mistral7b-decode-longctx-1chip", "session")])
def test_a_cell_reports_each_of_its_setup_metrics(cell, recording):
    """As `harness.layer_metrics` reads them: every `.setup` metric the
    cell lists comes back a finite number."""
    import importlib
    import math
    cell = harness.Cell(cell)
    context = {"build_log": recorded(recording)}
    names = [m["name"] for m in cell.metrics("per_layer")
             if m["name"].endswith(".setup")]
    assert names == list(METRICS)[:4 if recording == "session" else 3]
    for name in names:
        spec = cell.data("layer_metrics", name)
        reader = importlib.import_module(f"perfbench.readers.{spec['reader']}")
        value = reader.read(context, **spec.get("args", {}))
        assert math.isfinite(value) and value > 0, name
