"""CLI launchers (cli/execute_server, cli/execute_worker,
cli/remove_results): the reference's L7 layer (execute_server.lua,
execute_worker.lua, remove_results.sh — SURVEY.md §2.2) driven
end-to-end in-process."""

import glob
import os

import pytest

from examples.wordcount.naive import naive_wordcount
from lua_mapreduce_tpu.cli import (execute_server, execute_worker,
                                   remove_results)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CORPUS = sorted(glob.glob(os.path.join(REPO, "examples", "wordcount",
                                       "[a-z]*.py")))


def test_execute_server_inline_workers(tmp_path, capsys):
    """Full wordcount through the server CLI with an in-process pool,
    slash-path module normalization included (execute_server.lua:37-39)."""
    import examples.wordcount.finalfn as finalfn
    finalfn.counts.clear()
    # taskfn reads files from init args
    rc = execute_server.main([
        "mem",
        "examples/wordcount/taskfn.py",
        "examples/wordcount/mapfn",
        "examples.wordcount.partitionfn",
        "examples.wordcount.reducefn",
        "--finalfn", "examples.wordcount.finalfn",
        "--inline-workers", "2",
        "--poll", "0.02",
        "--init-arg", f"files={os.pathsep.join(CORPUS)}",
        "--quiet",
    ])
    assert rc == 0
    golden = naive_wordcount(CORPUS)
    assert dict(finalfn.counts) == golden


def test_execute_worker_rejects_bad_phase():
    with pytest.raises(SystemExit):
        execute_worker.main(["/tmp/nowhere", "--phases", "bogus"])


def test_execute_server_strict_flag_parses():
    args = execute_server.build_parser().parse_args(
        ["mem", "a", "b", "c", "d", "--strict"])
    assert args.strict is True


def test_batch_k_flags_parse():
    """The batch-lease knob on both launchers: the server flag is the
    fleet default (task doc), the worker flag an explicit override
    (None = follow the doc)."""
    args = execute_server.build_parser().parse_args(
        ["mem", "a", "b", "c", "d", "--batch-k", "16"])
    assert args.batch_k == 16
    args = execute_worker.build_parser().parse_args(["/tmp/x"])
    assert args.batch_k is None and args.max_jobs is None
    args = execute_worker.build_parser().parse_args(
        ["/tmp/x", "--batch-k", "8", "--max-jobs", "40"])
    assert args.batch_k == 8 and args.max_jobs == 40


def test_segment_format_flags_parse():
    """The spill-encoding knob on both launchers: server flag = fleet
    default (task doc), worker flag = explicit per-host pin (None =
    follow the doc); bogus values are rejected at parse time."""
    import pytest

    args = execute_server.build_parser().parse_args(
        ["mem", "a", "b", "c", "d", "--segment-format", "v2"])
    assert args.segment_format == "v2"
    args = execute_server.build_parser().parse_args(
        ["mem", "a", "b", "c", "d"])
    assert args.segment_format == "v1"
    args = execute_worker.build_parser().parse_args(["/tmp/x"])
    assert args.segment_format is None
    args = execute_worker.build_parser().parse_args(
        ["/tmp/x", "--segment-format", "v1"])
    assert args.segment_format == "v1"
    with pytest.raises(SystemExit):
        execute_server.build_parser().parse_args(
            ["mem", "a", "b", "c", "d", "--segment-format", "v3"])


def test_execute_server_segment_v2_end_to_end(capsys):
    """End-to-end through the server CLI with --segment-format v2:
    inline workers pick the format up from the task document and the
    result matches the naive oracle (results themselves stay v1)."""
    import examples.wordcount.finalfn as finalfn
    finalfn.counts.clear()
    rc = execute_server.main([
        "mem",
        "examples.wordcount.taskfn",
        "examples.wordcount.mapfn",
        "examples.wordcount.partitionfn",
        "examples.wordcount.reducefn",
        "--finalfn", "examples.wordcount.finalfn",
        "--inline-workers", "2",
        "--poll", "0.02",
        "--segment-format", "v2",
        "--init-arg", f"files={os.pathsep.join(CORPUS)}",
        "--quiet",
    ])
    assert rc == 0
    assert dict(finalfn.counts) == naive_wordcount(CORPUS)


def test_execute_server_batched_inline_workers(tmp_path, capsys):
    """End-to-end through the server CLI with --batch-k: inline workers
    inherit the lease size from the task document and the result still
    matches the naive oracle."""
    import examples.wordcount.finalfn as finalfn
    finalfn.counts.clear()
    rc = execute_server.main([
        "mem",
        "examples.wordcount.taskfn",
        "examples.wordcount.mapfn",
        "examples.wordcount.partitionfn",
        "examples.wordcount.reducefn",
        "--finalfn", "examples.wordcount.finalfn",
        "--inline-workers", "2",
        "--poll", "0.02",
        "--batch-k", "4",
        "--init-arg", f"files={os.pathsep.join(CORPUS)}",
        "--quiet",
    ])
    assert rc == 0
    assert dict(finalfn.counts) == naive_wordcount(CORPUS)


def test_remove_results_drops_store_and_files(tmp_path):
    from lua_mapreduce_tpu.coord.filestore import FileJobStore
    from lua_mapreduce_tpu.coord.jobstore import make_job
    from lua_mapreduce_tpu.store.router import get_storage_from

    coord = str(tmp_path / "coord")
    spill = str(tmp_path / "spill")
    store = FileJobStore(coord)
    store.insert_jobs("map_jobs", [make_job("k", 1)])
    store.put_task({"_id": "unique", "status": "MAP", "spec": {}})
    data = get_storage_from(f"shared:{spill}")
    b = data.builder()
    b.write("x\n")
    b.build("result.P0")

    rc = remove_results.main([coord, "--storage", f"shared:{spill}",
                              "--yes"])
    assert rc == 0
    assert store.get_task() is None
    assert sum(store.counts("map_jobs").values()) == 0
    assert data.list("result.P*") == []


def test_remove_results_aborts_without_confirmation(tmp_path, monkeypatch):
    monkeypatch.setattr("builtins.input", lambda *_: "n")
    coord = str(tmp_path / "coord")
    from lua_mapreduce_tpu.coord.filestore import FileJobStore
    FileJobStore(coord).put_task({"_id": "unique", "status": "MAP",
                                  "spec": {}})
    rc = remove_results.main([coord])
    assert rc == 1
    assert FileJobStore(coord).get_task() is not None


@pytest.mark.heavy
def test_lm_example_smoke():
    """The long-context LM demo must run end to end on a virtual mesh
    with nothing but ``JAX_PLATFORMS=cpu`` and the device count in its
    environment."""
    import os
    import subprocess
    import sys

    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               JAX_PLATFORMS="cpu")
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    out = subprocess.run(
        [sys.executable, "-m", "examples.lm.train_lm", "--steps", "2",
         "--seq", "32", "--dp", "2", "--sp", "2", "--grad-accum", "1",
         "--batch", "4"],
        capture_output=True, text=True, timeout=300, cwd=REPO, env=env)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "done: final loss" in out.stdout
    # the first step's table says where the optimizer state lives: split
    # by rows over the four devices
    (held,) = [line for line in out.stdout.splitlines()
               if line.startswith("optimizer state: ")]
    assert held.endswith(" whole") and not held.endswith(" 0 split, 0 whole")
