"""The `session` driver end to end on the CPU at tiny test-only sizes,
for both kinds of model it serves; then with the served tokens altered
and with the selection replaced, where `correct` has to come out
false."""

import json
import os
import shutil
import time

import jax
import numpy as np
import pytest

from conftest import HERE
from perfbench import harness
from perfbench import run as bench_run
from perfbench.drivers import session as session_driver

LOG = harness.CompileLog()
LIMITS = {
    "tiny-session": {"served_logit_gap": 0.05, "served_logit_gap_mean": 0.005},
    "tiny-session-dsv32": {"served_logit_gap": 0.01,
                           "served_logit_gap_mean": 0.001,
                           "index_selection_miss": 0.01,
                           "routing_miss": 0.01},
}
SESSION_METRICS = ("serve_tokens_per_s", "request_p95_ms",
                   "device_idle_pct.serve", "programs_built_in_window.serve",
                   "request_mfu_pct.session", "decode_step_ms.session")


@pytest.fixture
def session_checkout(checkout):
    """conftest's checkout with the two tiny session cells added, as
    new files and new entries."""
    bench = os.path.join(checkout, "perfbench")
    data = os.path.join(HERE, "data")
    shutil.copy(os.path.join(data, "tiny-dsv32.json"),
                os.path.join(bench, "configs"))
    for mix in ("session-tiny", "session-tiny-dsv32"):
        shutil.copy(os.path.join(data, mix + ".json"),
                    os.path.join(bench, "traffic"))
    for name, limits in LIMITS.items():
        with open(os.path.join(bench, "limits", name + ".json"), "w") as f:
            json.dump(limits, f)
    path = os.path.join(checkout, "BENCHMARK.json")
    with open(path) as f:
        manifest = json.load(f)
    manifest["configs"].append(
        {"name": "tiny-dsv32", "source": "test only", "reduced": [],
         "file": "perfbench/configs/tiny-dsv32.json", "why": "test only"})
    cells = {"tiny-session": ("tiny", "session-tiny"),
             "tiny-session-dsv32": ("tiny-dsv32", "session-tiny-dsv32")}
    for name, (config, mix) in cells.items():
        manifest["workloads"].append({"name": name, "config": config,
                                      "traffic": mix, "chips": 1,
                                      "why": "test only"})
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        if m["name"] in SESSION_METRICS:
            m["workloads"] += list(cells)
        elif m["name"].endswith(".dsv32"):
            m["workloads"].append("tiny-session-dsv32")
    with open(path, "w") as f:
        json.dump(manifest, f)
    return checkout


class Float32(session_driver.Session):
    """The served values computed in float32. The tiny DeepSeek model
    attends 8 keys of 32 and routes to 3 experts of 16 at width 64: in
    bfloat16 a rounding moves a key or an expert across its threshold
    every few tokens, and one such move is a large part of so small a
    sum. (At the cell's size 2048 keys dilute it.)"""

    def __init__(self, cell, seed, devices):
        super().__init__(cell, seed, devices)
        self.params = {k: v.astype(np.float32)
                       for k, v in self.params.items()}


def drive(checkout, capsys, workload, seed=2 ** 31 + 77, **driver_args):
    if workload.endswith("dsv32"):
        driver_args.setdefault("make_session", Float32)
    cell = harness.Cell(workload, checkout)
    t0 = time.perf_counter()
    rc = bench_run.run_cell(cell, seed, 0.3, False, jax.devices()[:1], LOG,
                            lambda: time.perf_counter() - t0, **driver_args)
    assert rc == 0
    captured = capsys.readouterr()
    return json.loads(captured.out.strip().splitlines()[-1]), captured.err


@pytest.mark.parametrize("workload,checks", [
    ("tiny-session", ["served_logit_gap", "served_logit_gap_mean"]),
    ("tiny-session-dsv32", ["index_selection_miss", "routing_miss",
                            "served_logit_gap", "served_logit_gap_mean"])])
def test_a_session_run_ends_in_the_contracts_line(session_checkout, capsys,
                                                  workload, checks):
    line, err = drive(session_checkout, capsys, workload)
    assert list(line) == ["correct", "attempted", "failed", "metrics",
                          "device", "checks"]
    assert line["correct"] is True, err
    assert line["attempted"] > 0 and line["failed"] == 0
    assert {k: v["unit"] for k, v in line["metrics"].items()} == {
        "serve_tokens_per_s": "tokens/s", "request_p95_ms": "ms",
        "setup_s": "s"}
    assert sorted(line["checks"]) == checks
    assert "programs built in the window 0" in err
    assert "checked turns: " in err
    if workload.endswith("dsv32"):
        assert "counters held_assignments_per_token" in err
        assert "left to its own choices the reference reads" in err


class AlteredTokens(Float32):
    """Every returned token moved on by one id."""

    def one(self, index, counted=False):
        call = super().one(index, counted)

        def broken():
            call()
            self.outputs[index] = (self.outputs[index] + 1) % \
                self.cfg["vocab_size"]
        return broken


class NewestKeys(Float32):
    """The counters report the newest keys in place of the indexer's."""

    def one(self, index, counted=False):
        call = super().one(index, counted)

        def broken():
            call()
            if index in self.counters:
                sel = np.asarray(self.counters[index]["selected"])
                t = self.traffic
                newest = (t["context_len"] + np.arange(t["n_new"])[:, None]
                          - np.arange(sel.shape[-1])[None, :])
                self.counters[index] = dict(
                    self.counters[index],
                    selected=np.broadcast_to(newest[:, None, None, :],
                                             sel.shape))
        return broken


class OtherExperts(Float32):
    """The counters report every token's experts moved on by one."""

    def one(self, index, counted=False):
        call = super().one(index, counted)

        def broken():
            call()
            if index in self.counters:
                c = self.counters[index]
                self.counters[index] = dict(
                    c, experts=(np.asarray(c["experts"]) + 1)
                    % self.cfg["router_experts"])
        return broken


@pytest.mark.parametrize("workload,broken,failing", [
    ("tiny-session", AlteredTokens, "served_logit_gap"),
    ("tiny-session-dsv32", AlteredTokens, "served_logit_gap"),
    ("tiny-session-dsv32", NewestKeys, "index_selection_miss"),
    ("tiny-session-dsv32", OtherExperts, "routing_miss")])
def test_a_broken_session_is_not_correct(session_checkout, capsys, workload,
                                         broken, failing):
    line, err = drive(session_checkout, capsys, workload, make_session=broken)
    assert line["correct"] is False
    value, limit = line["checks"][failing]
    assert value > limit, err


def test_the_controls_run_without_the_program(session_checkout, capsys):
    from perfbench import controls_session
    controls_session.dsv32(
        harness.Cell("tiny-session-dsv32", session_checkout), [12], {12},
        ["int8"], jax.devices()[:1], program=False)
    rows = {r["what"]: r["readings"] for r in map(
        json.loads, capsys.readouterr().out.strip().splitlines())}
    assert "program" not in rows and len(rows) == 5
    assert rows["fault newest_keys"]["index_selection_miss"] > 0.3
    assert rows["fault no_shared"]["served_logit_gap"] > 0.05


def test_the_fed_ids_differ_by_request_and_the_context_does_not(
        session_checkout):
    cell = harness.Cell("tiny-session", session_checkout)
    s = session_driver.Session(cell, 5, jax.devices()[:1])
    assert not np.array_equal(s.fed(1), s.fed(2))
    assert np.array_equal(s.context(), s.context())
    assert s.context().shape == (4, 8) and s.fed(1).shape == (4,)


def test_the_controls_read_higher_than_the_program(session_checkout, capsys):
    """`controls_session.py` at the tiny size: the program reads low,
    and the planted faults read higher in the number that is theirs."""
    from perfbench import controls_session
    controls_session.dsv32(
        harness.Cell("tiny-session-dsv32", session_checkout), [11], {11},
        ["int8"], jax.devices()[:1], make_session=Float32)
    controls_session.mistral(
        harness.Cell("tiny-session", session_checkout), [11], {11},
        ["int8"], jax.devices()[:1])
    rows = [json.loads(line) for line
            in capsys.readouterr().out.strip().splitlines()]
    dsv = {r["what"]: r["readings"] for r in rows[:-2]}
    program = dsv["program"]
    assert program["served_logit_gap_mean"] < 1e-3, rows
    assert program["index_selection_miss"] == 0
    assert program["routing_miss"] == 0
    assert dsv["fault no_bias"]["routing_miss"] > 0.02, rows
    assert [r["what"] for r in rows[:-2]][-1] == "control int8"
    assert sorted(dsv) == ["control int8", "fault newest_keys",
                           "fault no_bias", "fault no_scale",
                           "fault no_shared", "program"]
    assert dsv["fault newest_keys"]["index_selection_miss"] > 0.3
    for what in ("fault no_shared", "fault no_scale"):
        assert dsv[what]["served_logit_gap"] > 0.05, rows
    # 16 tokens of a vocabulary of 96 have few near-tied logits to flip;
    # the rounding shows in the choices
    assert dsv["control int8"]["index_selection_miss"] > 0.01, rows
    assert dsv["control int8"]["routing_miss"] > 0.01, rows
    assert [r["what"] for r in rows[-2:]] == ["program", "control int8"]
    assert rows[-1]["readings"]["served_logit_gap_mean"] > \
        rows[-2]["readings"]["served_logit_gap_mean"]
