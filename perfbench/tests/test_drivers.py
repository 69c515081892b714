"""Both drivers end to end on the CPU at the tiny test-only configuration:
everything a run does after the harness's look for a chip. Then the same
with the timed path broken underneath, and with the reference computed in
lower precision in the program's place: `correct` has to come out false."""

import json
import os
import subprocess
import sys
import time

import jax
import numpy as np
import pytest

from conftest import ROOT
from perfbench import compare, harness, reference
from perfbench import run as bench_run
from perfbench.drivers import decode as decode_driver
from perfbench.drivers import train as train_driver

LOG = harness.CompileLog()


def drive(checkout, capsys, workload, seed=2 ** 31 + 77, **driver_args):
    cell = harness.Cell(workload, checkout)
    if len(jax.devices()) < cell.chips:
        pytest.skip(f"needs {cell.chips} (virtual) devices")
    t0 = time.perf_counter()
    rc = bench_run.run_cell(cell, seed, 0.3, False,
                            jax.devices()[:cell.chips], LOG,
                            lambda: time.perf_counter() - t0, **driver_args)
    assert rc == 0
    captured = capsys.readouterr()
    return json.loads(captured.out.strip().splitlines()[-1]), captured.err


@pytest.mark.parametrize("workload,metrics", [
    ("tiny-train", {"train_tokens_per_s": "tokens/s", "step_p95_ms": "ms",
                    "setup_s": "s"}),
    ("tiny-train-2x2", {"train_tokens_per_s": "tokens/s",
                        "step_p95_ms": "ms", "setup_s": "s"}),
    ("tiny-decode", {"serve_tokens_per_s": "tokens/s", "request_p95_ms": "ms",
                     "setup_s": "s"})])
def test_a_run_ends_in_the_contracts_line(checkout, capsys, workload, metrics):
    line, err = drive(checkout, capsys, workload)
    assert list(line) == ["correct", "attempted", "failed", "metrics",
                          "device", "checks"]
    assert line["correct"] is True, err
    assert line["attempted"] > 0 and line["failed"] == 0
    assert {k: v["unit"] for k, v in line["metrics"].items()} == metrics
    assert all(v["value"] > 0 for v in line["metrics"].values())
    assert set(line["device"]) == {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    assert line["device"]["count"] == harness.Cell(workload, checkout).chips
    for name, (value, limit) in line["checks"].items():
        assert f"check {name}: value {value} limit {limit} ok" in err
    assert err.strip().splitlines()[-1].startswith("check ")


class StateUnchanged(train_driver.Trainer):
    """A step that returns its state as it got it."""

    def one(self, index):
        call = super().one(index)

        def broken():
            kept = jax.tree.map(lambda x: x.copy(),
                                (self.params, self.opt_state))
            call()
            self.params, self.opt_state = kept
        return broken


class HalfBatch(train_driver.Trainer):
    """Half of the batch left out, the mean taken over the rest. Only
    what the step is fed is halved: the reference, which asks `batch`
    once the window has closed, follows the whole batches."""

    def one(self, index):
        whole = self.batch

        def halved(i):
            tokens, targets = whole(i)
            half = tokens.shape[0] // 2
            return (np.concatenate([tokens[:half]] * 2),
                    np.concatenate([targets[:half]] * 2))
        self.batch = halved
        try:
            return super().one(index)
        finally:
            del self.batch


class ExchangeLeftOut(train_driver.Trainer):
    """The ring's exchange of keys and values between the chips left
    out: every hop folds the block that the chip already holds."""

    def one(self, index):
        call = super().one(index)

        def broken():
            real = jax.lax.ppermute      # the step is traced in its first call
            jax.lax.ppermute = lambda x, axis_name, perm: x
            try:
                call()
            finally:
                jax.lax.ppermute = real
        return broken


class TokenAltered(decode_driver.Decoder):
    """One served token altered where it is produced."""

    def __init__(self, *args):
        super().__init__(*args)
        inner = self.decode

        def altered(params, prompt, n_new, **kw):
            out = inner(params, prompt, n_new, **kw)
            return out.at[:, -3].set((out[:, -3] + 1) % self.cfg["vocab_size"])
        self.decode = altered


@pytest.mark.parametrize("workload,arg,broken,failing", [
    ("tiny-train", "make_trainer", StateUnchanged,
     {"grad1_worst_leaf", "delta_worst_leaf"}),
    ("tiny-train", "make_trainer", HalfBatch, {"grad1_worst_leaf"}),
    ("tiny-train-2x2", "make_trainer", ExchangeLeftOut,
     {"grad1_error_worst_leaf"}),
    ("tiny-decode", "make_decoder", TokenAltered, {"served_logit_gap"})])
def test_a_broken_timed_path_is_not_correct(checkout, capsys, workload, arg,
                                            broken, failing):
    line, err = drive(checkout, capsys, workload, **{arg: broken})
    assert line["correct"] is False
    limits = harness.Cell(workload, checkout).limits
    failed = {k for k, (v, lim) in line["checks"].items() if not v <= lim}
    assert failing <= failed, (failed, err)
    assert all(f"check {k}:" in err and "NOT OK" in err for k in failed)
    assert set(line["checks"]) == set(limits)


def test_a_state_left_unchanged_reads_one(checkout):
    cell = harness.Cell("tiny-train", checkout)
    trainer = StateUnchanged(cell, 9, jax.devices()[:1])
    prog = train_driver.first_steps(trainer, 3)
    ref = reference.train_readings(
        cell.config, 9, [trainer.batch(n) for n in range(3)], trainer.adam)
    readings, _ = compare.train(prog, ref)
    assert readings["grad1_worst_leaf"] == pytest.approx(1.0)
    assert readings["delta_worst_leaf"] == pytest.approx(1.0)


@pytest.mark.parametrize("seed", [11, 12, 13])
def test_the_training_control_is_not_correct(checkout, seed):
    """The reference in the program's place, its matmuls in the precision
    below the one that the tiny cell's limits were set for."""
    cell = harness.Cell("tiny-train", checkout)
    batches = [train_driver.batch_of(cell.config, cell.traffic, seed, n)
               for n in range(3)]
    adam = cell.traffic["adam"]
    ref = reference.train_readings(cell.config, seed, batches, adam)
    low = reference.train_readings(cell.config, seed, batches, adam,
                                   mode="fp8")
    checks = harness.judge(compare.train(low, ref)[0], cell.limits)
    assert not all(c["ok"] for c in checks.values()), checks
    same = harness.judge(compare.train(ref, ref)[0], cell.limits)
    assert all(c["ok"] for c in same.values())


@pytest.mark.parametrize("seed", [21, 22, 23])
def test_the_serving_control_is_not_correct(checkout, seed):
    cell = harness.Cell("tiny-decode", checkout)
    t = cell.traffic
    decoder = decode_driver.Decoder(cell, seed, jax.devices()[:1])
    decoder.one(1)()
    rows = np.asarray(decoder.outputs[1])
    gaps = reference.decode_logit_gaps(cell.config, seed, rows,
                                       t["prompt_len"], modes=("int4",))
    limit = cell.limits["served_logit_gap"]
    assert compare.decode(gaps["served"])["served_logit_gap"] <= limit
    assert compare.decode(gaps["int4"])["served_logit_gap"] > limit


def test_judge_fails_what_has_no_limit_or_no_reading():
    checks = harness.judge({"a": 0.1, "b": float("nan"), "c": 0.0},
                           {"a": 0.2, "b": 1.0, "d": 1.0})
    assert [checks[k]["ok"] for k in "abcd"] == [True, False, False, False]


@pytest.mark.parametrize("bare", [False, True])
def test_no_result_without_a_tpu_or_without_the_program(tmp_path, bare):
    """On the CPU the command exits non-zero and prints no result; so it
    does in a directory that holds only BENCHMARK.json and `paths`."""
    import shutil
    cwd = ROOT
    if bare:
        shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
        shutil.copytree(os.path.join(ROOT, "perfbench"), tmp_path / "perfbench",
                        ignore=shutil.ignore_patterns("__pycache__"))
        cwd = str(tmp_path)
    env = dict(os.environ, JAX_PLATFORMS="" if bare else "cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    env.pop("PYTHONPATH", None)
    done = subprocess.run(
        [sys.executable, "perfbench/run.py", "--workload",
         "mistral7b-train-1chip", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=cwd, env=env, capture_output=True, text=True,
        timeout=120)
    assert done.returncode != 0
    assert done.stdout.strip() == ""
