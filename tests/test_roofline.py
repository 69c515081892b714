"""Roofline/MFU accounting and the fixed-batch benchmark hot loop.

The reference publishes wall-clock tables only (README.md:43-113); the
build's north star is an MFU figure (BASELINE.md), so the accounting
itself needs tests: the peak tables, the MFU formula, and that
``run_steps`` (the measured hot loop) computes the same training
trajectory as discrete ``step`` calls.
"""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from lua_mapreduce_tpu.models.mlp import init_mlp, nll_loss  # noqa: E402
from lua_mapreduce_tpu.parallel.mesh import make_mesh  # noqa: E402
from lua_mapreduce_tpu.train.harness import (  # noqa: E402
    DataParallelTrainer, TrainConfig)
from lua_mapreduce_tpu.utils import roofline  # noqa: E402


def test_peak_known_generation_table():
    # table entries are per-chip bf16 figures; spot-check the bench chip
    assert roofline.PEAK_BF16_FLOPS["TPU v5 lite"] == 197e12


def test_peak_unknown_kind_raises():
    """A device that is not in the tables is an error, not a probed
    default: a utilization against a guessed peak is not a metric."""
    cpu = jax.devices()[0]
    assert cpu.device_kind not in roofline.PEAK_BF16_FLOPS
    with pytest.raises(ValueError, match="no peak bf16 FLOP/s on record"):
        roofline.peak_flops_per_s(cpu)
    with pytest.raises(ValueError, match="no peak HBM bandwidth on record"):
        roofline.peak_hbm_bytes_per_s(cpu)
    with pytest.raises(ValueError):
        roofline.mfu(1e12, 1.0, device=cpu)


def test_peak_tables_cover_the_same_devices():
    assert set(roofline.PEAK_BF16_FLOPS) == set(roofline.PEAK_HBM_BYTES)


def test_mfu_formula(monkeypatch):
    monkeypatch.setitem(roofline.PEAK_BF16_FLOPS,
                        jax.devices()[0].device_kind, 2e12)
    # 1e12 FLOPs in 1s on 1 chip of peak 2e12 → 50%
    assert roofline.mfu(1e12, 1.0, n_chips=1) == pytest.approx(0.5)
    assert roofline.mfu(1e12, 1.0, n_chips=2) == pytest.approx(0.25)


def test_run_steps_matches_discrete_steps():
    """run_steps(n) must be the same trajectory as n step() calls on the
    same fixed batch — the benchmark loop measures real training."""
    mesh = make_mesh(dp=8, mp=1)
    cfg = TrainConfig(batch_size=8, seed=0)
    rng = np.random.RandomState(0)
    x = rng.rand(8, 16).astype(np.float32)
    y = rng.randint(0, 4, 8)

    def make():
        return DataParallelTrainer(
            nll_loss, init_mlp(jax.random.PRNGKey(0), (16, 8, 4)),
            mesh, cfg)

    tr_a = make()
    losses = np.asarray(tr_a.run_steps(x, y, 3))
    tr_b = make()
    discrete = [tr_b.step(x, y) for _ in range(3)]

    assert losses.shape == (3,)
    np.testing.assert_allclose(losses, discrete, rtol=1e-5)
    np.testing.assert_allclose(
        np.asarray(tr_a.params["W0"]), np.asarray(tr_b.params["W0"]),
        rtol=1e-5)
    # loss decreases on a fixed batch: it is really optimizing
    assert losses[-1] < losses[0]


def test_run_steps_caches_compiled_fn():
    mesh = make_mesh(dp=8, mp=1)
    tr = DataParallelTrainer(
        nll_loss, init_mlp(jax.random.PRNGKey(0), (16, 8, 4)),
        mesh, TrainConfig(batch_size=8))
    rng = np.random.RandomState(0)
    x = rng.rand(8, 16).astype(np.float32)
    y = rng.randint(0, 4, 8)
    tr.run_steps(x, y, 2)
    fn = tr._steps_cache[2]
    tr.run_steps(x, y, 2)
    assert tr._steps_cache[2] is fn and len(tr._steps_cache) == 1
