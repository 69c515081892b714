"""Smoke tests for the benchmark harness functions that are cheap on
CPU: the bench code itself must stay runnable between chip runs."""

import numpy as np
import pytest


@pytest.fixture(autouse=True)
def cpu_in_the_peak_tables(monkeypatch):
    """The bench functions report against utils/roofline's tables,
    which hold TPUs only (an unknown device is an error there). Give
    this box's CPU a stand-in row so the functions can be smoke-run
    here; what they print under it is not a measurement."""
    from lua_mapreduce_tpu.utils import roofline

    monkeypatch.setitem(roofline.PEAK_BF16_FLOPS, "cpu", 1e12)
    monkeypatch.setitem(roofline.PEAK_HBM_BYTES, "cpu", 1e9)


def test_bench_conv_train_lenet_smoke():
    """bench_conv_train produces finite, sane numbers on CPU at toy
    scale (same code path the TPU run takes)."""
    from benchmarks.kernel_bench import bench_conv_train

    out = bench_conv_train("lenet5_cifar", batch=4, steps=1)
    assert out["ms_per_step"] > 0
    assert out["images_per_sec"] > 0
    assert np.isfinite(out["mfu"]) and out["mfu"] >= 0
    assert "lenet5_cifar" in out["config"]


@pytest.mark.heavy
def test_bench_decode_smoke():
    """bench_decode at toy scale on CPU: sane numbers, prefill path
    actually faster-or-equal is NOT asserted (CPU timings are noise) —
    only that both paths run and the dict is well-formed."""
    from benchmarks.kernel_bench import bench_decode

    out = bench_decode(d_model=32, n_heads=4, n_layers=1, d_ff=64,
                       vocab=64, max_seq=64, prompt_len=48, n_new=8,
                       batch=2)
    assert out["prefill_total_s"] > 0 and out["scan_total_s"] > 0
    assert out["decode_tokens_per_sec"] > 0
    assert out["end_to_end_tokens_per_sec"] > 0


def test_bench_transformer_step_moe_smoke():
    """The MoE train-step bench entry at toy scale: router/capacity
    machinery + shard_params_moe must survive the exact call the TPU
    window makes (a new case must never burn a window on a crash)."""
    from benchmarks.kernel_bench import bench_transformer_step

    out = bench_transformer_step(d_model=32, n_heads=4, n_layers=1,
                                 d_ff=64, vocab=64, seq=64, batch=4,
                                 steps=2, moe_experts=2)
    assert out["tokens_per_sec"] > 0
    assert "switch-moe2x" in out["config"]


@pytest.mark.heavy
def test_bench_transformer_step_long_seq_smoke():
    """The seq-doubling entry's path (modern recipe at seq > d_ff)."""
    from benchmarks.kernel_bench import bench_transformer_step

    out = bench_transformer_step(d_model=32, n_heads=4, n_layers=1,
                                 d_ff=64, vocab=64, seq=128, batch=2,
                                 steps=2, modern=True)
    assert out["tokens_per_sec"] > 0
    assert "seq128" in out["config"]


@pytest.mark.heavy
def test_bench_decode_quantized_smoke():
    """The int8 serving copy drives the same bench (q8 path resolves
    to the XLA dequant composition off-TPU)."""
    from benchmarks.kernel_bench import bench_decode

    out = bench_decode(d_model=32, n_heads=4, n_layers=1, d_ff=64,
                       vocab=64, max_seq=64, prompt_len=48, n_new=8,
                       batch=2, quantized=True)
    assert out["decode_tokens_per_sec"] > 0


def test_bench_conv_train_unknown_model_rejected():
    from benchmarks.kernel_bench import bench_conv_train

    with pytest.raises(ValueError, match="unknown conv bench model"):
        bench_conv_train("alexnet", batch=8)


def test_bench_pair_speedup_from_unrounded_seconds(monkeypatch):
    """ADVICE r2: an op faster than the ms-rounding granularity must
    still emit speedup_pallas_vs_xla (computed from unrounded seconds),
    and FLOP-less ops get the HBM-roofline suspect_elided check."""
    import benchmarks.kernel_bench as kb

    # fake measurement: both ops "run" in 20 ns — rounds to 0.0 ms at
    # 4 decimals, which used to drop the speedup key silently
    monkeypatch.setattr(kb, "_call_overhead", lambda: 0.001)
    monkeypatch.setattr(kb, "_measure_op",
                        lambda *a, **k: (2e-8, 8))

    import jax.numpy as jnp
    x = jnp.zeros((4, 4), jnp.float32)

    def make():
        return (lambda x: x, lambda x: x, (x,), None)

    # the fixture's 1 GB/s HBM row exercises the roofline check
    out = kb._bench_pair(make)
    assert out["speedup_pallas_vs_xla"] == 1.0
    # 64 bytes in 20 ns = 3.2 GB/s > 1.1 * 1 GB/s → flagged on both
    assert out["pallas_suspect_elided"] and out["xla_suspect_elided"]


def test_attn_memory_measures_the_l2_term():
    """The compiler-reported temp bytes for the XLA attention grad must
    contain the analytic O(L²) score term — the measured basis of the
    flash auto-policy (ops/__init__.py, DESIGN.md §9). Small shape so
    the compile stays cheap on CPU."""
    from benchmarks.attn_memory import flash_analytic, xla_measured

    b, h, l, d = 1, 2, 512, 64
    meas = xla_measured(b, h, l, d)
    ana = flash_analytic(b, h, l, d)
    # fwd and grad both materialize at least one (L, L) f32 buffer
    assert meas["fwd"]["temp_bytes"] >= ana["xla_score_term_bytes"]
    assert meas["grad"]["temp_bytes"] >= 2 * ana["xla_score_term_bytes"]
    # flash residents are O(L): far below the score term at this shape
    assert ana["hbm_grad_bytes"] < ana["xla_score_term_bytes"]


def test_attn_memory_utest():
    import benchmarks.attn_memory as am

    am.utest()


@pytest.mark.heavy
def test_moe_profile_smoke():
    """benchmarks/moe_profile.py's component breakdown at toy scale on
    CPU: every timed component and both cost analyses must produce a
    number, not an error row (a crash here would burn sprint phase B's
    slice of a hardware window)."""
    from benchmarks.moe_profile import profile

    res = profile(T=64, E=4, D=16, FF=32, cap=32, target_s=0.03)
    for name in ("dense_ffn_fwd", "dense_ffn_fwdbwd", "moe_einsum_fwd",
                 "moe_einsum_fwdbwd", "moe_sorted_fwd",
                 "moe_sorted_fwdbwd", "sorted_route_and_gather_fwd",
                 "expert_ffn_only_fwd"):
        assert "ms" in res[name], (name, res[name])
        assert res[name]["ms"] >= 0
    for impl in ("einsum", "sorted"):
        assert "flops" in res[f"cost_analysis_{impl}_fwdbwd"], (
            res[f"cost_analysis_{impl}_fwdbwd"])


@pytest.mark.heavy
def test_lenet_roofline_smoke():
    """benchmarks/lenet_roofline.py at toy batch on CPU: every stage
    row must carry a time, not an error (sprint phase G)."""
    from benchmarks.lenet_roofline import profile

    res = profile(batch=8, target_s=0.03)
    for name in ("fwd_loss", "fwdbwd", "conv1_5x5_3to6", "tanh_28x28x6",
                 "pool1_pallas", "pool1_xla", "conv2_5x5_6to16",
                 "pool2_pallas", "fc_stack_400_120_84_10",
                 "control_conv_5x5_128to128_b128"):
        assert "ms" in res[name], (name, res[name])


@pytest.mark.heavy
def test_lm_convergence_quick_smoke():
    """benchmarks/lm_convergence.py --quick end to end on CPU (sprint
    phase H, the longest phase): corpus build, the word tokenizer, the
    train_lm flags, and the artifact assembly must all survive — a
    crash here would burn the biggest slice of a hardware window."""
    import json
    import subprocess
    import sys

    r = subprocess.run(
        [sys.executable, "benchmarks/lm_convergence.py", "--quick"],
        capture_output=True, text=True, timeout=540,
        cwd=__file__.rsplit("/tests/", 1)[0])
    assert r.returncode == 0, r.stderr[-800:]
    out = json.loads(r.stdout.strip().rsplit("\n", 1)[-1])
    assert out["losses"], out
    assert out["sample"] is not None
    assert out["config"]["tok"] == "word:8192"
