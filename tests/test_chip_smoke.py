"""chip_smoke.py on the CPU: it must refuse to run here, and each of
its stages must still work as a function — at a tiny size, on the
virtual 8-device mesh, with XLA attention (and interpreted kernels for
the kernel stage) standing in for what only a chip compiles."""

import json
import os
import subprocess
import sys

import jax
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402

LM = dict(vocab=64, d_model=32, n_heads=4, n_layers=1, d_ff=64)
TRAIN = dict(batch=4, seq=32, steps=4, n_kv_heads=2)
DECODE = dict(batch=2, prompt_len=24, n_new=8, max_seq=32, requests=2)


@pytest.fixture(scope="module")
def log():
    """A build log of this module's own: the process's one, which
    ``main`` takes, keeps its first rows only, and a test process has
    built thousands of programs by the time it comes here."""
    from lua_mapreduce_tpu.utils.profiling import BuildLog
    log = BuildLog().register()
    yield log
    jax.monitoring.unregister_event_time_span_listener(log._span)
    jax.monitoring.unregister_event_listener(log._event)


def built_programs(line, program):
    """The stage's line carries the build log's rows for what it built:
    as many as it counts, the named program with its times among them."""
    assert len(line["builds"]) == line["programs"] > 0
    (row,) = [b for b in line["builds"] if b["program"] == program]
    assert row["trace_s"] > 0 and row["lower_s"] > 0 and row["build_s"] > 0
    assert row["cache"] in ("hit", "miss", "off")
    assert set(row) == {"program", "trace_s", "lower_s", "build_s",
                        "cache", "after_s"}


def test_refuses_to_run_without_a_tpu():
    """Under JAX_PLATFORMS=cpu: non-zero at once, no stage, no result."""
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        env=dict(os.environ, JAX_PLATFORMS="cpu"), cwd=REPO,
        capture_output=True, text=True, timeout=120)
    assert out.returncode != 0
    assert out.stdout == ""
    assert "refusing to run" in out.stderr


def test_has_no_way_around_the_device_check():
    src = open(os.path.join(REPO, "chip_smoke.py")).read()
    for word in ("argparse", "sys.argv", "os.environ", "pallas_interpret",
                 "subprocess", "except"):
        assert word not in src, word


@pytest.mark.parametrize("moe", [0, 2])
@pytest.mark.parametrize("mesh_shape", [(1, 1), (2, 2)])
def test_stage_trainer(log, mesh_shape, moe):
    line = chip_smoke.stage_trainer(
        log, jax.devices(), mesh_shape, lm=LM, moe_experts=moe,
        expect_backend="xla", **TRAIN)
    assert line["losses"][-1] < line["losses"][0]
    assert line["programs_after_first_step"] == 0
    assert line["devices"] == mesh_shape[0] * mesh_shape[1]
    # the state split by rows where the step splits it: dense, on four
    state = line["opt_state"]
    if mesh_shape == (2, 2) and not moe:
        assert state["leaves_whole"] == 0
        assert state["bytes_a_device"] < state["bytes"] / 3
    elif not moe:
        assert state["leaves_split"] == 0
        assert state["bytes_a_device"] == state["bytes"]
    built_programs(line, "lm_train_step")
    json.dumps(line)


def test_stage_trainer_fails_when_attention_takes_another_route(log):
    with pytest.raises(AssertionError, match="routes to xla"):
        chip_smoke.stage_trainer(log, jax.devices(), (1, 1), lm=LM,
                                 expect_backend="pallas", **TRAIN)


@pytest.mark.parametrize("variant", ["mha", "q8", "gqa"])
def test_stage_decoder(log, variant):
    line = chip_smoke.stage_decoder(log, lm=LM, variant=variant, **DECODE)
    assert line["programs_after_first_request"] == 0
    assert len(line["smoke_later_calls_s"]) == DECODE["requests"] - 1
    built_programs(line, "greedy_decode")


def test_stage_kernels_walks_the_policy_table(log, monkeypatch):
    from lua_mapreduce_tpu import ops

    routed = {op for op, to in ops._TPU_AUTO_POLICY.items()
              if to == "pallas"}
    line = chip_smoke.stage_kernels(
        log, lm=LM, train=dict(TRAIN, seq=128), decode=DECODE,
        pool_shape=(2, 8, 8, 128), kernel_backend="pallas_interpret")
    assert {k.split(" [")[0] for k in line["compared"]} == routed
    assert all(c["rel_err"] <= chip_smoke.BF16_TOLERANCE
               for c in line["compared"].values())
    # an op routed to Pallas with no case in the smoke fails the stage
    monkeypatch.setitem(ops._TPU_AUTO_POLICY, "softmax", "pallas")
    with pytest.raises(KeyError, match="softmax"):
        chip_smoke.stage_kernels(
            log, lm=LM, train=dict(TRAIN, seq=128), decode=DECODE,
            pool_shape=(2, 8, 8, 128), kernel_backend="pallas_interpret")


@pytest.mark.parametrize("mod,mode,folds", [
    ("examples.digits.mr_sgd", "shard_map", {"psum": 5}),
    ("examples.kmeans.mr_kmeans", "jit", {"fused": 9}),
])
def test_stage_engine(log, capsys, mod, mode, folds):
    line = chip_smoke.stage_engine(log, mod, iterations=2,
                                   dp=len(jax.devices()))
    assert (line["mode"], line["traces"], line["folds"]) == (mode, 1, folds)
    assert line["ingraph_iterations"] == 2
    # k-means reaches the jit tier because the collective tier refuses
    # its mapfn; the reason must be on record, not dropped
    assert (line["collective_tier_refused"] is None) == (mode == "shard_map")
    short = mod.rsplit(".", 1)[-1]
    assert f"{short}: {mode}, folds all" in capsys.readouterr().out


def test_main_ends_with_the_result_line_and_nothing_else_in_it(
        log, capsys, monkeypatch):
    """What reads the run accepts a last line with exactly ``ok`` and
    ``device``, the device exactly ``platform``, ``kind`` (text) and
    ``count`` (a whole number); the summary, with ``"claim": null``, is
    the line before. Stages are stubbed: this pins main's own output."""
    def stub(name):
        def stage(log, *a, **kw):
            return {"stage": kw.get("variant") or name}
        return stage

    for name in ("stage_trainer", "stage_decoder", "stage_kernels",
                 "stage_engine"):
        monkeypatch.setattr(chip_smoke, name, stub(name))
    monkeypatch.setattr("lua_mapreduce_tpu.utils.profiling.build_log",
                        lambda: log)
    monkeypatch.setattr(
        chip_smoke, "check_device",
        lambda: (chip_smoke.describe_device(), {"jax": jax.__version__}))
    monkeypatch.setattr(
        "lua_mapreduce_tpu.utils.jax_env.place_compile_cache",
        lambda: "/nowhere")

    assert chip_smoke.main() == 0
    lines = capsys.readouterr().out.splitlines()
    result = json.loads(lines[-1])
    assert set(result) == {"ok", "device"} and result["ok"] is True
    assert set(result["device"]) == {"platform", "kind", "count"}
    assert result["device"] == {
        "platform": jax.devices()[0].platform,
        "kind": jax.devices()[0].device_kind, "count": len(jax.devices())}
    assert type(result["device"]["count"]) is int
    summary = json.loads(lines[-2])
    assert summary["claim"] is None and "ok" not in summary
    assert summary["programs_built"] == log.programs
    assert summary["process_start_to_first_build_s"] > 0
    assert summary["four_chip_stages_ran"] == (len(jax.devices()) >= 4)
    assert len(summary["stages"]) == (2 + 3 + 1 + 2
                                      + 2 * summary["four_chip_stages_ran"])
