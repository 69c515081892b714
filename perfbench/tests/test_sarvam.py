"""The sarvam-105b files of the benchmark on the CPU: the counts from
shapes, the new readers on a made-up context, the manifest's entries,
and the `session` driver end to end at a tiny test-only size, sound and
with each kind of breakage, where `correct` has to come out false."""

import json
import os
import shutil

import pytest

from conftest import HERE, ROOT
from perfbench import counts_sarvam, harness
from perfbench.readers import (sarvam_mfu, sarvam_roofline,
                               session_step_ms_less)
from test_session import AlteredTokens, Float32, OtherExperts, drive

CELL = "sarvam105b-session-decode-32k-1chip"
LIMITS = {"served_logit_gap": 0.01, "served_logit_gap_mean": 0.001,
          "routing_miss": 0.01}


@pytest.fixture(scope="module")
def published():
    with open(os.path.join(ROOT, "perfbench", "configs",
                           "sarvam-105b.serve-ep4.json")) as f:
        return json.load(f)


def test_the_configuration_holds_the_catalogs_numbers(published):
    """Every published width unchanged; the three keys of `reduced` are
    the only ones that differ from the source."""
    source = {
        "first_k_dense_replace": 1, "head_dim": 576, "hidden_size": 4096,
        "intermediate_size": 16384, "kv_lora_rank": 512,
        "max_position_embeddings": 131072, "moe_intermediate_size": 2048,
        "num_attention_heads": 64, "num_experts": 128,
        "num_experts_per_tok": 8, "num_hidden_layers": 32,
        "num_shared_experts": 1, "q_head_dim": 192, "qk_nope_head_dim": 128,
        "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06, "rope_theta": 10000,
        "routed_scaling_factor": 2.5, "v_head_dim": 128,
        "vocab_size": 262144, "use_qk_norm": True,
        "moe_router_enable_expert_bias": True, "tie_word_embeddings": False}
    changed = sorted(k for k, v in source.items() if published[k] != v)
    assert changed == sorted(published["reduced"]) == [
        "num_experts", "num_hidden_layers", "vocab_size"]
    manifest = harness.load_json(ROOT, "BENCHMARK.json")
    entry = [c for c in manifest["configs"]
             if c["name"] == "sarvam-105b.serve-ep4"][0]
    assert sorted(entry["reduced"]) == changed
    assert entry["source"] == published["source"]
    assert published["router_experts"] == 128


def test_the_counts_are_the_issues_arithmetic(published):
    from perfbench import weights_sarvam
    assert weights_sarvam.n_params(published) == 4_535_402_432
    assert counts_sarvam.held_assignments_expected(published) == 2.0
    assert abs(counts_sarvam.experts_touched_expected(published, 16)
               - 20.6) < 0.01
    # a step of 16 sessions at 32k: 3.02 GB of latents, 3.65e11 FLOPs over
    # them, 4.15 GB of expert weights
    step = lambda f, *a: f(published, 16, 32768, 64, *a) / 64  # noqa: E731
    assert abs(step(counts_sarvam.latent_attn_bytes) / 1e9 - 3.02) < 0.01
    assert abs(step(counts_sarvam.latent_attn_flops) / 1e11 - 3.65) < 0.01
    assert abs(counts_sarvam.moe_expert_bytes(published, 16, 64) / 64 / 1e9
               - 4.15) < 0.01
    # attention is part of the turn's model FLOPs, position by position
    assert (counts_sarvam.turn_flops(published, 16, 32768, 64)
            > counts_sarvam.latent_attn_flops(published, 16, 32768, 64))


class Device:
    device_kind = "TPU v5 lite"


def context_of(sub_scopes, trace=None, calls=2):
    return {"cell": harness.Cell(CELL), "calls": calls, "chips": 1,
            "device": Device(), "sub_scopes": sub_scopes, "trace": trace,
            "loop": {"window_s": 2.0}}


def test_the_readers_read_scopes_and_return_nothing_without_them(capsys):
    """On a program without the scopes (the parent) every new reader
    returns None and the line leaves the metric out."""
    for sub in ({}, None):
        ctx = context_of(sub)
        assert session_step_ms_less.read(ctx, ["lm.attn"], ["lm.mla"]) is None
        assert sarvam_roofline.read(ctx, "latent_attn", ["lm.attn"],
                                    ["lm.mla"], ["^%_mla_decode_pallas"]) is None
        assert sarvam_roofline.read(ctx, "moe_experts",
                                    ["lm.moe.experts"]) is None
    # 2 calls x 64 steps; 5.0 ms of attention a step, 1.2 of projections
    sub = {"lm.attn": 128 * 6.2e-3, "lm.mla": 128 * 1.2e-3,
           "lm.moe.experts": 128 * 6.0e-3}
    trace = {"ops_s": {"%_mla_decode_pallas.3 custom-call -> f32": 0.6}}
    ctx = context_of(sub, trace)
    assert abs(session_step_ms_less.read(ctx, ["lm.attn"], ["lm.mla"])
               - 5.0) < 1e-9
    share = sarvam_roofline.read(ctx, "latent_attn", ["lm.attn"], ["lm.mla"],
                                 ["^%_mla_decode_pallas"])
    # 3.02 GB a step at 819 GB/s = 3.69 ms of the 5.0
    assert abs(share - 100 * 3.691 / 5.0) < 0.1
    err = capsys.readouterr().err
    assert "bound by bytes" in err and "events matching" in err
    assert "0.600000 s" in err
    experts = sarvam_roofline.read(ctx, "moe_experts", ["lm.moe.experts"])
    assert abs(experts - 100 * 4.148 / 0.819 / 6.0) < 0.1
    mfu = sarvam_mfu.read(ctx)
    assert abs(mfu - 100 * 2 * counts_sarvam.turn_flops(
        ctx["cell"].config, 16, 32768, 64) / (2.0 * 197e12)) < 1e-9
    with pytest.raises(SystemExit):
        sarvam_roofline.read(ctx, "something", ["lm.attn"])


def test_the_cell_finds_its_files_and_names_its_metrics():
    cell = harness.Cell(CELL)
    assert cell.chips == 1 and cell.traffic["kind"] == "session"
    assert {k: cell.traffic[k] for k in (
        "batch", "context_len", "n_new", "prefill_chunk", "checked_requests",
        "checked_rows", "traced_calls")} == {
            "batch": 16, "context_len": 32768, "n_new": 64,
            "prefill_chunk": 512, "checked_requests": 2, "checked_rows": 1,
            "traced_calls": 2}
    assert sorted(cell.limits) == ["routing_miss", "served_logit_gap",
                                   "served_logit_gap_mean"]
    names = [m["name"] for m in cell.metrics("per_layer")]
    assert names == ["device_idle_pct.serve", "programs_built_in_window.serve",
                     "decode_step_ms.session", "request_mfu_pct.sarvam",
                     "latent_attn_ms.sarvam", "latent_attn_roofline_pct.sarvam",
                     "moe_ms.sarvam", "moe_experts_roofline_pct.sarvam"]
    for name in names:
        spec = cell.data("layer_metrics", name)
        assert os.path.exists(os.path.join(
            ROOT, "perfbench", "readers", spec["reader"] + ".py"))
    assert [m["name"] for m in cell.metrics("end_to_end")] == [
        "serve_tokens_per_s", "request_p95_ms", "setup_s"]


@pytest.fixture
def sarvam_checkout(checkout):
    """conftest's checkout with a tiny sarvam session cell added, as new
    files and new entries."""
    bench = os.path.join(checkout, "perfbench")
    data = os.path.join(HERE, "data")
    shutil.copy(os.path.join(data, "tiny-sarvam.json"),
                os.path.join(bench, "configs"))
    shutil.copy(os.path.join(data, "session-tiny-dsv32.json"),
                os.path.join(bench, "traffic", "session-tiny-sarvam.json"))
    with open(os.path.join(bench, "limits", "tiny-session-sarvam.json"),
              "w") as f:
        json.dump(LIMITS, f)
    path = os.path.join(checkout, "BENCHMARK.json")
    with open(path) as f:
        manifest = json.load(f)
    manifest["configs"].append(
        {"name": "tiny-sarvam", "source": "test only", "reduced": [],
         "file": "perfbench/configs/tiny-sarvam.json", "why": "test only"})
    manifest["workloads"].append(
        {"name": "tiny-session-sarvam", "config": "tiny-sarvam",
         "traffic": "session-tiny-sarvam", "chips": 1, "why": "test only"})
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        if CELL in m.get("workloads", ()):
            m["workloads"].append("tiny-session-sarvam")
    with open(path, "w") as f:
        json.dump(manifest, f)
    return checkout


def test_a_sarvam_session_run_ends_in_the_contracts_line(sarvam_checkout,
                                                         capsys):
    line, err = drive(sarvam_checkout, capsys, "tiny-session-sarvam",
                      make_session=Float32)
    assert line["correct"] is True, err
    assert line["attempted"] > 0 and line["failed"] == 0
    assert sorted(line["checks"]) == ["routing_miss", "served_logit_gap",
                                      "served_logit_gap_mean"]
    assert "programs built in the window 0" in err
    assert "counters held_assignments_per_token" in err
    assert "experts_touched_expected" in err
    assert "keys_selected" not in err           # nothing is selected
    assert "left to its own choices the reference reads" in err
    assert "attention's output against the stream, by layer" in err


@pytest.mark.parametrize("make,check", [
    (AlteredTokens, "served_logit_gap"), (OtherExperts, "routing_miss")])
def test_a_broken_sarvam_session_is_not_correct(sarvam_checkout, capsys,
                                                make, check):
    line, err = drive(sarvam_checkout, capsys, "tiny-session-sarvam",
                      make_session=make)
    assert line["correct"] is False
    value, limit = line["checks"][check]
    assert value > limit, err


def test_the_controls_read_higher_than_the_program(sarvam_checkout, capsys):
    """Each planted fault and the int8 control read over a limit of the
    tiny cell, the program under every one."""
    import jax
    from perfbench import controls_sarvam
    controls_sarvam.run(harness.Cell("tiny-session-sarvam", sarvam_checkout),
                        [11], {11}, ["int8"], jax.devices()[:1],
                        make_session=Float32)
    rows = [json.loads(line) for line
            in capsys.readouterr().out.strip().splitlines()]
    by = {r["what"]: r["readings"] for r in rows}
    assert all(by["program"][k] <= LIMITS[k] for k in LIMITS), by["program"]
    for what in ("control int8", "fault skip_newest", "fault no_k_rope",
                 "fault no_q_gain", "fault no_shared", "fault no_bias",
                 "fault no_scale"):
        assert any(by[what][k] > LIMITS[k] for k in LIMITS), (what, by[what])
