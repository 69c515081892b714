"""The command-a-plus-05-2026 files of the benchmark on the CPU: the
configuration against the catalog, the counts from shapes, the new
readers on a made-up context, the manifest's entries, and the `session`
driver end to end at a tiny test-only size, sound and with each kind of
breakage, where `correct` has to come out false."""

import json
import os
import shutil

import pytest

from conftest import HERE, ROOT
from perfbench import counts_cmdaplus, harness, weights_cmdaplus
from perfbench.readers import cmdaplus_mfu, cmdaplus_roofline
from test_session import AlteredTokens, Float32, OtherExperts, drive

CELL = "cmdaplus-session-decode-32k-1chip"
LIMITS = {"served_logit_gap": 0.01, "served_logit_gap_mean": 0.001,
          "routing_miss": 0.01}


@pytest.fixture(scope="module")
def published():
    with open(os.path.join(ROOT, "perfbench", "configs",
                           "command-a-plus-05-2026.serve-ep8.json")) as f:
        return json.load(f)


def test_the_configuration_holds_the_catalogs_numbers(published):
    """Every published width unchanged; the three keys of `reduced` are
    the only ones that differ from the source, and the layer types are
    the published list whole."""
    source = {
        "hidden_size": 4096, "intermediate_size": 4096, "head_dim": 128,
        "num_attention_heads": 128, "num_key_value_heads": 8,
        "num_experts": 128, "num_experts_per_tok": 8, "num_shared_experts": 4,
        "num_hidden_layers": 32, "vocab_size": 262144, "sliding_window": 4096,
        "layer_switch": 4, "layer_norm_eps": 1e-05, "rope_theta": 50000,
        "max_position_embeddings": 200000, "first_k_dense_replace": 0,
        "prefix_dense_intermediate_size": 16384, "logit_scale": 1,
        "rotary_pct": 1, "tie_word_embeddings": True,
        "use_parallel_block": True, "use_qk_norm": False,
        "attention_bias": False, "norm_topk_prob": True,
        "expert_selection_fn": "sigmoid",
        "shared_expert_combination_strategy": "average",
        "position_embedding_type": "rope_gptj"}
    changed = sorted(k for k, v in source.items() if published[k] != v)
    assert changed == sorted(published["reduced"]) == [
        "num_experts", "num_hidden_layers", "vocab_size"]
    assert published["layer_types"] == (
        ["sliding_attention"] * 3 + ["full_attention"]) * 8
    manifest = harness.load_json(ROOT, "BENCHMARK.json")
    entry = [c for c in manifest["configs"]
             if c["name"] == "command-a-plus-05-2026.serve-ep8"][0]
    assert sorted(entry["reduced"]) == changed
    assert entry["source"] == published["source"]
    assert published["router_experts"] == 128


def test_the_counts_are_the_issues_arithmetic(published):
    """4.73 B parameters (9.47 GB); a step of 16 sessions at 32k reads
    2.96 GB of keys and values (2.15 of the full layer's, 0.81 of the
    windows') and 5.76 GB of experts (10.3 of 16 touched and 4 shared,
    4 layers)."""
    assert weights_cmdaplus.n_params(published) == 4_733_292_544
    assert counts_cmdaplus.held_assignments_expected(published) == 1.0
    assert abs(counts_cmdaplus.experts_touched_expected(published, 16)
               - 10.30) < 0.01
    step = lambda f: f(published, 16, 32768, 64) / 64  # noqa: E731
    full = 16 * 8 * 2 * 128 * 2 * sum(p + 1 for p in range(32768, 32832)) / 64
    assert abs(step(counts_cmdaplus.attn_bytes) - full - 3 * 16 * 8 * 4096
               * 2 * 128 * 2) < 1
    assert abs(step(counts_cmdaplus.attn_bytes) / 1e9 - 2.955) < 0.001
    assert abs(counts_cmdaplus.moe_bytes(published, 16, 64) / 64 / 1e9
               - 5.759) < 0.001
    assert (counts_cmdaplus.turn_flops(published, 16, 32768, 64)
            > counts_cmdaplus.attn_flops(published, 16, 32768, 64))


class Device:
    device_kind = "TPU v5 lite"


def context_of(sub_scopes, trace=None, calls=2):
    return {"cell": harness.Cell(CELL), "calls": calls, "chips": 1,
            "device": Device(), "sub_scopes": sub_scopes, "trace": trace,
            "loop": {"window_s": 2.0}}


def test_the_readers_read_what_they_find_and_nothing_without_it(capsys):
    """On a program without the scopes or the kernel every new reader
    returns None and the line leaves the metric out."""
    for sub, trace in (({}, {}), (None, None)):
        ctx = context_of(sub, trace)
        assert cmdaplus_roofline.read(ctx, "kv",
                                      match=["^%_decode_pallas"]) is None
        assert cmdaplus_roofline.read(
            ctx, "moe", marks=["lm.moe.experts", "lm.moe.shared"]) is None
    # 2 calls x 64 steps: 4.0 ms of the kernel a step, 8.0 of experts
    sub = {"lm.moe.experts": 128 * 6.0e-3, "lm.moe.shared": 128 * 2.0e-3}
    trace = {"ops_s": {"%_decode_pallas.3 custom-call -> f32": 0.256,
                       "%_decode_pallas.7 custom-call -> f32": 0.256}}
    ctx = context_of(sub, trace)
    kv = cmdaplus_roofline.read(ctx, "kv", match=["^%_decode_pallas"])
    # 2.955 GB a step at 819 GB/s = 3.608 ms of the 4.0
    assert abs(kv - 100 * 2.955 / 0.819 / 4.0) < 0.05
    assert "bound by bytes" in capsys.readouterr().err
    moe = cmdaplus_roofline.read(ctx, "moe",
                                 marks=["lm.moe.experts", "lm.moe.shared"])
    assert abs(moe - 100 * 5.759 / 0.819 / 8.0) < 0.05
    mfu = cmdaplus_mfu.read(ctx)
    assert abs(mfu - 100 * 2 * counts_cmdaplus.turn_flops(
        ctx["cell"].config, 16, 32768, 64) / (2.0 * 197e12)) < 1e-9
    with pytest.raises(SystemExit):
        cmdaplus_roofline.read(ctx, "something", marks=["lm.moe.experts"])


def test_the_cell_finds_its_files_and_names_its_metrics():
    cell = harness.Cell(CELL)
    assert cell.chips == 1 and cell.traffic["kind"] == "session"
    assert cell.traffic["batch"] == 16 and cell.traffic["n_new"] == 64
    assert cell.traffic["context_len"] == 32768
    assert cell.config["session_model"] == "model_cmdaplus"
    assert sorted(cell.limits) == ["routing_miss", "served_logit_gap",
                                   "served_logit_gap_mean"]
    names = [m["name"] for m in cell.metrics("per_layer")]
    assert names == [
        "device_idle_pct.serve", "programs_built_in_window.serve",
        "decode_step_ms.session", "start_s.setup", "trace_lower_s.setup",
        "build_s.setup", "prefill_run_s.setup", "request_mfu_pct.cmdaplus",
        "decode_kernel_roofline_pct.cmdaplus", "moe_ms.cmdaplus",
        "moe_experts_roofline_pct.cmdaplus"]
    for name in names:
        spec = cell.data("layer_metrics", name)
        assert os.path.exists(os.path.join(
            ROOT, "perfbench", "readers", spec["reader"] + ".py"))
    assert [m["name"] for m in cell.metrics("end_to_end")] == [
        "serve_tokens_per_s", "request_p95_ms", "setup_s"]


def test_a_program_without_the_pattern_stops_before_the_weights(published):
    """The parent's program has no attention pattern: the family's module
    names what it lacks, before a byte is drawn."""
    from perfbench import model_cmdaplus
    import lua_mapreduce_tpu.models.transformer as tfm
    real = tfm.TransformerConfig

    def without(**kw):
        if "attn_pattern" in kw:
            raise TypeError("unexpected keyword argument 'attn_pattern'")
        return real(**kw)

    tfm.TransformerConfig = without
    try:
        with pytest.raises(SystemExit, match="attn_pattern"):
            model_cmdaplus.make_params(published, 1)
    finally:
        tfm.TransformerConfig = real


@pytest.fixture
def cmdaplus_checkout(checkout):
    """conftest's checkout with a tiny command-a-plus session cell added,
    as new files and new entries: 24 positions of context over a window
    of 8, so that the turns go on past the rolling buffer's wrap."""
    bench = os.path.join(checkout, "perfbench")
    data = os.path.join(HERE, "data")
    shutil.copy(os.path.join(data, "tiny-cmdaplus.json"),
                os.path.join(bench, "configs"))
    shutil.copy(os.path.join(data, "session-tiny-dsv32.json"),
                os.path.join(bench, "traffic", "session-tiny-cmdaplus.json"))
    with open(os.path.join(bench, "limits", "tiny-session-cmdaplus.json"),
              "w") as f:
        json.dump(LIMITS, f)
    path = os.path.join(checkout, "BENCHMARK.json")
    with open(path) as f:
        manifest = json.load(f)
    manifest["configs"].append(
        {"name": "tiny-cmdaplus", "source": "test only", "reduced": [],
         "file": "perfbench/configs/tiny-cmdaplus.json", "why": "test only"})
    manifest["workloads"].append(
        {"name": "tiny-session-cmdaplus", "config": "tiny-cmdaplus",
         "traffic": "session-tiny-cmdaplus", "chips": 1, "why": "test only"})
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        if CELL in m.get("workloads", ()):
            m["workloads"].append("tiny-session-cmdaplus")
    with open(path, "w") as f:
        json.dump(manifest, f)
    return checkout


def test_a_cmdaplus_session_run_ends_in_the_contracts_line(cmdaplus_checkout,
                                                           capsys):
    line, err = drive(cmdaplus_checkout, capsys, "tiny-session-cmdaplus",
                      make_session=Float32)
    assert line["correct"] is True, err
    assert line["attempted"] > 0 and line["failed"] == 0
    assert sorted(line["checks"]) == ["routing_miss", "served_logit_gap",
                                      "served_logit_gap_mean"]
    assert "programs built in the window 0" in err
    assert "counters held_assignments_per_token" in err
    assert "left to its own choices the reference reads" in err
    assert "attention's output against the stream, by layer" in err


@pytest.mark.parametrize("make,check", [
    (AlteredTokens, "served_logit_gap"), (OtherExperts, "routing_miss")])
def test_a_broken_cmdaplus_session_is_not_correct(cmdaplus_checkout, capsys,
                                                  make, check):
    line, err = drive(cmdaplus_checkout, capsys, "tiny-session-cmdaplus",
                      make_session=make)
    assert line["correct"] is False
    value, limit = line["checks"][check]
    assert value > limit, err


def test_the_controls_read_higher_than_the_program(cmdaplus_checkout,
                                                   capsys):
    """Each planted fault and the int8 control read over a limit of the
    tiny cell, the program under every one."""
    import jax
    from perfbench import controls_cmdaplus
    controls_cmdaplus.run(
        harness.Cell("tiny-session-cmdaplus", cmdaplus_checkout), [11], {11},
        ["int8"], jax.devices()[:1], make_session=Float32)
    rows = [json.loads(line) for line
            in capsys.readouterr().out.strip().splitlines()]
    by = {r["what"]: r["readings"] for r in rows}
    assert all(by["program"][k] <= LIMITS[k] for k in LIMITS), by["program"]
    for what in ("control int8", "fault rope_on_full", "fault sequential",
                 "fault shared_sum"):
        assert any(by[what][k] > LIMITS[k] for k in LIMITS), (what, by[what])
