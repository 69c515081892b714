"""The Kimi-Linear-48B-A3B-Instruct files of the benchmark on the CPU:
the configuration against the published one, the counts from shapes, the new
readers on a made-up context, the manifest's entries, and the `session`
driver end to end at a tiny test-only size, sound and with each kind of
breakage, where `correct` has to come out false."""

import json
import os
import shutil

import pytest

from conftest import HERE, ROOT
from perfbench import (counts_kimilinear, harness, weights,
                       weights_kimilinear)
from perfbench.readers import kimilinear_kda, kimilinear_mfu, kimilinear_moe
from test_session import LOG, AlteredTokens, Float32, OtherExperts, drive

CELL = "kimilinear-session-decode-4k-1chip"
with open(os.path.join(HERE, "data", "tiny-kimilinear.json")) as f:
    TINY = json.load(f)
LIMITS = {"served_logit_gap": 0.01, "served_logit_gap_mean": 0.001,
          "routing_miss": 0.01, "cache_bytes_off": 0.0}
NEW_METRICS = ["request_mfu_pct.kimilinear", "kda_kernel_ms.kimilinear",
               "kda_kernel_roofline_pct.kimilinear", "moe_ms.kimilinear",
               "moe_experts_roofline_pct.kimilinear"]


@pytest.fixture(scope="module")
def published():
    with open(os.path.join(
            ROOT, "perfbench", "configs",
            "kimi-linear-48b-a3b-instruct.serve-ep16.json")) as f:
        return json.load(f)


def test_the_configuration_holds_the_published_numbers(published):
    """Every number of the published config at the top level unchanged
    but the two of `reduced`; the nested group whole."""
    source = {
        "first_k_dense_replace": 1, "head_dim": 72, "hidden_size": 2304,
        "intermediate_size": 9216, "kv_lora_rank": 512,
        "model_max_length": 1048576, "moe_intermediate_size": 1024,
        "moe_layer_freq": 1, "num_attention_heads": 32,
        "num_expert_group": 1, "num_experts": 256,
        "num_experts_per_token": 8, "num_hidden_layers": 27,
        "num_key_value_heads": 32, "num_nextn_predict_layers": 0,
        "num_shared_experts": 1, "qk_nope_head_dim": 128,
        "qk_rope_head_dim": 64, "rms_norm_eps": 1e-05, "rope_theta": 10000,
        "routed_scaling_factor": 2.446, "topk_group": 1, "v_head_dim": 128,
        "vocab_size": 163840, "mla_use_nope": True, "q_lora_rank": None,
        "tie_word_embeddings": False, "moe_renormalize": True,
        "moe_router_activation_func": "sigmoid"}
    changed = sorted(k for k, v in source.items() if published[k] != v)
    assert changed == sorted(published["reduced"]) == [
        "num_experts", "vocab_size"]
    assert published["linear_attn_config"] == {
        "full_attn_layers": [4, 8, 12, 16, 20, 24, 27], "head_dim": 128,
        "kda_layers": [1, 2, 3, 5, 6, 7, 9, 10, 11, 13, 14, 15, 17, 18, 19,
                       21, 22, 23, 25, 26],
        "num_heads": 32, "short_conv_kernel_size": 4}
    manifest = harness.load_json(ROOT, "BENCHMARK.json")
    entry = [c for c in manifest["configs"]
             if c["name"] == "kimi-linear-48b-a3b-instruct.serve-ep16"][0]
    assert sorted(entry["reduced"]) == changed
    assert entry["source"] == published["source"]
    assert published["router_experts"] == 256


def test_the_counts_are_the_cells_arithmetic(published):
    """4.296 B parameters (8.59 GB); 12.44 GB with the caches of 32
    sessions of 4,160 positions; a step reads 2.68 GB of KDA state (and
    0.05 of its rows), 10.2 of 16 held experts a layer, 10.16 GB in all,
    12.40 ms at 819 GB/s."""
    assert weights_kimilinear.n_params(published) == 4_296_057_728
    caches = counts_kimilinear.cache_bytes(published, 32, 4160)
    assert caches["state_bytes"] == 20 * 32 * (32 * 128 * 128 * 4
                                               + 3 * 12288 * 2)
    assert caches["snapshot_bytes"] == caches["state_bytes"] + 20 * 4
    assert caches["growing_bytes"] == 7 * 32 * 4160 * 576 * 2
    assert abs((2 * 4_296_057_728 + sum(caches.values())) / 1e9
               - 12.44) < 0.01
    state = 20 * 32 * 32 * 128 * 128 * 4 * 2
    assert state / 1e9 == pytest.approx(2.684, abs=1e-3)
    assert counts_kimilinear.kda_bytes(published, 32, 1) > state
    assert counts_kimilinear.kda_bytes(published, 32, 1) < state * 1.02
    assert counts_kimilinear.experts_touched_expected(published, 32) == \
        pytest.approx(10.21, abs=0.01)
    step = counts_kimilinear.step_bytes(published, 32, 4096 + 32)
    assert sum(step.values()) / 1e9 == pytest.approx(10.16, abs=0.01)
    assert step["held_experts"] / 1e9 == pytest.approx(3.76, abs=0.01)
    assert counts_kimilinear.kda_mixer_params(published) == \
        pytest.approx(39.51e6, rel=1e-3)
    assert counts_kimilinear.latent_mixer_params(published) == \
        pytest.approx(29.11e6, rel=1e-3)
    assert (counts_kimilinear.turn_flops(published, 32, 4096, 64)
            > counts_kimilinear.kda_flops(published, 32, 64))


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
        for report in ("kernel_ms", "roofline_pct"):
            assert kimilinear_kda.read(
                ctx, report, match=["^%_kda_step_pallas"]) is None
        assert kimilinear_moe.read(
            ctx, marks=["lm.moe.experts", "lm.moe.shared"]) is None
    # 2 calls x 64 steps: 4.0 ms of the kernel a step, 6.0 of experts
    sub = {"lm.moe.experts": 128 * 5.0e-3, "lm.moe.shared": 128 * 1.0e-3}
    trace = {"ops_s": {"%_kda_step_pallas.3 custom-call -> f32": 0.256,
                       "%_kda_step_pallas.7 custom-call -> f32": 0.256}}
    ctx = context_of(sub, trace)
    assert kimilinear_kda.read(ctx, "kernel_ms",
                               match=["^%_kda_step_pallas"]) == 4.0
    pct = kimilinear_kda.read(ctx, "roofline_pct",
                              match=["^%_kda_step_pallas"])
    kda = counts_kimilinear.kda_bytes(ctx["cell"].config, 32, 1)
    assert abs(pct - 100 * kda / 819e9 / 4.0e-3) < 0.05
    assert "bound by bytes" in capsys.readouterr().err
    # the experts touched are the program's count, left on the cell by
    # the comparison; without it nothing is read
    marks = ["lm.moe.experts", "lm.moe.shared"]
    assert kimilinear_moe.read(ctx, marks=marks) is None
    ctx["cell"].counted = {"experts_touched_mean": 4.5}
    moe = kimilinear_moe.read(ctx, marks=marks)
    per_step = counts_kimilinear.moe_bytes(ctx["cell"].config, 4.5, 1)
    assert per_step == 26 * 5.5 * 3 * 2304 * 1024 * 2
    assert abs(moe - 100 * per_step / 819e9 / 6.0e-3) < 0.05
    mfu = kimilinear_mfu.read(ctx)
    assert abs(mfu - 100 * 2 * counts_kimilinear.turn_flops(
        ctx["cell"].config, 32, 4096, 64) / (2.0 * 197e12)) < 1e-9
    with pytest.raises(SystemExit):
        kimilinear_kda.read(ctx, "something", match=["^%_kda_step_pallas"])


def test_the_cell_finds_its_files_and_names_its_metrics():
    cell = harness.Cell(CELL)
    assert cell.chips == 1 and cell.traffic["kind"] == "session"
    assert cell.traffic["batch"] == 32 and cell.traffic["n_new"] == 64
    assert cell.traffic["context_len"] == 4096
    assert cell.traffic["prefill_chunk"] % 64 == 0
    assert cell.config["session_model"] == "model_kimilinear"
    assert sorted(cell.limits) == ["cache_bytes_off", "routing_miss",
                                   "served_logit_gap",
                                   "served_logit_gap_mean"]
    assert cell.limits["cache_bytes_off"] == 0
    names = [m["name"] for m in cell.metrics("per_layer")]
    assert names == [
        "device_idle_pct.serve", "programs_built_in_window.serve",
        "decode_step_ms.session", "start_s.setup", "trace_lower_s.setup",
        "build_s.setup", "prefill_run_s.setup"] + NEW_METRICS
    for name in names:
        spec = cell.data("layer_metrics", name)
        assert os.path.exists(os.path.join(
            ROOT, "perfbench", "readers", spec["reader"] + ".py"))
    assert [m["name"] for m in cell.metrics("end_to_end")] == [
        "serve_tokens_per_s", "request_p95_ms", "setup_s"]
    manifest = harness.load_json(ROOT, "BENCHMARK.json")
    new = [m for m in manifest["per_layer"] if m["name"] in NEW_METRICS]
    assert all(m["workloads"] == [CELL] for m in new)
    assert all(m["moves"] == "serve_tokens_per_s" for m in new)


@pytest.mark.parametrize("missing", ["LinearAttention", "nope"])
def test_a_program_without_the_kind_stops_before_the_weights(published,
                                                             missing):
    """The parent's program has neither the KDA kind's sizes nor latent
    attention without positions: the family's module names what it
    lacks, before a byte is drawn."""
    from perfbench import model_kimilinear
    import lua_mapreduce_tpu.models.transformer as tfm
    real = {n: getattr(tfm, n) for n in ("LinearAttention", "_check_arch")}
    try:
        if missing == "LinearAttention":
            del tfm.LinearAttention
        else:
            def check(pc):
                if not pc.rope:
                    raise ValueError("latent attention is written for "
                                     "rope=True and norm='rms'")
            tfm._check_arch = check
        with pytest.raises(SystemExit, match=(
                missing if missing == "LinearAttention" else "rope=True")):
            model_kimilinear.make_params(published, 1)
    finally:
        for n, v in real.items():
            setattr(tfm, n, v)


@pytest.fixture
def kimilinear_checkout(checkout):
    """conftest's checkout with a tiny Kimi Linear session cell added, as
    new files and new entries: 24 positions of context prefilled in
    chunks of 8, turns of 8."""
    bench = os.path.join(checkout, "perfbench")
    data = os.path.join(HERE, "data")
    shutil.copy(os.path.join(data, "tiny-kimilinear.json"),
                os.path.join(bench, "configs"))
    shutil.copy(os.path.join(data, "session-tiny-dsv32.json"),
                os.path.join(bench, "traffic", "session-tiny-kimilinear.json"))
    with open(os.path.join(bench, "limits", "tiny-session-kimilinear.json"),
              "w") as f:
        json.dump(LIMITS, f)
    path = os.path.join(checkout, "BENCHMARK.json")
    with open(path) as f:
        manifest = json.load(f)
    manifest["configs"].append(
        {"name": "tiny-kimilinear", "source": "test only", "reduced": [],
         "file": "perfbench/configs/tiny-kimilinear.json",
         "why": "test only"})
    manifest["workloads"].append(
        {"name": "tiny-session-kimilinear", "config": "tiny-kimilinear",
         "traffic": "session-tiny-kimilinear", "chips": 1,
         "why": "test only"})
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        if CELL in m.get("workloads", ()):
            m["workloads"].append("tiny-session-kimilinear")
    with open(path, "w") as f:
        json.dump(manifest, f)
    return checkout


def test_a_kimilinear_session_run_ends_in_the_contracts_line(
        kimilinear_checkout, capsys):
    line, err = drive(kimilinear_checkout, capsys, "tiny-session-kimilinear",
                      make_session=Float32)
    assert line["correct"] is True, err
    assert line["attempted"] > 0 and line["failed"] == 0
    assert sorted(line["checks"]) == ["cache_bytes_off", "routing_miss",
                                      "served_logit_gap",
                                      "served_logit_gap_mean"]
    assert line["checks"]["cache_bytes_off"] == [0.0, 0.0]
    assert "programs built in the window 0" in err
    assert "counters held_assignments_per_token" in err
    # the caches by kind, from the pytree the scan carries
    fields = dict(zip(*[iter(err.split("counters ")[1].split("\n")[0]
                             .split())] * 2))
    for kind in ("state_bytes", "snapshot_bytes", "growing_bytes"):
        assert fields[kind] == fields[kind + "_configured"] != "0"
    assert "left to its own choices the reference reads" in err
    assert "each mixer's output against the stream, by layer" in err


def test_a_session_that_carries_its_state_in_bfloat16_is_not_correct(
        kimilinear_checkout, capsys, monkeypatch):
    """The configuration states a float32 KDA state. A program that
    carries it in bfloat16 halves the states' share of their kind's bytes
    (the convolutions' tails are bfloat16 already), and `cache_bytes_off`
    reads that share: the tokens alone would not tell (PERF.md section
    4)."""
    import jax
    import jax.numpy as jnp
    from perfbench import model_kimilinear
    carried = model_kimilinear.carried_caches

    def halved(cfg, t):
        return {name: jax.ShapeDtypeStruct(leaf.shape, jnp.bfloat16)
                if leaf.dtype == jnp.float32 else leaf
                for name, leaf in carried(cfg, t).items()}

    monkeypatch.setattr(model_kimilinear, "carried_caches", halved)
    line, err = drive(kimilinear_checkout, capsys, "tiny-session-kimilinear",
                      make_session=Float32)
    assert line["correct"] is False
    value, limit = line["checks"]["cache_bytes_off"]
    h, dk, taps = weights_kimilinear.kda_dims(TINY)
    states, tails = 4 * h * dk * dk, 2 * (taps - 1) * 3 * h * dk
    assert value == pytest.approx(states / 2 / (states + tails)), err
    assert limit == 0.0


def test_the_driver_leaves_the_counted_experts_for_the_readers(
        kimilinear_checkout):
    """`moe_experts_roofline_pct.kimilinear` reads the experts touched
    from the cell (`counted`), which `model_kimilinear.judge` leaves
    there from the checked turns' counters. The driver judges before it
    returns the context that `run.run_cell` hands the readers, so the
    context already carries the count; without it the metric would fall
    silent."""
    import jax
    from perfbench.drivers import session
    cell = harness.Cell("tiny-session-kimilinear", kimilinear_checkout)
    assert not hasattr(cell, "counted")
    _, context, _, _ = session.run(
        cell, 2 ** 31 + 77, 0.3, False, jax.devices()[:1],
        lambda: 0.0, LOG, make_session=Float32)
    touched = context["cell"].counted["experts_touched_mean"]
    assert 0 < touched <= cell.config["num_experts"]
    # the readers' view of a traced window, on a described chip
    context.update(device=Device(),
                   sub_scopes={"lm.moe.experts": 1e-3, "lm.moe.shared": 1e-3})
    assert kimilinear_moe.read(
        context, marks=["lm.moe.experts", "lm.moe.shared"]) > 0


@pytest.mark.parametrize("make,check", [
    (AlteredTokens, "served_logit_gap"), (OtherExperts, "routing_miss")])
def test_a_broken_kimilinear_session_is_not_correct(kimilinear_checkout,
                                                    capsys, make, check):
    line, err = drive(kimilinear_checkout, capsys, "tiny-session-kimilinear",
                      make_session=make)
    assert line["correct"] is False
    value, limit = line["checks"][check]
    assert value > limit, err


def test_the_controls_read_higher_than_the_program(kimilinear_checkout,
                                                   capsys):
    """Each planted fault and the int8 control read over a limit of the
    tiny cell, the program under every one. The KDA state in bfloat16
    reads higher than the program, and is held to no limit of the
    tokens: as on the chip, `cache_bytes_off` is what refuses a program
    that carries such a state (the test above)."""
    import jax
    from perfbench import controls_kimilinear, reference_kimilinear
    controls_kimilinear.run(
        harness.Cell("tiny-session-kimilinear", kimilinear_checkout), [11],
        {11}, ["int8", "state_bf16"], jax.devices()[:1],
        make_session=Float32)
    rows = [json.loads(line) for line
            in capsys.readouterr().out.strip().splitlines()]
    by = {r["what"]: r["readings"] for r in rows}
    assert all(v <= LIMITS[k] for k, v in by["program"].items()), by[
        "program"]
    for what in ["control int8"] + [
            f"fault {f}" for f in reference_kimilinear.FAULTS]:
        assert any(v > LIMITS[k] for k, v in by[what].items()), (what,
                                                                 by[what])
    assert any(v > by["program"][k]
               for k, v in by["control state_bf16"].items()), by


def test_the_kda_out_projection_is_blind_to_the_values_means():
    """`weights_kimilinear.finish` takes from each KDA head's rows of
    `out_W` their part along the head's `value_means`, the same in the
    program's bfloat16 copy and the reference's float32 one; the latent
    layers' `out_W` is a plain draw."""
    import gc
    import jax.numpy as jnp
    import numpy as np
    from perfbench import model_kimilinear, reference_kimilinear
    h, dk, _ = weights_kimilinear.kda_dims(TINY)
    served = model_kimilinear.make_params(TINY, 2 ** 31 + 5)
    gc.enable()  # off from `make_params` to `judge`
    ref = reference_kimilinear.Weights(TINY, 2 ** 31 + 5)
    for i in range(TINY["num_hidden_layers"]):
        w = ref.layer(i, False)
        assert np.array_equal(np.asarray(served[f"L{i}_out_W"], np.float32),
                              np.asarray(w["out_W"]))
        if weights_kimilinear.kind(TINY, i) != "kda":
            continue
        mu = np.asarray(weights_kimilinear.value_means(
            w["qkv_W"], w["conv_W"], h * dk)).reshape(h, dk)
        u = mu / np.linalg.norm(mu, axis=1, keepdims=True)
        rows = np.asarray(w["out_W"]).reshape(h, dk, -1)
        along = np.einsum("hj,hjd->hd", u, rows)
        # what is left is the rounding to bfloat16
        assert np.abs(along).max() < 0.02 * np.abs(rows).max()
        drawn = np.asarray(jnp.einsum("hj,hjd->hd", u, np.asarray(
            weights.make_leaves(weights.seed_key(2 ** 31 + 5),
                                weights_kimilinear.indexed(
                                    TINY, {f"L{i}_out_W"}),
                                jnp.float32, via=jnp.bfloat16)[
                f"L{i}_out_W"]).reshape(h, dk, -1)))
        assert np.abs(drawn).max() > 10 * np.abs(along).max()


def test_the_collector_is_off_from_the_weights_to_the_judgement():
    """`model_kimilinear.make_params` holds the interpreter's cyclic
    collector off, so that no full collection falls inside the timed
    window; `judge` turns it on again."""
    import gc
    from perfbench import model_kimilinear
    assert gc.isenabled()
    try:
        model_kimilinear.make_params(TINY, 3)
        assert not gc.isenabled()
    finally:
        gc.enable()
