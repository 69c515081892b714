"""`counts_dsv32` on cases small enough to count by hand, and the new
configuration file against the published keys."""

import json
import os

import pytest

from conftest import HERE, ROOT
from perfbench import counts, counts_dsv32

TOY = {
    "model_type": "deepseek_v32", "hidden_size": 4, "num_attention_heads": 2,
    "q_lora_rank": 3, "kv_lora_rank": 2, "qk_nope_head_dim": 1,
    "qk_rope_head_dim": 2, "v_head_dim": 1, "index_n_heads": 1,
    "index_head_dim": 2, "index_topk": 2, "num_hidden_layers": 2,
    "first_k_dense_replace": 1, "intermediate_size": 5,
    "moe_intermediate_size": 3, "router_experts": 4, "n_routed_experts": 2,
    "num_experts_per_tok": 2, "n_shared_experts": 1, "vocab_size": 7,
}


def test_expectations_under_uniform_routing():
    assert counts_dsv32.held_assignments_expected(TOY) == 1.0     # 2 * 2/4
    # a token picks a given expert with 2/4; two tokens miss it with 1/4
    assert counts_dsv32.experts_touched_expected(TOY, 2) == 1.5
    real = {"num_experts_per_tok": 8, "n_routed_experts": 16,
            "router_experts": 256}
    assert counts_dsv32.held_assignments_expected(real) == 0.5
    assert counts_dsv32.experts_touched_expected(real, 8) == pytest.approx(
        16 * (1 - (31 / 32) ** 8))


def test_a_tokens_flops_by_hand():
    # position 4: 5 keys for the indexer, min(5, 2) = 2 for attention
    proj = 2 * (4 * 3 + 3 * 2 * 3 + 4 * 4 + 2 * 2 * 2 + 2 * 1 * 4)    # 124
    attn = 2 * 2 * (1 + 2 + 1) * 2                                     # 32
    indexer = 2 * (3 * 1 * 2 + 4 * 2 + 4 * 1) + 2 * 1 * 2 * 5          # 56
    dense = 6 * 4 * 5                                                  # 120
    moe = 2 * 4 * 4 + 6 * 4 * 3 * (1 + 1.0)                            # 176
    head = 2 * 4 * 7                                                   # 56
    assert 2 * (proj + attn + indexer) + dense + moe + head == 776
    assert counts_dsv32.dsv32_token_flops(TOY, 4) == 776
    # a turn: batch x the scanned positions, nothing of the context
    assert counts_dsv32.turn_flops(TOY, 3, 4, 2) == 3 * (
        776 + counts_dsv32.dsv32_token_flops(TOY, 5))
    assert counts_dsv32.dsv32_token_flops(TOY, 5) - 776 == 2 * 1 * 2 * 2


def test_bytes_of_the_expert_and_sparse_layers_by_hand():
    # an expert: 3 matrices of 4 x 3 in 2 bytes; 1.5 touched; 1 layer; 3 steps
    assert counts_dsv32.moe_expert_bytes(TOY, 2, 3) == 72 * 1.5 * 1 * 3
    # position 1: 2 index keys of 2 + 2 rows of 4; position 2: 3 and 2
    assert counts_dsv32.sparse_attn_bytes(TOY, 2, 1, 2) == \
        2 * 2 * ((2 * 2 + 2 * 4) + (3 * 2 + 2 * 4)) * 2
    assert counts_dsv32.sparse_attn_flops(TOY, 1, 1, 1) == 2 * (
        2 * 1 * 2 * 2 + 2 * 2 * (4 + 2) * 2)


def test_the_dense_models_session_counts():
    with open(os.path.join(HERE, "data", "tiny.json")) as f:
        tiny = json.load(f)         # d 64, 4 heads of 16, 2 kv heads, 2 layers
    # positions 8 and 9 see 9 and 10 keys (window 16)
    assert counts_dsv32.gqa_kernel_bytes(tiny, 3, 8, 2) == \
        2 * 2 * 3 * 2 * 16 * 19 * 2
    assert counts_dsv32.gqa_kernel_flops(tiny, 3, 8, 2) == 4 * 3 * 64 * 19 * 2
    assert counts_dsv32.turn_flops(tiny, 3, 8, 2) == 3 * (
        counts.forward_flops_per_token(tiny, 9)
        + counts.forward_flops_per_token(tiny, 10))
    # past the window the count stops growing
    assert counts_dsv32.gqa_kernel_bytes(tiny, 1, 40, 1) == \
        counts_dsv32.gqa_kernel_bytes(tiny, 1, 15, 1)


# huggingface.co/deepseek-ai/DeepSeek-V3.2-Exp config.json, the keys that
# say something about the model's shape
PUBLISHED = {
    "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 3,
    "hidden_act": "silu", "hidden_size": 7168, "index_head_dim": 128,
    "index_n_heads": 64, "index_topk": 2048, "intermediate_size": 18432,
    "kv_lora_rank": 512, "max_position_embeddings": 163840,
    "model_type": "deepseek_v32", "moe_intermediate_size": 2048,
    "moe_layer_freq": 1, "n_group": 8, "n_routed_experts": 256,
    "n_shared_experts": 1, "norm_topk_prob": True,
    "num_attention_heads": 128, "num_experts_per_tok": 8,
    "num_hidden_layers": 61, "num_key_value_heads": 128,
    "num_nextn_predict_layers": 1, "q_lora_rank": 1536,
    "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 40,
                     "mscale": 1, "mscale_all_dim": 1,
                     "original_max_position_embeddings": 4096,
                     "type": "yarn"},
    "rope_theta": 10000, "routed_scaling_factor": 2.5,
    "scoring_func": "sigmoid", "tie_word_embeddings": False, "topk_group": 4,
    "topk_method": "noaux_tc", "v_head_dim": 128, "vocab_size": 129280,
}
REDUCED = {"num_hidden_layers": 5, "first_k_dense_replace": 1,
           "n_routed_experts": 16, "vocab_size": 16160,
           "num_nextn_predict_layers": 0}


def test_the_configuration_holds_the_published_keys():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entry = {c["name"]: c for c in json.load(f)["configs"]}[
            "deepseek-v3.2-exp.serve-ep16"]
    with open(os.path.join(ROOT, entry["file"])) as f:
        cfg = json.load(f)
    assert sorted(entry["reduced"]) == sorted(REDUCED) == sorted(
        cfg["reduced"])
    for key, value in PUBLISHED.items():
        assert cfg[key] == REDUCED.get(key, value), key
    # no width is cut: what is reduced counts layers, experts held, ids
    assert not [k for k in REDUCED if k.endswith(("_dim", "_rank", "_size"))
                and k != "vocab_size"]
    # the share: the router keeps its published width
    assert cfg["router_experts"] == PUBLISHED["n_routed_experts"]
    assert cfg["first_expert_held"] + cfg["n_routed_experts"] <= 256
    assert cfg["vocab_size"] * 8 == PUBLISHED["vocab_size"]
    assert entry["source"] == cfg["source"]
    for key in ("assumed", "departures", "precision", "deployment"):
        assert cfg[key], key
    assert "EP16" in cfg["deployment"]


def test_the_share_is_9_27_gb_of_weights():
    from perfbench import weights_dsv32
    with open(os.path.join(ROOT, "perfbench", "configs",
                           "deepseek-v3.2-exp.serve-ep16.json")) as f:
        cfg = json.load(f)
    assert weights_dsv32.n_params(cfg) == 4_635_518_208
    layer = sum(s[0] * (s[1] if len(s) > 1 else 1) * (s[2] if len(s) > 2
                                                      else 1)
                for _, s, _ in weights_dsv32.attention_leaves(cfg, 0, ""))
    # 187.1 M of latent attention, 14.0 M of indexer, and the gains
    assert layer == 187_105_280 + 13_959_168 + 1536 + 512 + 2 * 128


def test_only_the_last_layers_attention_output_is_scaled_up():
    from perfbench import weights_dsv32
    with open(os.path.join(ROOT, "perfbench", "configs",
                           "deepseek-v3.2-exp.serve-ep16.json")) as f:
        cfg = json.load(f)
    std = {i: dict((n, s) for n, _, s in weights_dsv32.attention_leaves(
        cfg, i, ""))["out_W"] * 16384 ** 0.5 for i in range(5)}
    assert std == {0: 0.25, 1: 0.25, 2: 0.25, 3: 0.25, 4: 4.0}
    assert "out_W" in cfg["assumed"]["weight_scales"]
    assert "quantiles" in cfg["assumed"]["selection_bias"]
    assert cfg["session_model"] == "model_dsv32"


def test_every_share_gets_the_same_biases():
    import jax
    import numpy as np

    from perfbench import weights_dsv32
    drawn = 0.02 * jax.random.normal(jax.random.PRNGKey(3), (64,))
    got = np.asarray(weights_dsv32.balance_bias(drawn, 16)).reshape(4, 16)
    for share in got:
        np.testing.assert_allclose(np.sort(share), np.sort(got[0]), rtol=1e-6)
    # rank for rank the draws, symmetric about zero, about as wide
    assert np.array_equal(np.argsort(got, -1),
                          np.argsort(np.asarray(drawn).reshape(4, 16), -1))
    assert abs(got[0].sum()) < 1e-6 and 0.017 < got[0].std() < 0.02
    leaves = weights_dsv32.finish({"n_routed_experts": 16},
                                  {"L1_moe_router_b": drawn, "L1_ln1_g": drawn})
    assert np.array_equal(np.asarray(leaves["L1_ln1_g"]), np.asarray(drawn))
    assert not np.array_equal(np.asarray(leaves["L1_moe_router_b"]),
                              np.asarray(drawn))
