"""What the `session` driver asks of a model family, for
command-a-plus-05-2026 (`"session_model": "model_cmdaplus"` in the
configuration): weights, the program's configuration, and the comparison
that decides `correct`."""

from __future__ import annotations

import sys

import numpy as np

from perfbench import reference_cmdaplus, weights_cmdaplus
# the counters, the turns' rows and the readings are sarvam's: the same
# keys of the configuration and the same comparison
from perfbench.model_sarvam import readings_of, say_counters, turns_of

# the checked turns run `decode_from(stats=True)`: where the router sent
# each token is part of the comparison
COUNTERS = True

KINDS = {"sliding_attention": "swa", "full_attention": "full_nope"}


def pattern(cfg: dict) -> tuple:
    """One period of `layer_types` as the program's `attn_pattern`: the
    published list is that period over and over, and the layers held
    here are whole periods."""
    period, types = cfg["layer_switch"], cfg["layer_types"]
    if (any(t != types[i % period] for i, t in enumerate(types))
            or cfg["num_hidden_layers"] % period):
        raise SystemExit(f"model_cmdaplus: layer_types is no period of "
                         f"{period} repeated over whole periods")
    return tuple(KINDS[t] for t in types[:period])


def program_config(cfg: dict):
    """The published keys as the program's `TransformerConfig`. A
    program that cannot be told this configuration (one without
    attention patterns or the parallel block) stops here, with a name
    for what it lacks. The shared experts are averaged in the weights
    (`weights_cmdaplus.program_form`)."""
    from lua_mapreduce_tpu.models.transformer import TransformerConfig
    try:
        return TransformerConfig(
            vocab=cfg["vocab_size"], d_model=cfg["hidden_size"],
            n_heads=cfg["num_attention_heads"],
            n_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
            n_layers=cfg["num_hidden_layers"],
            d_ff=cfg["intermediate_size"],
            max_seq=cfg["max_position_embeddings"], rope=True,
            rope_base=float(cfg["rope_theta"]), norm="ln_gain",
            norm_eps=float(cfg["layer_norm_eps"]), ffn="swiglu",
            tied_head=cfg["tie_word_embeddings"],
            window=cfg["sliding_window"], attn_pattern=pattern(cfg),
            parallel_block=cfg["use_parallel_block"],
            moe_experts=cfg["router_experts"], moe_router="grouped",
            moe_top_k=cfg["num_experts_per_tok"],
            moe_d_ff=cfg["intermediate_size"],
            moe_shared=cfg["num_shared_experts"],
            moe_router_bias=False,
            moe_held=(cfg["first_expert_held"], cfg["num_experts"]),
            moe_first_dense=cfg["first_k_dense_replace"])
    except TypeError as e:
        raise SystemExit(f"model_cmdaplus: the program cannot be told "
                         f"this configuration: {e}") from e


def make_params(cfg: dict, seed: int):
    """The seed's weights in the served type. A program that cannot be
    told this configuration says so first, before 9 GB are drawn."""
    program_config(cfg)
    return weights_cmdaplus.make_params(cfg, seed)


def judge(cell, seed: int, session, picks: list) -> dict:
    """The reference's forward over context + turn for the checked
    rows. The tails run twice: left to the reference's own router (on
    standard error: what it would read with every near-tied choice
    counted as an error of the logits), and forced to the experts the
    program's router chose. The readings are the forced pass's: the
    tokens' logit gaps, and the share of the program's experts that the
    reference, at the same hidden state, did not choose."""
    cfg = cell.config
    say_counters(session.counters, cfg, cell.traffic)
    context = session.context()
    per_row = []
    for row in sorted({row for _, row in picks}):
        tails, served, experts = turns_of(session, picks, row)
        state = reference_cmdaplus.context_pass(cfg, seed, context[row])
        free, forced = reference_cmdaplus.tails_pass(
            cfg, seed, state, tails, [{}, {"forced": experts}])
        per_row.append((served, experts, free, forced))
    served = np.concatenate([p[0] for p in per_row], 0)
    experts = np.concatenate([p[1] for p in per_row], 1)
    merged = [{"logits": np.concatenate([p[j]["logits"] for p in per_row], 0),
               "experts": np.concatenate([p[j]["experts"] for p in per_row],
                                         1)} for j in (2, 3)]
    print(f"served tokens: {len(np.unique(served))} distinct of "
          f"{served.size}", file=sys.stderr)
    left = readings_of(served, experts, merged[0])
    print("left to its own choices the reference reads "
          + " ".join(f"{k} {v:.6g}" for k, v in left.items()),
          file=sys.stderr)
    return readings_of(served, experts, merged[1])
