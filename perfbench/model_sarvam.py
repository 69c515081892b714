"""What the `session` driver asks of a model family, for sarvam-105b
(`"session_model": "model_sarvam"` in the configuration): weights, the
program's configuration, and the comparison that decides `correct`."""

from __future__ import annotations

import sys
import time

import numpy as np

from perfbench import compare, counts_sarvam, reference_sarvam, weights_sarvam

# the checked turns run `decode_from(stats=True)`: where the router sent
# each token is part of the comparison
COUNTERS = True


def program_config(cfg: dict):
    """The published keys as the program's `TransformerConfig`."""
    from lua_mapreduce_tpu.models.transformer import (LatentAttention,
                                                      TransformerConfig)
    yarn = cfg["rope_scaling"]
    latent = LatentAttention(
        q_rank=cfg.get("q_lora_rank") or 0, kv_rank=cfg["kv_lora_rank"],
        nope_dim=cfg["qk_nope_head_dim"], rope_dim=cfg["qk_rope_head_dim"],
        v_dim=cfg["v_head_dim"], qk_norm=cfg["use_qk_norm"],
        rope_factor=float(yarn["factor"]),
        rope_original=yarn["original_max_position_embeddings"],
        beta_fast=float(yarn["beta_fast"]), beta_slow=float(yarn["beta_slow"]),
        mscale_all_dim=float(yarn["mscale_all_dim"]))
    return TransformerConfig(
        vocab=cfg["vocab_size"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"],
        n_layers=cfg["num_hidden_layers"], d_ff=cfg["intermediate_size"],
        max_seq=cfg["max_position_embeddings"], rope=True,
        rope_base=float(cfg["rope_theta"]), norm="rms",
        norm_eps=float(cfg["rms_norm_eps"]), ffn="swiglu",
        tied_head=cfg["tie_word_embeddings"], latent=latent,
        moe_experts=cfg["router_experts"], moe_router="grouped",
        moe_top_k=cfg["num_experts_per_tok"],
        moe_scale=float(cfg["routed_scaling_factor"]),
        moe_d_ff=cfg["moe_intermediate_size"],
        moe_shared=cfg["num_shared_experts"],
        moe_held=(cfg["first_expert_held"], cfg["num_experts"]),
        moe_first_dense=cfg["first_k_dense_replace"])


def make_params(cfg: dict, seed: int):
    """The seed's weights in the served type. A program that cannot be
    told this configuration says so first, before 9 GB are drawn."""
    program_config(cfg)
    return weights_sarvam.make_params(cfg, seed)


def say_counters(counters: dict, cfg: dict, t: dict) -> None:
    """The program's counters of the checked turns, as fields of an
    lmr-trace span where a tracer is installed, and on standard error."""
    held = np.concatenate([np.asarray(c["held_assignments"])
                           for c in counters.values()])    # (steps, layers)
    touched = np.concatenate([np.asarray(c["experts_touched"])
                              for c in counters.values()])
    fields = {
        "held_assignments_per_token": float(held.mean() / t["batch"]),
        "held_assignments_expected":
            counts_sarvam.held_assignments_expected(cfg),
        "experts_touched_mean": float(touched.mean()),
        "experts_touched_max": int(touched.max()),
        "experts_touched_expected":
            counts_sarvam.experts_touched_expected(cfg, t["batch"]),
    }
    from lua_mapreduce_tpu.trace.span import active_tracer
    tracer = active_tracer()
    if tracer is not None:
        tracer.op("lm.session.counters", time.time(), **fields)
    print("counters " + " ".join(f"{k} {v}" for k, v in fields.items()),
          file=sys.stderr)


def turns_of(session, picks: list, row: int) -> tuple:
    """Of the checked requests of one row: (tails (R, n) fed to the
    positions, served (R, n) tokens, experts (expert layers, R, n,
    k))."""
    requests = [r for r, w in picks if w == row]
    served = np.stack([session.outputs[r][row] for r in requests])
    fed = np.stack([session.fed(r)[row] for r in requests])
    tails = np.concatenate([fed[:, None], served[:, :-1]], axis=1)
    experts = np.stack([np.asarray(session.counters[r]["experts"])
                        [:, :, row] for r in requests])      # (R, n, Lm, k)
    return tails, served, experts.transpose(2, 0, 1, 3)


def readings_of(served, experts, judged: dict) -> dict:
    """The comparison's numbers for tokens and routed experts against a
    tails pass of the reference that was forced to the same experts."""
    return dict(
        compare.decode(reference_sarvam.logit_gaps(judged["logits"], served)),
        routing_miss=reference_sarvam.routing_miss(experts,
                                                   judged["experts"]))


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
        state = reference_sarvam.context_pass(cfg, seed, context[row])
        free, forced = reference_sarvam.tails_pass(
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
