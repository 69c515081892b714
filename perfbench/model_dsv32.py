"""What the `session` driver asks of a model family, for
DeepSeek-V3.2-Exp (`"session_model": "model_dsv32"` in the
configuration): weights, the program's configuration, and the
comparison that decides `correct`."""

from __future__ import annotations

import sys
import time

import numpy as np

from perfbench import compare, counts_dsv32, reference_dsv32, weights_dsv32

make_params = weights_dsv32.make_params
# the checked turns run `decode_from(stats=True)`: what attention read and
# where the router sent each token are part of the comparison
COUNTERS = True


def program_config(cfg: dict):
    """The published keys as the program's `TransformerConfig`."""
    from lua_mapreduce_tpu.models.transformer import (LatentAttention,
                                                      TransformerConfig)
    yarn = cfg["rope_scaling"]
    latent = LatentAttention(
        q_rank=cfg["q_lora_rank"], kv_rank=cfg["kv_lora_rank"],
        nope_dim=cfg["qk_nope_head_dim"], rope_dim=cfg["qk_rope_head_dim"],
        v_dim=cfg["v_head_dim"], index_heads=cfg["index_n_heads"],
        index_dim=cfg["index_head_dim"], index_top_k=cfg["index_topk"],
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
        moe_top_k=cfg["num_experts_per_tok"], moe_groups=cfg["n_group"],
        moe_topk_groups=cfg["topk_group"],
        moe_scale=float(cfg["routed_scaling_factor"]),
        moe_d_ff=cfg["moe_intermediate_size"],
        moe_shared=cfg["n_shared_experts"],
        moe_held=(cfg["first_expert_held"], cfg["n_routed_experts"]),
        moe_first_dense=cfg["first_k_dense_replace"])


def say_counters(counters: dict, cfg: dict, t: dict) -> None:
    """The program's counters of the checked turns, as fields of an
    lmr-trace span where a tracer is installed, and on standard error."""
    held = np.concatenate([np.asarray(c["held_assignments"])
                           for c in counters.values()])    # (steps, layers)
    touched = np.concatenate([np.asarray(c["experts_touched"])
                              for c in counters.values()])
    keys = np.concatenate([np.sum(np.asarray(c["selected"]) >= 0, axis=-1)
                           for c in counters.values()])
    fields = {
        "held_assignments_per_token": float(held.mean() / t["batch"]),
        "held_assignments_expected":
            counts_dsv32.held_assignments_expected(cfg),
        "experts_touched_mean": float(touched.mean()),
        "experts_touched_max": int(touched.max()),
        "experts_touched_expected":
            counts_dsv32.experts_touched_expected(cfg, t["batch"]),
        "keys_selected_per_query": float(keys.mean()),
    }
    from lua_mapreduce_tpu.trace.span import active_tracer
    tracer = active_tracer()
    if tracer is not None:
        tracer.op("lm.session.counters", time.time(), **fields)
    print("counters " + " ".join(f"{k} {v}" for k, v in fields.items()),
          file=sys.stderr)


def turns_of(session, picks: list, row: int) -> tuple:
    """Of the checked requests of one row: (requests, tails (R, n) fed
    to the positions, served (R, n) tokens, selected (layers, R, n, K),
    experts (expert layers, R, n, k))."""
    requests = [r for r, w in picks if w == row]
    served = np.stack([session.outputs[r][row] for r in requests])
    fed = np.stack([session.fed(r)[row] for r in requests])
    tails = np.concatenate([fed[:, None], served[:, :-1]], axis=1)
    selected = np.stack([np.asarray(session.counters[r]["selected"])
                         [:, :, row] for r in requests])     # (R, n, L, K)
    experts = np.stack([np.asarray(session.counters[r]["experts"])
                        [:, :, row] for r in requests])      # (R, n, Lm, k)
    return (requests, tails, served, selected.transpose(2, 0, 1, 3),
            experts.transpose(2, 0, 1, 3))


def readings_of(served, selected, experts, judged: dict) -> dict:
    """The comparison's numbers for tokens, attended positions and
    routed experts against a tails pass of the reference that was
    forced to the same positions and experts."""
    return dict(
        compare.decode(reference_dsv32.logit_gaps(judged["logits"], served)),
        index_selection_miss=reference_dsv32.selection_miss(
            selected, judged["selected"]),
        routing_miss=reference_dsv32.routing_miss(experts,
                                                  judged["experts"]))


def judge(cell, seed: int, session, picks: list) -> dict:
    """The reference's forward over context + turn for the checked
    rows. The tails run twice: left to the reference's own indexer and
    router (on standard error: what it would read with every near-tied
    choice counted as an error of the logits), and forced to the
    positions the program's attention read and the experts its router
    chose. The readings are the forced pass's: the tokens' logit gaps,
    and the share of the program's positions and experts that the
    reference, at the same hidden state, did not choose."""
    cfg = cell.config
    say_counters(session.counters, cfg, cell.traffic)
    context = session.context()
    per_row = []
    for row in sorted({row for _, row in picks}):
        _, tails, served, selected, experts = turns_of(session, picks, row)
        state = reference_dsv32.context_pass(cfg, seed, context[row])
        free, forced = reference_dsv32.tails_pass(
            cfg, seed, state, tails, [{}, {"forced": (selected, experts)}])
        per_row.append((served, selected, experts, free, forced))
    join = lambda i, axis: np.concatenate([p[i] for p in per_row], axis)  # noqa: E731
    merged = [{k: np.concatenate([p[j][k] for p in per_row],
                                 0 if k == "logits" else 1)
               for k in ("logits", "selected", "experts")} for j in (3, 4)]
    served, selected, experts = join(0, 0), join(1, 1), join(2, 1)
    left = readings_of(served, selected, experts, merged[0])
    print("left to its own choices the reference reads "
          + " ".join(f"{k} {v:.6g}" for k, v in left.items()),
          file=sys.stderr)
    return readings_of(served, selected, experts, merged[1])
