"""What the `session` driver asks of a model family, for
Phi-4-mini-flash-reasoning (`"session_model": "model_phi4flash"` in the
configuration): weights, the program's configuration, and the
comparison that decides `correct`."""

from __future__ import annotations

import sys
import time

import numpy as np

from perfbench import compare, reference_phi4flash, weights_phi4flash

# the model makes no data-dependent choice: `decode_from(stats=True)`
# has nothing to count
COUNTERS = False


def program_config(cfg: dict):
    """The published keys as the program's `TransformerConfig`. A
    program that has no hybrid stack fails here, at the import, before
    any weight is drawn."""
    from lua_mapreduce_tpu.models.transformer import (HybridStack,
                                                      TransformerConfig)
    stack = HybridStack.sambay(
        cfg["num_hidden_layers"], cfg["sliding_window"],
        ssm_state=cfg["mamba_d_state"], ssm_conv=cfg["mamba_d_conv"],
        ssm_expand=cfg["mamba_expand"], ssm_rank=cfg["mamba_dt_rank"])
    kinds = tuple(weights_phi4flash.layer_kind(cfg, i)
                  for i in range(cfg["num_hidden_layers"]))
    if (stack.kinds != kinds or cfg["mb_per_layer"] != 2
            or cfg["mlp_bias"] or cfg["lm_head_bias"]):
        raise ValueError(f"the program's SambaY stack is {stack.kinds}; the "
                         f"configuration's layers are {kinds}")
    return TransformerConfig(
        vocab=cfg["vocab_size"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"],
        n_layers=cfg["num_hidden_layers"], d_ff=cfg["intermediate_size"],
        max_seq=cfg["max_position_embeddings"], positions="none",
        norm="ln", norm_eps=float(cfg["layer_norm_eps"]), ffn="swiglu",
        tied_head=cfg["tie_word_embeddings"], hybrid=stack)


def make_params(cfg: dict, seed: int):
    """The seed's weights in the served type. A program that cannot be
    told this configuration says so first, before 7.7 GB are drawn."""
    program_config(cfg)
    return weights_phi4flash.make_params(cfg, seed)


def cache_shapes(cfg: dict, t: dict, program_cfg) -> dict:
    """The caches a session of this cell holds, as the program's own
    prefill and `decode_caches` lay them out: {leaf: ShapeDtypeStruct},
    evaluated abstractly (nothing runs, nothing is allocated)."""
    import jax
    import jax.numpy as jnp

    from lua_mapreduce_tpu.models.transformer import decode_caches, prefill
    total = t["context_len"] + t["n_new"]

    def prepared(params, ids):
        caches, _ = prefill(params, ids, cfg=program_cfg, total=total,
                            chunk=t.get("prefill_chunk"))
        return decode_caches(caches, cfg=program_cfg, p_len=t["context_len"],
                             total=total)

    return jax.eval_shape(
        prepared, jax.eval_shape(lambda: weights_phi4flash.make_params(cfg, 0)),
        jax.ShapeDtypeStruct((t["batch"], t["context_len"]), jnp.int32))


def say_counters(caches: dict, cfg: dict, t: dict) -> dict:
    """The sessions' caches by kind, from the cache pytree's own shapes
    (what grows with the position, what rolls, what is state, and the
    one snapshot of the last two that lets a turn be taken back),
    beside what the configuration's shapes give: as fields of an
    lmr-trace span where a tracer is installed, and on standard
    error."""
    total = t["context_len"] + t["n_new"]
    fields = {"growing_bytes": 0, "rolling_bytes": 0, "state_bytes": 0,
              "snapshot_bytes": 0}
    for name, leaf in caches.items():
        positions = leaf.shape[2] if leaf.ndim == 4 else 0
        kind = ("snapshot" if name.endswith("0") else
                "state" if not positions else
                "growing" if positions >= total else "rolling")
        fields[kind + "_bytes"] += int(np.prod(leaf.shape)) * leaf.dtype.itemsize
    expected = weights_phi4flash.cache_bytes(cfg, t["batch"], total)
    fields.update({k + "_bytes_expected": v for k, v in expected.items()})
    from lua_mapreduce_tpu.trace.span import active_tracer
    tracer = active_tracer()
    if tracer is not None:
        tracer.op("lm.session.counters", time.time(), **fields)
    print("counters " + " ".join(f"{k} {v}" for k, v in fields.items()),
          file=sys.stderr)
    return fields


def rows_of(session, picks: list) -> tuple:
    """Of every checked (request, row): the ids the reference passes
    over (context, the fed id, the served tokens but the last) and the
    served tokens."""
    context = session.context()
    rows, served = [], []
    for r, row in picks:
        out = session.outputs[r][row]
        rows.append(np.concatenate([context[row], session.fed(r)[row:row + 1],
                                    out[:-1]]))
        served.append(out)
    return np.stack(rows), np.stack(served)


def gaps_of(cfg: dict, seed: int, rows, served, **run) -> np.ndarray:
    """By how much each served token's logit lies below the best, in
    the reference's forward over its row: (R, n)."""
    n = served.shape[1]
    logits = np.stack([reference_phi4flash.forward(cfg, seed, row, n, **run)
                       for row in rows])
    return reference_phi4flash.logit_gaps(logits, served)


def judge(cell, seed: int, session, picks: list) -> dict:
    """The reference's forward over context + fed id + served tokens of
    every checked (request, row); the served tokens' logit gaps."""
    say_counters(cache_shapes(cell.config, cell.traffic, session.program_cfg),
                 cell.config, cell.traffic)
    rows, served = rows_of(session, picks)
    print(f"served tokens: {len(np.unique(served))} distinct of "
          f"{served.size}", file=sys.stderr)
    return compare.decode(gaps_of(cell.config, seed, rows, served))
