"""Operations and bytes of a session's decode steps for sarvam-105b,
from shapes: dense latent attention over the whole cache (absorbed:
a cached row is key and value at once), direct queries, the expert
layer of a chip that holds a share. The benchmark's own copy. A
configuration is the dict read from `perfbench/configs/<name>.json`;
counted is what the work needs (every cached row up to the position,
every layer), not what an implementation reads.
"""

from __future__ import annotations


def n_moe_layers(cfg: dict) -> int:
    return cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]


def cache_row(cfg: dict) -> int:
    """Values of one cached row: the latent and the shared rope key."""
    return cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]


def held_assignments_expected(cfg: dict) -> float:
    """Routed (token, expert) pairs a token leaves on the held experts,
    in expectation under uniform routing."""
    return (cfg["num_experts_per_tok"] * cfg["num_experts"]
            / cfg["router_experts"])


def experts_touched_expected(cfg: dict, batch: int) -> float:
    """Held experts that at least one of ``batch`` tokens chose, a
    layer, in expectation under uniform routing."""
    p = cfg["num_experts_per_tok"] / cfg["router_experts"]
    return cfg["num_experts"] * (1.0 - (1.0 - p) ** batch)


def attn_pair_flops(cfg: dict) -> float:
    """FLOPs of one (query, key) pair of absorbed latent attention, all
    heads: the score over the row, the weighted sum over the latent."""
    return (2.0 * cfg["num_attention_heads"]
            * (cache_row(cfg) + cfg["kv_lora_rank"]))


def token_flops(cfg: dict, position: int) -> float:
    """Model FLOPs of one token's forward at 0-based ``position``: the
    attention's projections (the query's absorption into the latent's
    basis and the value half's way out among them), scores and values
    of every head over all ``position + 1`` keys, the dense FFN or the
    router, the shared expert and the expected held assignments, and
    the sliced head."""
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    kvr = cfg["kv_lora_rank"]
    nope, rope, v = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                     cfg["v_head_dim"])
    proj = 2.0 * (d * h * (nope + rope) + d * (kvr + rope)
                  + kvr * h * (nope + v) + h * v * d)
    attn = attn_pair_flops(cfg) * (position + 1)
    dense = 6.0 * d * cfg["intermediate_size"]
    moe = (2.0 * d * cfg["router_experts"]
           + 6.0 * d * cfg["moe_intermediate_size"]
           * (cfg["num_shared_experts"] + held_assignments_expected(cfg)))
    layers, first = cfg["num_hidden_layers"], cfg["first_k_dense_replace"]
    return (layers * (proj + attn) + first * dense + (layers - first) * moe
            + 2.0 * d * cfg["vocab_size"])


def turn_flops(cfg: dict, batch: int, start: int, n_new: int) -> float:
    """Model FLOPs of one turn: the ``n_new`` scanned positions of
    every row, and nothing of the cache that was there."""
    return batch * sum(token_flops(cfg, p)
                       for p in range(start, start + n_new))


def keys_read(cfg: dict, batch: int, start: int, n_new: int) -> float:
    """(Query, key) pairs of one turn: at each scanned position every
    cached row up to it, every row of the batch, every layer."""
    return float(sum(p + 1 for p in range(start, start + n_new))
                 * batch * cfg["num_hidden_layers"])


def latent_attn_flops(cfg: dict, batch: int, start: int, n_new: int) -> float:
    return attn_pair_flops(cfg) * keys_read(cfg, batch, start, n_new)


def latent_attn_bytes(cfg: dict, batch: int, start: int, n_new: int,
                      itemsize: int = 2) -> float:
    """Cache bytes a turn must read: each of those rows once."""
    return itemsize * cache_row(cfg) * keys_read(cfg, batch, start, n_new)


def moe_expert_bytes(cfg: dict, batch: int, n_new: int,
                     itemsize: int = 2) -> float:
    """Weight bytes of the held experts that one turn's steps are
    expected to touch under uniform routing: three matrices an expert."""
    one = 3.0 * cfg["hidden_size"] * cfg["moe_intermediate_size"] * itemsize
    return (one * experts_touched_expected(cfg, batch) * n_moe_layers(cfg)
            * n_new)
