"""Operations and bytes of a session's decode steps, from shapes: for
DeepSeek-V3.2-Exp (latent attention, the indexer, the expert layer of
a chip that holds a share) and, for the `session` traffic kind, for the
dense grouped-query model that `counts.py` describes. The benchmark's
own copy. A configuration is the dict read from
`perfbench/configs/<name>.json`.
"""

from __future__ import annotations

from perfbench import counts


def is_dsv32(cfg: dict) -> bool:
    return cfg["model_type"] == "deepseek_v32"


def n_moe_layers(cfg: dict) -> int:
    return cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]


def held_assignments_expected(cfg: dict) -> float:
    """Routed (token, expert) pairs a token leaves on the held experts,
    in expectation under uniform routing."""
    return (cfg["num_experts_per_tok"] * cfg["n_routed_experts"]
            / cfg["router_experts"])


def experts_touched_expected(cfg: dict, batch: int) -> float:
    """Held experts that at least one of ``batch`` tokens chose, a
    layer, in expectation under uniform routing."""
    p = cfg["num_experts_per_tok"] / cfg["router_experts"]
    return cfg["n_routed_experts"] * (1.0 - (1.0 - p) ** batch)


def dsv32_token_flops(cfg: dict, position: int) -> float:
    """Model FLOPs of one token's forward at 0-based ``position``:
    the latent attention's projections (the key-value up-projection for
    the token itself), scores and values of every head over the
    min(position + 1, index_topk) selected keys, the indexer over all
    position + 1 keys, the dense FFN or the router, the shared expert
    and the expected held assignments, and the head."""
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    qr, kvr = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    nope, rope, v = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                     cfg["v_head_dim"])
    ih, idim = cfg["index_n_heads"], cfg["index_head_dim"]
    keys = position + 1
    attended = min(keys, cfg["index_topk"])
    proj = 2.0 * (d * qr + qr * h * (nope + rope) + d * (kvr + rope)
                  + kvr * h * (nope + v) + h * v * d)
    attn = 2.0 * h * (nope + rope + v) * attended
    indexer = 2.0 * (qr * ih * idim + d * idim + d * ih) + 2.0 * ih * idim * keys
    dense = 6.0 * d * cfg["intermediate_size"]
    ff = cfg["moe_intermediate_size"]
    moe = (2.0 * d * cfg["router_experts"]
           + 6.0 * d * ff * (cfg["n_shared_experts"]
                             + held_assignments_expected(cfg)))
    layers, first = cfg["num_hidden_layers"], cfg["first_k_dense_replace"]
    return (layers * (proj + attn + indexer) + first * dense
            + (layers - first) * moe + 2.0 * d * cfg["vocab_size"])


def turn_flops(cfg: dict, batch: int, start: int, n_new: int) -> float:
    """Model FLOPs of one turn: the ``n_new`` scanned positions of
    every row, and nothing of the cache that was there."""
    positions = range(start, start + n_new)
    if is_dsv32(cfg):
        return batch * sum(dsv32_token_flops(cfg, p) for p in positions)
    return batch * sum(counts.forward_flops_per_token(
        cfg, counts.visible_keys(cfg, p)) for p in positions)


def moe_expert_bytes(cfg: dict, batch: int, n_new: int,
                     itemsize: int = 2) -> float:
    """Weight bytes of the held experts that one turn's steps are
    expected to touch under uniform routing: three matrices an expert."""
    one = 3.0 * cfg["hidden_size"] * cfg["moe_intermediate_size"] * itemsize
    return (one * experts_touched_expected(cfg, batch) * n_moe_layers(cfg)
            * n_new)


def sparse_attn_bytes(cfg: dict, batch: int, start: int, n_new: int,
                      itemsize: int = 2) -> float:
    """Cache bytes a turn must read: at each scanned position the
    indexer's key of every cached position and the selected latent rows,
    every row of the batch, every layer."""
    row = cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]
    total = sum((p + 1) * cfg["index_head_dim"]
                + min(p + 1, cfg["index_topk"]) * row
                for p in range(start, start + n_new))
    return float(itemsize * batch * total * cfg["num_hidden_layers"])


def sparse_attn_flops(cfg: dict, batch: int, start: int, n_new: int) -> float:
    h = cfg["num_attention_heads"]
    row = cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]
    ih, idim = cfg["index_n_heads"], cfg["index_head_dim"]
    total = sum(2.0 * ih * idim * (p + 1)
                + 2.0 * h * (row + cfg["kv_lora_rank"])
                * min(p + 1, cfg["index_topk"])
                for p in range(start, start + n_new))
    return batch * total * cfg["num_hidden_layers"]


def gqa_kernel_bytes(cfg: dict, batch: int, start: int, n_new: int,
                     itemsize: int = 2) -> float:
    """Cache bytes the decode kernel must read in one turn of the dense
    grouped-query model: `counts.decode_kernel_bytes` over the ``n_new``
    scanned positions (a request there scans one fewer than it emits)."""
    return counts.decode_kernel_bytes(cfg, batch, start, n_new + 1, itemsize)


def gqa_kernel_flops(cfg: dict, batch: int, start: int, n_new: int) -> float:
    return counts.decode_kernel_flops(cfg, batch, start, n_new + 1)
