"""Operations and bytes of a session's decode steps for
command-a-plus-05-2026, from shapes: grouped-query attention, three
window layers to a full one, over caches of keys and values; the expert
layer of a chip that holds a share, with four shared experts beside it.
The benchmark's own copy. A configuration is the dict read from
`perfbench/configs/<name>.json`; counted is what the work needs (every
key and value row a layer's query may see, once, every layer), not what
an implementation reads.
"""

from __future__ import annotations

# routing's expectations read the same keys as sarvam-105b's
from perfbench.counts_sarvam import (experts_touched_expected,
                                     held_assignments_expected)


def keys_seen(cfg: dict, i: int, position: int) -> int:
    """Keys a query of layer ``i`` at 0-based ``position`` sees: all up
    to its own in a full layer, the last `sliding_window` in a window
    layer."""
    if cfg["layer_types"][i] == "full_attention":
        return position + 1
    return min(position + 1, cfg["sliding_window"])


def kv_rows(cfg: dict, batch: int, start: int, n_new: int) -> float:
    """(Session, kv head, key) rows a turn's scanned positions read, every
    layer."""
    return float(batch * cfg["num_key_value_heads"] * sum(
        keys_seen(cfg, i, p) for i in range(cfg["num_hidden_layers"])
        for p in range(start, start + n_new)))


def attn_flops(cfg: dict, batch: int, start: int, n_new: int) -> float:
    """Scores and weighted values of every query head over those rows."""
    group = cfg["num_attention_heads"] // cfg["num_key_value_heads"]
    return 4.0 * group * cfg["head_dim"] * kv_rows(cfg, batch, start, n_new)


def attn_bytes(cfg: dict, batch: int, start: int, n_new: int,
               itemsize: int = 2) -> float:
    """A key and a value row of `head_dim` each, for each of those rows."""
    return 2.0 * cfg["head_dim"] * itemsize * kv_rows(cfg, batch, start,
                                                      n_new)


def expert_bytes(cfg: dict, itemsize: int = 2) -> float:
    """One expert's three matrices (shared or routed: the same width)."""
    return 3.0 * cfg["hidden_size"] * cfg["intermediate_size"] * itemsize


def moe_bytes(cfg: dict, batch: int, n_new: int, itemsize: int = 2) -> float:
    """Weight bytes a turn's steps are expected to read in the expert
    layers: the held experts touched under uniform routing and the four
    shared ones, every layer, every step."""
    per_layer = (experts_touched_expected(cfg, batch)
                 + cfg["num_shared_experts"]) * expert_bytes(cfg, itemsize)
    return per_layer * cfg["num_hidden_layers"] * n_new


def token_flops(cfg: dict, position: int) -> float:
    """Model FLOPs of one token's forward at 0-based ``position``: the
    q|k|v and out projections, scores and values of every head over the
    keys it sees, the router, the shared experts and the expected held
    assignments, and the sliced tied head."""
    d, h, hkv, hd = (cfg["hidden_size"], cfg["num_attention_heads"],
                     cfg["num_key_value_heads"], cfg["head_dim"])
    proj = 2.0 * (d * (h + 2 * hkv) * hd + h * hd * d)
    moe = (2.0 * d * cfg["router_experts"]
           + 6.0 * d * cfg["intermediate_size"]
           * (cfg["num_shared_experts"] + held_assignments_expected(cfg)))
    attn = sum(4.0 * h * hd * keys_seen(cfg, i, position)
               for i in range(cfg["num_hidden_layers"]))
    return (cfg["num_hidden_layers"] * (proj + moe) + attn
            + 2.0 * d * cfg["vocab_size"])


def turn_flops(cfg: dict, batch: int, start: int, n_new: int) -> float:
    """Model FLOPs of one turn: the ``n_new`` scanned positions of
    every row, and nothing of the cache that was there."""
    return batch * sum(token_flops(cfg, p)
                       for p in range(start, start + n_new))

