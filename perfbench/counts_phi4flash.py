"""Operations and bytes of a session's decode steps for
Phi-4-mini-flash-reasoning, from shapes. The benchmark's own copy: it
imports nothing of the program. A configuration is the dict read from
`perfbench/configs/<name>.json`; counted is what the work needs, not
what an implementation reads: for each of the layers that read the one
shared cache (the full-attention layer and every cross layer) every key
and value row up to the position once, for each window layer the rows
in its window, and the state read and written once.
"""

from __future__ import annotations

from perfbench.weights_phi4flash import layer_kind, sizes


def kinds(cfg: dict) -> list:
    return [layer_kind(cfg, i) for i in range(cfg["num_hidden_layers"])]


def kv_row_bytes(cfg: dict, itemsize: int = 2) -> int:
    """Bytes of one position's keys and values, all kv heads."""
    z = sizes(cfg)
    return 2 * z["hkv"] * z["hd"] * itemsize


def keys_read(cfg: dict, position: int) -> int:
    """Cached positions a token at 0-based ``position`` attends, summed
    over the layers: all ``position + 1`` in each reader of the shared
    cache, at most the window in each window layer."""
    k = kinds(cfg)
    whole = k.count("full") + k.count("cross")
    return (whole * (position + 1)
            + k.count("swa") * min(position + 1, cfg["sliding_window"]))


def pair_flops(cfg: dict) -> float:
    """FLOPs of one (query position, key) pair, all heads: a head's
    score over its 64 values, its sum over the pair's 128."""
    z = sizes(cfg)
    return 2.0 * z["h"] * 3 * z["hd"]


def token_flops(cfg: dict, position: int) -> float:
    """Model FLOPs of one token's forward at 0-based ``position``:
    every layer's projections and MLP, the state-space layers' scan (6
    an element of the state: the decay, the input, the output), both
    softmaxes of every query pair over the keys it sees (a score over
    64, a sum over the 128-wide values), and the tied head."""
    z = sizes(cfg)
    d, e, n, r, hd, h = z["d"], z["e"], z["n"], z["r"], z["hd"], z["h"]
    kv = z["hkv"] * hd
    mixer = {
        "ssm": 2.0 * (d * 2 * e + z["taps"] * e + e * (r + 2 * n) + r * e
                      + e * d) + 6.0 * e * n,
        "gmu": 2.0 * (d * e + e * d),
        "swa": 2.0 * (d * (h * hd + 2 * kv) + h * hd * d),
        "cross": 2.0 * (d * h * hd + h * hd * d)}
    mixer["full"] = mixer["swa"]
    mlp = 6.0 * d * cfg["intermediate_size"]
    return (sum(mixer[k] + mlp for k in kinds(cfg))
            + pair_flops(cfg) * keys_read(cfg, position)
            + 2.0 * d * cfg["vocab_size"])


def turn_flops(cfg: dict, batch: int, start: int, n_new: int) -> float:
    """Model FLOPs of one turn: the ``n_new`` scanned positions of
    every row, and nothing of the cache that was there."""
    return batch * sum(token_flops(cfg, p)
                       for p in range(start, start + n_new))


def attn_bytes(cfg: dict, batch: int, start: int, n_new: int,
               itemsize: int = 2) -> float:
    """Cache bytes a turn's attention must read: each of those rows
    once, every row of the batch."""
    return float(batch * kv_row_bytes(cfg, itemsize)
                 * sum(keys_read(cfg, p) for p in range(start, start + n_new)))


def attn_flops(cfg: dict, batch: int, start: int, n_new: int) -> float:
    return float(batch * pair_flops(cfg)
                 * sum(keys_read(cfg, p) for p in range(start, start + n_new)))


def state_bytes(cfg: dict, batch: int, n_new: int, itemsize: int = 2) -> float:
    """State bytes a turn reads and writes: every state-space layer's
    float32 state and its convolution tail, once each way a step."""
    z = sizes(cfg)
    one = z["e"] * (z["n"] * 4 + (z["taps"] - 1) * itemsize)
    return 2.0 * batch * one * kinds(cfg).count("ssm") * n_new


def step_bytes(cfg: dict, batch: int, position: int, n_params: int,
               itemsize: int = 2) -> float:
    """What one decode step at ``position`` must stream: the weights
    once, the caches its attention reads, the states both ways."""
    return (itemsize * n_params + attn_bytes(cfg, batch, position, 1, itemsize)
            + state_bytes(cfg, batch, 1, itemsize))
