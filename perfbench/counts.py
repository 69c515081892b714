"""Operations and bytes that the algorithms need, computed from shapes.

The benchmark's own copy (the program's `flops_per_token` and
`benchmarks/kernel_bench.py` formulas may change; the yardstick may not).
A configuration here is the dict read from `perfbench/configs/<name>.json`
(the published key names).
"""

from __future__ import annotations


def _dims(cfg: dict):
    d = cfg["hidden_size"]
    h = cfg["num_attention_heads"]
    return d, h, cfg["num_key_value_heads"], d // h, cfg["intermediate_size"]


def visible_keys(cfg: dict, position: int) -> int:
    """Keys that the query at 0-based ``position`` attends (causal, and
    at most the last ``sliding_window``)."""
    w = cfg.get("sliding_window") or 0
    return min(position + 1, w) if w else position + 1


def mean_visible_keys(cfg: dict, seq_len: int) -> float:
    w = cfg.get("sliding_window") or 0
    we = min(w, seq_len) if w else seq_len
    return (we * (we + 1) / 2 + (seq_len - we) * we) / seq_len


def forward_flops_per_token(cfg: dict, keys_visible: float) -> float:
    """Matmul FLOPs of one forward pass for one token that attends
    ``keys_visible`` keys: q/k/v and output projections, the score and
    value contractions of every query head, SwiGLU's three matrices and
    the output head. Norms, softmax, RoPE and the embedding gather are
    not counted (understates utilization)."""
    d, h, hkv, hd, dff = _dims(cfg)
    qkv = 2.0 * d * (h + 2 * hkv) * hd
    out = 2.0 * d * d
    attn = 4.0 * d * keys_visible
    ffn = 6.0 * d * dff
    head = 2.0 * d * cfg["vocab_size"]
    return cfg["num_hidden_layers"] * (qkv + out + attn + ffn) + head


def train_flops_per_token(cfg: dict, seq_len: int) -> float:
    """Forward and backward (3x forward) for one token of a sequence of
    ``seq_len``; recomputed operations are not counted."""
    return 3.0 * forward_flops_per_token(cfg, mean_visible_keys(cfg, seq_len))


def decode_request_flops(cfg: dict, batch: int, prompt: int,
                         n_new: int) -> float:
    """Model FLOPs of one request: the forward pass over every position
    that produces a token or fills the cache. The last of the
    ``prompt + n_new`` positions is emitted and never fed back."""
    fed = prompt + n_new - 1
    total = sum(forward_flops_per_token(cfg, visible_keys(cfg, p))
                for p in range(fed))
    return batch * total


def flash_train_flops(cfg: dict, batch: int, seq_len: int) -> float:
    """Flash attention forward (4·L·keys·hd per head) and backward (10:
    the score recompute, dV, dP, dQ, dK), every layer, one step."""
    d, h, _, hd, _ = _dims(cfg)
    pairs = seq_len * mean_visible_keys(cfg, seq_len)
    return 14.0 * batch * h * pairs * hd * cfg["num_hidden_layers"]


def flash_train_bytes(cfg: dict, batch: int, seq_len: int,
                      itemsize: int = 2) -> float:
    """Least HBM traffic of those kernels for one step: forward reads q,
    k, v and writes o; backward reads q, k, v, o, do and writes dq, dk,
    dv (the float32 log-sum-exp rows are left out)."""
    _, h, hkv, hd, _ = _dims(cfg)
    q = batch * seq_len * h * hd
    kv = batch * seq_len * hkv * hd
    fwd = 2 * q + 2 * kv
    bwd = 4 * q + 4 * kv
    return float(itemsize * (fwd + bwd) * cfg["num_hidden_layers"])


def decode_kernel_bytes(cfg: dict, batch: int, prompt: int, n_new: int,
                        itemsize: int = 2) -> float:
    """Cache bytes that the decode steps of one request must read: at
    each scanned position the visible keys and values of every layer."""
    _, _, hkv, hd, _ = _dims(cfg)
    rows = sum(visible_keys(cfg, p) for p in range(prompt, prompt + n_new - 1))
    return float(2 * itemsize * batch * hkv * hd * rows
                 * cfg["num_hidden_layers"])


def decode_kernel_flops(cfg: dict, batch: int, prompt: int,
                        n_new: int) -> float:
    d, h, _, hd, _ = _dims(cfg)
    rows = sum(visible_keys(cfg, p) for p in range(prompt, prompt + n_new - 1))
    return 4.0 * batch * h * hd * rows * cfg["num_hidden_layers"]


def n_params(cfg: dict) -> int:
    d, h, hkv, hd, dff = _dims(cfg)
    layer = d * (h + 2 * hkv) * hd + d * d + 3 * d * dff + 2 * d
    return cfg["num_hidden_layers"] * layer + cfg["vocab_size"] * d + d
