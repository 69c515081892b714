"""command-a-plus-05-2026's weights from the seed: the table of leaves of
the program's flat parameter dict (grouped-query attention with a fused
q|k|v matrix, one gain-only LayerNorm a layer, the held experts, the
four shared experts side by side, the tied head), drawn by `weights.py`'s
`make_leaves`, each leaf from a key of its own, so that the program's
bfloat16 copy and the reference's float32 one hold the same values.

A configuration is the dict read from `perfbench/configs/<name>.json`:
`num_experts` counts the experts held here (the chip's share),
`router_experts` is the router's published width.

The leaves are the checkpoint's: q and k in the interleaved (GPT-J)
order of their pairs. `make_params`, the program's copy, puts every
head's q and k columns in the rotate-half order the program turns
(`half_pairs`), and divides the shared experts' down projection by their
count, so that the program's sum of them is their mean; the reference
turns the interleaved pairs and averages the four itself.

Scales: matrices N(0, 1/fan_in), gains 1, each shared expert's down
projection N(0, 1/its width). Four are set, and the configuration file
states them (`assumed.weight_scales`):

- `tok_emb` N(0, 1/d): the head is tied, so the logits' deviation is
  the embedding's norm; at 1/d it is 1, as in the other cells.
- the query columns of `qkv_W` `Q_SCALE` times N(0, 1/d): a head's
  score over random keys then has the deviation 2, and the full layer's
  softmax weighs some hundreds of its 32k keys, not all of them alike
  (at 1 attention's output would be the mean of the values and no fault
  of the kernel would move a logit).
- attention's `out_W` `OUT_SCALE[kind]` times N(0, 1/fan_in), so that
  attention's output is a fifth to a half of the stream from layer 1 on
  (the reference prints the shares it finds, layer by layer). Random
  layers make the stream ever more alike from position to position, so
  a head's weighted sum of values shrinks less with depth than the
  softmax's width says: at 2 and 4.5 the chip's reference read 11.5,
  0.75, 1.16, 2.38 by layer (layer 0's stream is the embedding, 1/64 of
  a unit), at 0.8 and 0.6 it read 4.6, 0.29, 0.23, 0.12 (PERF.md
  section 4); the full layer's 1.2 doubles its 0.12.
- the router's columns levelled over the shares
  (`weights_sarvam.level_shares`).
"""

from __future__ import annotations

import math

import jax.numpy as jnp
import numpy as np

from perfbench import weights
from perfbench.weights_dsv32 import _leaves_from
from perfbench.weights_sarvam import level_shares

Q_SCALE = 2.0
OUT_SCALE = {"sliding_attention": 0.8, "full_attention": 1.2}


def layer_type(cfg: dict, i: int) -> str:
    return cfg["layer_types"][i]


def head_dims(cfg: dict) -> tuple:
    return (cfg["num_attention_heads"], cfg["num_key_value_heads"],
            cfg["head_dim"])


def layer_leaves(cfg: dict, i: int) -> list:
    d, p = cfg["hidden_size"], f"L{i}_"
    h, hkv, hd = head_dims(cfg)
    ff, held = cfg["intermediate_size"], cfg["num_experts"]
    sw = cfg["num_shared_experts"] * ff
    return [(p + "qkv_W", (d, (h + 2 * hkv) * hd), d ** -0.5),
            (p + "out_W", (h * hd, d),
             OUT_SCALE[layer_type(cfg, i)] * (h * hd) ** -0.5),
            (p + "ln1_g", (d,), None),
            (p + "moe_router_W", (d, cfg["router_experts"]), d ** -0.5),
            (p + "moe_wg", (held, d, ff), d ** -0.5),
            (p + "moe_wu", (held, d, ff), d ** -0.5),
            (p + "moe_wd", (held, ff, d), ff ** -0.5),
            (p + "moe_sg", (d, sw), d ** -0.5),
            (p + "moe_su", (d, sw), d ** -0.5),
            (p + "moe_sd", (sw, d), ff ** -0.5)]


def leaf_table(cfg: dict) -> list:
    """Every leaf as (name, shape, std); its position is what its key is
    folded from."""
    d = cfg["hidden_size"]
    table = [("tok_emb", (cfg["vocab_size"], d), d ** -0.5)]
    for i in range(cfg["num_hidden_layers"]):
        table += layer_leaves(cfg, i)
    return table + [("lnf_g", (d,), None)]


def indexed(cfg: dict, names=None) -> tuple:
    rows = tuple((i, n, s, std) for i, (n, s, std)
                 in enumerate(leaf_table(cfg)))
    if names is not None:
        rows = tuple(r for r in rows if r[1] in names)
    return rows


def layer_names(cfg: dict, i: int) -> set:
    return {name for name, _, _ in layer_leaves(cfg, i)}


def finish(cfg: dict, leaves: dict) -> dict:
    """What follows the draws, for the program's copy and the
    reference's alike: the query columns scaled, the router's columns
    levelled over the shares."""
    h, _, hd = head_dims(cfg)
    out = {}
    for name, leaf in leaves.items():
        if name.endswith("_qkv_W"):
            leaf = leaf.at[:, :h * hd].multiply(Q_SCALE)
        elif name.endswith("moe_router_W"):
            leaf = level_shares(leaf, cfg["num_experts"])
        out[name] = leaf
    return out


def half_order(cfg: dict) -> np.ndarray:
    """The columns of the fused q|k|v matrix in the program's order:
    within every q and k head the interleaved pairs' first members, then
    their second (pair (2i, 2i + 1) becomes (i, i + hd/2)); v as it is."""
    h, hkv, hd = head_dims(cfg)
    within = np.concatenate([np.arange(0, hd, 2), np.arange(1, hd, 2)])
    turned = (np.arange(h + hkv)[:, None] * hd + within[None, :]).reshape(-1)
    return np.concatenate([turned, np.arange((h + hkv) * hd,
                                             (h + 2 * hkv) * hd)])


def half_pairs(cfg: dict, qkv_w):
    """A checkpoint's q|k|v matrix as the program turns it: scores of
    a query and a key are the same in either order of both."""
    return qkv_w[:, half_order(cfg)]


def program_form(cfg: dict, leaves: dict) -> dict:
    """Drawn leaves as the program holds them: finished, every fused
    q|k|v matrix in the program's order of q and k, and the shared
    experts' down projection over their count (the program sums the
    shared experts; 1/4 of a bfloat16 value is exact, so their sum is
    then their mean)."""
    out = {}
    for name, leaf in finish(cfg, leaves).items():
        if name.endswith("_qkv_W"):
            leaf = half_pairs(cfg, leaf)
        elif name.endswith("_moe_sd"):
            leaf = leaf / cfg["num_shared_experts"]
        out[name] = leaf
    return out


def make_params(cfg: dict, seed: int, dtype=jnp.bfloat16) -> dict:
    """The whole flat dict in the served type and the program's order of
    q and k, a layer to a jitted call (one call for 9 GB would hold
    every leaf's float32 draw at once)."""
    key = weights.seed_key(seed)
    out = weights.make_leaves(key, indexed(cfg, {"tok_emb", "lnf_g"}), dtype)
    for i in range(cfg["num_hidden_layers"]):
        rows = indexed(cfg, layer_names(cfg, i))
        first = rows[0][0]
        leaves = _leaves_from(
            key, first, tuple((index - first, shape, std)
                              for index, _, shape, std in rows), dtype)
        out.update(program_form(cfg, {row[1]: leaf
                                      for row, leaf in zip(rows, leaves)}))
    return out


def n_params(cfg: dict) -> int:
    return sum(math.prod(shape) for _, shape, _ in leaf_table(cfg))
