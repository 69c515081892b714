"""sarvam-105b's weights from the seed: the table of leaves of the
program's flat parameter dict (latent attention with direct queries
and the qk-norm, the dense and the expert layers, the untied head),
drawn by `weights.py`'s `make_leaves`, each leaf from a key of its own,
so that the program's bfloat16 copy and the reference's float32 one
hold the same values.

A configuration is the dict read from `perfbench/configs/<name>.json`:
`num_experts` counts the experts held here (the chip's share),
`router_experts` is the router's published width.

Scales: matrices N(0, 1/fan_in), gains 1, the router's selection bias
the 32 quantiles of N(0, 0.02) within every share
(`weights_dsv32.balance_bias`). Three scales are set, and the
configuration file states them (`assumed.weight_scales`): a softmax
over 32k keys of random N(0, 1) scores is all but uniform, attention's
output is then 1/180 of a value's size and no fault of the kernel would
move a logit.

- `q_g`, the qk-norm's gain, `Q_GAIN`: a head's score then has the
  deviation `Q_GAIN * m^2` (m^2 = 1.874, YaRN's softmax correction) =
  2.34 and the softmax weighs some hundreds of the 32k keys (median 416
  by 1 / sum p^2; float32 on the CPU, one layer, 32768 keys). At 1.5
  (142 keys) the program's own bfloat16 rounding of a score, through so
  peaked a softmax, read as much as the int8 control (my chip runs, PR
  32: mean gap 0.0069-0.0084 against 0.0129); at 1.25 it reads a seventh
  of it.
- attention's `out_W` `OUT_SCALE` times N(0, 1/fan_in) in every layer:
  attention's output is then a fifth to a half of the stream in every
  layer (nothing here feeds back into a selection, so no layer needs
  less; the reference prints the shares it finds, layer by layer).
- `tok_emb` N(0, 1): the stream the shares are measured against.
"""

from __future__ import annotations

import math

import jax.numpy as jnp

from perfbench import weights
from perfbench.weights_dsv32 import BIAS_STD, _leaves_from, balance_bias

EMBED_STD = 1.0
Q_GAIN = 1.25
OUT_SCALE = 2.5


def is_moe(cfg: dict, i: int) -> bool:
    return i >= cfg["first_k_dense_replace"]


def attention_leaves(cfg: dict, p: str) -> list:
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    kvr = cfg["kv_lora_rank"]
    nope, rope, v = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                     cfg["v_head_dim"])
    qr = cfg.get("q_lora_rank") or 0
    if qr:
        q = [(p + "qa_W", (d, qr), d ** -0.5), (p + "qa_g", (qr,), None),
             (p + "qb_W", (qr, h * (nope + rope)), qr ** -0.5)]
    else:
        q = [(p + "q_W", (d, h * (nope + rope)), d ** -0.5)]
    if cfg["use_qk_norm"]:
        q.append((p + "q_g", (nope + rope,), None))
    return q + [(p + "kva_W", (d, kvr + rope), d ** -0.5),
                (p + "kv_g", (kvr,), None),
                (p + "kvb_W", (kvr, h * (nope + v)), kvr ** -0.5),
                (p + "out_W", (h * v, d), OUT_SCALE * (h * v) ** -0.5)]


def ffn_leaves(cfg: dict, i: int, p: str) -> list:
    d = cfg["hidden_size"]
    if not is_moe(cfg, i):
        ff = cfg["intermediate_size"]
        return [(p + "ff1_W", (d, ff), d ** -0.5),
                (p + "ff3_W", (d, ff), d ** -0.5),
                (p + "ff2_W", (ff, d), ff ** -0.5)]
    ff, held = cfg["moe_intermediate_size"], cfg["num_experts"]
    sw = cfg["num_shared_experts"] * ff
    return [(p + "moe_router_W", (d, cfg["router_experts"]), d ** -0.5),
            (p + "moe_router_b", (cfg["router_experts"],), BIAS_STD),
            (p + "moe_wg", (held, d, ff), d ** -0.5),
            (p + "moe_wu", (held, d, ff), d ** -0.5),
            (p + "moe_wd", (held, ff, d), ff ** -0.5),
            (p + "moe_sg", (d, sw), d ** -0.5),
            (p + "moe_su", (d, sw), d ** -0.5),
            (p + "moe_sd", (sw, d), sw ** -0.5)]


def layer_leaves(cfg: dict, i: int) -> list:
    d, p = cfg["hidden_size"], f"L{i}_"
    return (attention_leaves(cfg, p) + ffn_leaves(cfg, i, p)
            + [(p + "ln1_g", (d,), None), (p + "ln2_g", (d,), None)])


def leaf_table(cfg: dict) -> list:
    """Every leaf as (name, shape, std); its position is what its key is
    folded from."""
    d, vocab = cfg["hidden_size"], cfg["vocab_size"]
    table = [("tok_emb", (vocab, d), EMBED_STD)]
    for i in range(cfg["num_hidden_layers"]):
        table += layer_leaves(cfg, i)
    return table + [("lnf_g", (d,), None), ("head_W", (d, vocab), d ** -0.5)]


def indexed(cfg: dict, names=None) -> tuple:
    rows = tuple((i, n, s, std) for i, (n, s, std)
                 in enumerate(leaf_table(cfg)))
    if names is not None:
        rows = tuple(r for r in rows if r[1] in names)
    return rows


def layer_names(cfg: dict, i: int) -> set:
    return {name for name, _, _ in layer_leaves(cfg, i)}


def level_shares(router_w, share: int):
    """The router's columns with every run of ``share`` consecutive
    experts (a chip's share) given the same mean column. Hidden states
    have a part that all tokens share, and what it adds to an expert's
    score is a constant of the seed: with free draws one chip's 32
    experts together are 2% more or less popular than another's from
    seed to seed (1.96-2.09 held assignments a token), and with the
    expert layer 45% of a step the step's time follows (my chip runs, PR
    32: `serve_tokens_per_s` spread by 0.63% over six seeds). A
    deployment levels the load over its chips, as `balance_bias` says;
    this is the same levelling for the part of it that the weights
    carry. The served bfloat16 values, in the leaf's type."""
    d, e = router_w.shape
    w = router_w.astype(jnp.float32).reshape(d, e // share, share)
    w = w - w.mean(axis=2, keepdims=True) + w.mean(axis=(1, 2), keepdims=True)
    return w.reshape(d, e).astype(jnp.bfloat16).astype(router_w.dtype)


def finish(cfg: dict, leaves: dict) -> dict:
    """What follows the draws, for the program's copy and the
    reference's alike: the qk-norm's gain set, the selection biases
    balanced over the shares and the router's columns levelled over
    them."""
    out = {}
    for name, leaf in leaves.items():
        if name.endswith("_q_g"):
            leaf = leaf * Q_GAIN
        elif name.endswith("moe_router_b"):
            leaf = balance_bias(leaf, cfg["num_experts"])
        elif name.endswith("moe_router_W"):
            leaf = level_shares(leaf, cfg["num_experts"])
        out[name] = leaf
    return out


def make_params(cfg: dict, seed: int, dtype=jnp.bfloat16) -> dict:
    """The whole flat dict in the served type, a layer to a jitted call
    (one call for 9 GB would hold every leaf's float32 draw at once)."""
    key = weights.seed_key(seed)
    out = weights.make_leaves(
        key, indexed(cfg, {"tok_emb", "lnf_g", "head_W"}), dtype)
    for i in range(cfg["num_hidden_layers"]):
        rows = indexed(cfg, layer_names(cfg, i))
        first = rows[0][0]
        leaves = _leaves_from(
            key, first, tuple((index - first, shape, std)
                              for index, _, shape, std in rows), dtype)
        out.update({row[1]: leaf for row, leaf in zip(rows, leaves)})
    return finish(cfg, out)


def n_params(cfg: dict) -> int:
    return sum(math.prod(shape) for _, shape, _ in leaf_table(cfg))
