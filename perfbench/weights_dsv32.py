"""DeepSeek-V3.2-Exp's weights from the seed: the table of leaves of the
program's flat parameter dict for latent attention, the indexer, the
dense and the expert layers and the untied head, drawn by `weights.py`'s
`make_leaves` (each leaf from a key of its own, so that the program's
bfloat16 copy and the reference's float32 one hold the same values).

A configuration is the dict read from `perfbench/configs/<name>.json`:
`n_routed_experts` counts the experts held here (the chip's share),
`router_experts` is the router's published width.

Scales: matrices N(0, 1/fan_in), gains 1, the indexer's LayerNorm bias
0, the router's selection bias N(0, 0.02) (in the checkpoint it is what
load balancing left; zero would leave the path idle), balanced over the
chips' shares (`balance_bias`). Three scales are set, and the
configuration file states them (`assumed.weight_scales`):

- `tok_emb` N(0, 1) and attention's `out_W` a quarter of N(0,
  1/fan_in) in every layer whose output a later layer's indexer reads.
  With random weights the top-2048 choice is made among 32k near-tied
  scores, and it feeds back on itself: one rounding moves a key across
  the threshold, a changed key changes attention's output, and that
  moves the next layer's index keys by more than a rounding. At
  `tok_emb` N(0, 0.02) attention's output is as large as the stream,
  and program and reference part ways for no fault of either (my chip
  run, PR 28: 27% of the selected keys differ); at N(0, 1) with `out_W`
  unscaled the keys that differ still grow from 0.6% in the first layer
  to 9.6% in the fifth; with the quarter they stay at 1.5-1.8%.
- the LAST layer's `out_W` four times N(0, 1/fan_in): no indexer reads
  what it adds, so nothing feeds back. A softmax at this scale (score
  deviation 1.87) weighs some 60 of the 2048 keys, so attention's
  output is 0.13 of a value's size; at the quarter it is 2-3% of the
  stream, at four times a third. Two things need that third. The
  logits then depend on attention's value path (a fault there shows in
  `served_logit_gap`), and on the row and the position: without it the
  next token is all but a function of the token before, greedy walks
  from any start run into the same few hundred tokens after some tens
  of steps, and the experts a run touches, and with them its time,
  follow the seed (my chip runs, PR 28: 3.39-3.66 experts a layer a
  step from seed to seed, tokens per second spread by 0.58%).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from perfbench import weights

BIAS_STD = 0.02
EMBED_STD = 1.0
# attention's output projection, against N(0, 1/fan_in): see above
OUT_SCALE = 0.25
OUT_SCALE_LAST = 4.0
ZERO = 0.0          # a leaf of zeros: `make_leaf` scales its draw by this


def balance_bias(bias, share: int):
    """The drawn selection biases with every chip's share made equally
    popular: within each run of ``share`` consecutive experts the draws
    are replaced, rank for rank, by the ``share`` quantiles of N(0,
    BIAS_STD). A bias of one standard deviation makes an expert 1.4
    times as likely to be among a token's 8; sixteen free draws move a
    share's load, and with it the step's time, by a tenth from seed to
    seed (my chip runs, PR 28: 0.44-0.52 held assignments a token).
    DeepSeek-V3's bias is there to level the load, and its deployment
    levels it over devices too."""
    blocks = bias.astype(jnp.float32).reshape(-1, share)
    ranks = jnp.argsort(jnp.argsort(blocks, axis=-1), axis=-1)
    quantiles = BIAS_STD * jax.scipy.special.ndtri(
        (jnp.arange(share) + 0.5) / share)
    return quantiles[ranks].reshape(bias.shape).astype(bias.dtype)


def finish(cfg: dict, leaves: dict) -> dict:
    """What follows the draws, for the program's copy and the
    reference's alike: the selection biases balanced over the shares."""
    return {name: (balance_bias(leaf, cfg["n_routed_experts"])
                   if name.endswith("moe_router_b") else leaf)
            for name, leaf in leaves.items()}


def is_moe(cfg: dict, i: int) -> bool:
    return i >= cfg["first_k_dense_replace"]


def attention_leaves(cfg: dict, i: int, p: str) -> list:
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    last = i == cfg["num_hidden_layers"] - 1
    out_scale = OUT_SCALE_LAST if last else OUT_SCALE
    qr, kvr = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    nope, rope, v = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                     cfg["v_head_dim"])
    ih, idim = cfg["index_n_heads"], cfg["index_head_dim"]
    return [(p + "qa_W", (d, qr), d ** -0.5),
            (p + "qa_g", (qr,), None),
            (p + "qb_W", (qr, h * (nope + rope)), qr ** -0.5),
            (p + "kva_W", (d, kvr + rope), d ** -0.5),
            (p + "kv_g", (kvr,), None),
            (p + "kvb_W", (kvr, h * (nope + v)), kvr ** -0.5),
            (p + "out_W", (h * v, d), out_scale * (h * v) ** -0.5),
            (p + "iq_W", (qr, ih * idim), qr ** -0.5),
            (p + "ik_W", (d, idim), d ** -0.5),
            (p + "ik_g", (idim,), None),
            (p + "ik_b", (idim,), ZERO),
            (p + "iw_W", (d, ih), d ** -0.5)]


def ffn_leaves(cfg: dict, i: int, p: str) -> list:
    d = cfg["hidden_size"]
    if not is_moe(cfg, i):
        ff = cfg["intermediate_size"]
        return [(p + "ff1_W", (d, ff), d ** -0.5),
                (p + "ff3_W", (d, ff), d ** -0.5),
                (p + "ff2_W", (ff, d), ff ** -0.5)]
    ff, held = cfg["moe_intermediate_size"], cfg["n_routed_experts"]
    sw = cfg["n_shared_experts"] * ff
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
    return (attention_leaves(cfg, i, p) + ffn_leaves(cfg, i, p)
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


@functools.partial(jax.jit, static_argnames=("rows", "dtype"))
def _leaves_from(key, first, rows: tuple, dtype):
    """`weights.make_leaves` for rows of (offset, shape, std) whose
    indices start at the operand ``first``: layers of one shape share
    the compiled program."""
    return [weights.make_leaf(key, first + offset, shape, std, dtype)
            for offset, shape, std in rows]


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
