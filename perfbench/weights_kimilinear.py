"""Kimi-Linear-48B-A3B-Instruct's weights from the seed: the table of
leaves of the program's flat parameter dict (Kimi Delta Attention in the
layers `linear_attn_config.kda_layers` names, latent attention with
direct queries in the others, the dense first layer and the expert
layers, the untied head), drawn by `weights.py`'s `make_leaves`, each
leaf from a key of its own, so that the program's bfloat16 copy and the
reference's float32 one hold the same values.

A configuration is the dict read from `perfbench/configs/<name>.json`:
`num_experts` counts the experts held here (the chip's share),
`router_experts` is the router's published width; layer lists are
1-indexed, as published.

Scales: matrices N(0, 1/fan_in), gains 1, the convolution's taps N(0,
1/taps), the router's selection bias the 16 quantiles of N(0, 0.02)
within every share (`weights_dsv32.balance_bias`), the router's columns
levelled over the shares (`weights_sarvam.level_shares`). Two leaves of
a KDA layer are the published initialisation, not normal draws, made in
`finish` from a normal draw of their own (the same bfloat16 draw in both
copies, so the same float32 values): `A_log` = log A, A ~ U(1, 16) a
head, and `dt_b` the inverse softplus of dt ~ logU(1e-3, 0.1) a key
channel. Three scales are set, and the configuration file states them
(`assumed.weight_scales`):

- `tok_emb` N(0, 1): the stream the shares are measured against.
- the latent layers' `q_W` `Q_SCALE` times N(0, 1/d): a head's score
  over random keys then has the deviation `Q_SCALE` and the softmax over
  4k keys weighs some hundreds of them, not all alike (at 1 attention's
  output is near the mean of the values and no fault of it would move a
  logit).
- the mixers' `out_W` `OUT_SCALE[kind]` times N(0, 1/fan_in). Random
  KDA layers share a direction from token to token and from session to
  session: silu after the convolution gives every value a positive mean,
  so a head's normed output is nearly one vector; latent attention
  averages over many keys and hands that on. At 1 and 3 x the 32
  sessions' router inputs came out alike and a step touched 4.3 of 16
  held experts where uniform routing touches 10.2 (on the chip);
  at 0.5 and 1 x the sessions' last positions stay within a cosine of
  0.085 of each other through the 27 layers (float32 on the CPU, 32
  contexts of 512), a step touched 8.8-9.0 on the chip, and
  KDA's output is a quarter to a fourteenth of the stream, the latent
  layers' a sixth to a tenth (the reference prints the shares it finds,
  layer by layer).

The KDA layers' `out_W` is then made blind to the mean that a head's
output has over tokens (`blind_to_value_means`). silu after the
convolution gives value channel j the mean E[silu(z_j)], z_j ~ N(0,
s_j^2) with s_j^2 the channel's projection column and taps squared and
summed; the rule hands it on, so a head's normed output carries that
vector, and `out_W` would map it to one direction of the stream that
every token of every session shares. What that direction adds to each
expert's router score is a constant of the seed: within the held share
some experts are chosen more often than others, a step touches fewer
of them, and how many fewer moves from seed to seed (8.80-8.96 of 16 on
the chip, and the step's time with it). Each head's 128 rows of `out_W`
lose their part along that head's vector: 32 of its 4,096 input
directions. On the CPU (float32, 32 contexts of 192, the first 8
layers) the mixer's mean over tokens falls from 0.64 of its size to
0.07, the sessions' router inputs from a mean cosine of 0.04-0.06 to
0.000, and 9.1 held experts touched a step become 9.7 (uniform routing
with the selection biases: 9.8); on the chip seven seeds touched
9.79-9.88, and their runs' rates spread by 0.22% (PERF.md section 6).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from perfbench import weights
from perfbench.weights_dsv32 import BIAS_STD, _leaves_from, balance_bias
from perfbench.weights_sarvam import level_shares

EMBED_STD = 1.0
Q_SCALE = 2.0
OUT_SCALE = {"kda": 0.5, "latent": 1.0}


def kind(cfg: dict, i: int) -> str:
    """Layer ``i``'s (0-based) mixer: `kda` or `latent`."""
    la = cfg["linear_attn_config"]
    return "kda" if i + 1 in la["kda_layers"] else "latent"


def is_moe(cfg: dict, i: int) -> bool:
    return i >= cfg["first_k_dense_replace"]


def kda_dims(cfg: dict) -> tuple:
    """(heads, head width, taps) of the KDA layers."""
    la = cfg["linear_attn_config"]
    return la["num_heads"], la["head_dim"], la["short_conv_kernel_size"]


def kda_leaves(cfg: dict, p: str) -> list:
    d = cfg["hidden_size"]
    h, dk, taps = kda_dims(cfg)
    width = h * dk
    return [(p + "qkv_W", (d, 3 * width), d ** -0.5),
            (p + "conv_W", (taps, 3 * width), taps ** -0.5),
            (p + "fa_W", (d, dk), d ** -0.5),
            (p + "fb_W", (dk, width), dk ** -0.5),
            (p + "dt_b", (width,), 1.0),
            (p + "A_log", (h,), 1.0),
            (p + "b_W", (d, h), d ** -0.5),
            (p + "ga_W", (d, dk), d ** -0.5),
            (p + "gb_W", (dk, width), dk ** -0.5),
            (p + "o_g", (dk,), None),
            (p + "out_W", (width, d), OUT_SCALE["kda"] * width ** -0.5)]


def latent_leaves(cfg: dict, p: str) -> list:
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    kvr = cfg["kv_lora_rank"]
    nope, rope, v = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                     cfg["v_head_dim"])
    return [(p + "q_W", (d, h * (nope + rope)), Q_SCALE * d ** -0.5),
            (p + "kva_W", (d, kvr + rope), d ** -0.5),
            (p + "kv_g", (kvr,), None),
            (p + "kvb_W", (kvr, h * (nope + v)), kvr ** -0.5),
            (p + "out_W", (h * v, d), OUT_SCALE["latent"] * (h * v) ** -0.5)]


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
    mixer = (kda_leaves(cfg, p) if kind(cfg, i) == "kda"
             else latent_leaves(cfg, p))
    return (mixer + ffn_leaves(cfg, i, p)
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


def _uniform(draw):
    """A N(0, 1) draw as U(0, 1), in float32."""
    return jax.scipy.special.ndtr(draw.astype(jnp.float32))


def value_means(qkv_w, conv_w, width: int):
    """E[silu(z_j)] of each value channel j of a KDA layer, z_j ~ N(0,
    s_j^2) with s_j^2 = |qkv_W[:, j]|^2 |conv_W[:, j]|^2: the channel's
    input after the convolution, for a normed layer input of unit
    elements. Elementwise products and sums in float32 only (no matmul),
    so that the program's copy and the reference's, which is drawn under
    `highest` matmul precision, come out the same."""
    v = slice(2 * width, 3 * width)
    s2 = (jnp.sum(jnp.square(qkv_w[:, v].astype(jnp.float32)), 0)
          * jnp.sum(jnp.square(conv_w[:, v].astype(jnp.float32)), 0))
    nodes, w = np.polynomial.hermite_e.hermegauss(32)
    z = jnp.sqrt(s2)[:, None] * jnp.asarray(nodes, jnp.float32)[None]
    return jnp.sum(jax.nn.silu(z) * jnp.asarray(w / w.sum(), jnp.float32),
                   axis=1)


def blind_to_value_means(out_w, qkv_w, conv_w, heads: int, dk: int):
    """A KDA layer's `out_W` (H dk, d) with each head's dk rows made
    orthogonal to that head's `value_means`: a mean the head's output
    carries over tokens maps to nothing. In float32 by elementwise
    products and sums, the served bfloat16 values, in the leaf's
    type."""
    mu = value_means(qkv_w, conv_w, heads * dk).reshape(heads, dk)
    u = mu * lax.rsqrt(jnp.sum(mu * mu, axis=1, keepdims=True))
    w = out_w.astype(jnp.float32).reshape(heads, dk, -1)
    along = jnp.sum(u[:, :, None] * w, axis=1)
    w = w - u[:, :, None] * along[:, None, :]
    return w.reshape(out_w.shape).astype(jnp.bfloat16).astype(out_w.dtype)


def _layer_of(name: str) -> int:
    """The layer (0-based) of a leaf named `L<i>_...`."""
    return int(name[1:name.index("_")])


def finish(cfg: dict, leaves: dict) -> dict:
    """What follows the draws, for the program's copy and the
    reference's alike: the decays' initialisation made from their draws
    (float32 leaves), the KDA layers' `out_W` blind to their values'
    means, the selection biases balanced over the shares and the
    router's columns levelled over them."""
    heads, dk, _ = kda_dims(cfg)
    out = {}
    for name, leaf in leaves.items():
        if name.endswith("_A_log"):
            leaf = jnp.log(1.0 + 15.0 * _uniform(leaf))
        elif name.endswith("_dt_b"):
            dt = jnp.exp(math.log(1e-3) + _uniform(leaf)
                         * (math.log(1e-1) - math.log(1e-3)))
            leaf = dt + jnp.log(-jnp.expm1(-dt))
        elif name.endswith("moe_router_b"):
            leaf = balance_bias(leaf, cfg["num_experts"])
        elif name.endswith("moe_router_W"):
            leaf = level_shares(leaf, cfg["num_experts"])
        elif name.endswith("_out_W") and kind(cfg, _layer_of(name)) == "kda":
            p = name[:-len("out_W")]
            leaf = blind_to_value_means(leaf, leaves[p + "qkv_W"],
                                        leaves[p + "conv_W"], heads, dk)
        out[name] = leaf
    return out


def make_params(cfg: dict, seed: int, dtype=jnp.bfloat16) -> dict:
    """The whole flat dict in the served type (the decays' leaves in
    float32), a layer to a jitted call (one call for 8.6 GB would hold
    every leaf's float32 draw at once)."""
    key = weights.seed_key(seed)
    out = weights.make_leaves(
        key, indexed(cfg, {"tok_emb", "lnf_g", "head_W"}), dtype)
    for i in range(cfg["num_hidden_layers"]):
        rows = indexed(cfg, layer_names(cfg, i))
        first = rows[0][0]
        leaves = _leaves_from(
            key, first, tuple((index - first, shape, std)
                              for index, _, shape, std in rows), dtype)
        out.update(finish(cfg, {row[1]: leaf
                                for row, leaf in zip(rows, leaves)}))
    return out


def n_params(cfg: dict) -> int:
    return sum(math.prod(shape) for _, shape, _ in leaf_table(cfg))
