"""Phi-4-mini-flash-reasoning's weights from the seed: the table of
leaves of the program's flat parameter dict (a SambaY stack: Mamba,
window and full differential attention, cross-attention over the one
shared cache, gated memory units; LayerNorm, SwiGLU, the tied head),
drawn by `weights.py`'s `make_leaves`, each leaf from a key of its own,
so that the program's bfloat16 copy and the reference's float32 one
hold the same values. It imports nothing of the program.

A configuration is the dict read from `perfbench/configs/<name>.json`;
`layer_kind` says what mixer a layer has, from `mb_per_layer`,
`num_hidden_layers` and nothing else.

Scales: matrices N(0, 1/fan_in), norm gains 1 and biases 0, projection
biases N(0, 0.02), `tok_emb` N(0, 0.02) (tied: the logits then have a
deviation near 1), the four `lam` vectors N(0, 0.1). Mamba's own
initialisation: `A_log = log(1..16)` in every channel, `D` 1, the
step's bias the inverse softplus of a log-uniform draw in [0.001, 0.1]
(the state then remembers over tens to thousands of positions, and a
fault that loses it shows); these three leaves are float32 in the
program too. Set, and stated in the configuration file
(`assumed.weight_scales`):

- an attention layer's `out_W` (window, full and cross alike)
  `ATTN_GAIN / (1 - lam0)` times N(0, 1/fan_in), `lam0` the layer's own:
  differential attention ends in a norm times `(1 - lam0)`, 0.65 in
  layer 1 and 0.2 from layer 17 on, and with plain draws the seven cross
  layers and the full one would each add a twentieth of the stream: a
  fault in what they read would move no logit. So every attention
  layer's output has the size `ATTN_GAIN`. The state-space and memory
  units' `out_W` `OUT_GAIN` times N(0, 1/fan_in) (the reference prints
  each block's output against the stream, layer by layer).

NOT set: the query and key projections stay N(0, 1/fan_in), a pair's
score over random keys has the deviation 1. A gain of 1.4 on both
(deviation 1.96, a softmax over 16k keys that weighs 350 of them) was
tried first, so that a fault of the kernel would show: through 32
layers it multiplied the program's own bfloat16 rounding until the
served tokens lay 0.21 below the reference's best in the mean (my chip
run, PR 34), while the planted faults read the same whatever the gain
(the norm after `a1 - lam a2` makes the softmaxes' fluctuations the
signal at any sharpness): PERF.md section 6 has the sweep.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from perfbench import weights
from perfbench.weights_dsv32 import _leaves_from

EMBED_STD = 0.02
BIAS_STD = 0.02
LAM_STD = 0.1
ATTN_GAIN = 1.0
OUT_GAIN = 2.0
DT_MIN, DT_MAX = 1e-3, 1e-1
FLOAT32 = ("_dt_b", "_A_log", "_D")


def layer_kind(cfg: dict, i: int) -> str:
    """ "ssm", "swa", "full", "cross" or "gmu": the self-decoder is the
    first half (state space on every `mb_per_layer`-th layer, window
    attention between), then one more state-space layer and the one
    full-attention layer; the cross-decoder alternates gated memory
    units and cross-attention."""
    half = cfg["num_hidden_layers"] // 2
    if i <= half:
        return "ssm" if i % cfg["mb_per_layer"] == 0 else "swa"
    if i == half + 1:
        return "full"
    return "gmu" if i % cfg["mb_per_layer"] == 0 else "cross"


def memory_layer(cfg: dict) -> int:
    """The state-space layer whose scan output the memory units gate."""
    return cfg["num_hidden_layers"] // 2


def lam0(i: int) -> float:
    """Differential attention's fixed part of `lam` in layer ``i``."""
    return 0.8 - 0.6 * math.exp(-0.3 * i)


def sizes(cfg: dict) -> dict:
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    return {"d": d, "hd": d // h, "h": h, "hkv": cfg["num_key_value_heads"],
            "e": cfg["mamba_expand"] * d, "n": cfg["mamba_d_state"],
            "taps": cfg["mamba_d_conv"], "r": cfg["mamba_dt_rank"]}


def mixer_leaves(cfg: dict, i: int, p: str) -> list:
    z = sizes(cfg)
    d, hd, e = z["d"], z["hd"], z["e"]
    kind = layer_kind(cfg, i)
    fan = d ** -0.5
    if kind == "ssm":
        return [(p + "in_W", (d, 2 * e), fan),
                (p + "conv_W", (z["taps"], e), z["taps"] ** -0.5),
                (p + "conv_b", (e,), BIAS_STD),
                (p + "x_W", (e, z["r"] + 2 * z["n"]), e ** -0.5),
                (p + "dt_W", (z["r"], e), z["r"] ** -0.5),
                (p + "dt_b", (e,), 1.0),        # `finish` makes the bias of it
                (p + "A_log", (z["n"], e), None),
                (p + "D", (e,), None),
                (p + "out_W", (e, d), OUT_GAIN * e ** -0.5)]
    if kind == "gmu":
        return [(p + "in_W", (d, e), fan),
                (p + "out_W", (e, d), OUT_GAIN * e ** -0.5)]
    width = z["h"] * hd
    pair = [(p + "lam", (4, hd), LAM_STD), (p + "sub_g", (2 * hd,), None),
            # `finish` divides by the layer's (1 - lam0): a table that is
            # the same for every layer of a kind compiles once a kind
            (p + "out_W", (width, d), ATTN_GAIN * width ** -0.5),
            (p + "out_b", (d,), BIAS_STD)]
    if kind == "cross":
        return [(p + "q_W", (d, width), fan),
                (p + "q_b", (width,), BIAS_STD)] + pair
    cols = width + 2 * z["hkv"] * hd
    return [(p + "qkv_W", (d, cols), fan),
            (p + "qkv_b", (cols,), BIAS_STD)] + pair


def layer_leaves(cfg: dict, i: int) -> list:
    d, ff, p = cfg["hidden_size"], cfg["intermediate_size"], f"L{i}_"
    norms = [(p + f"{ln}_{gb}", (d,), None if gb == "g" else 0.0)
             for ln in ("ln1", "ln2") for gb in ("g", "b")]
    return (mixer_leaves(cfg, i, p)
            + [(p + "ff1_W", (d, ff), d ** -0.5),
               (p + "ff3_W", (d, ff), d ** -0.5),
               (p + "ff2_W", (ff, d), ff ** -0.5)] + norms)


def leaf_table(cfg: dict) -> list:
    """Every leaf as (name, shape, std); std None is ones, 0.0 zeros. A
    leaf's position is what its key is folded from."""
    d = cfg["hidden_size"]
    table = [("tok_emb", (cfg["vocab_size"], d), EMBED_STD)]
    for i in range(cfg["num_hidden_layers"]):
        table += layer_leaves(cfg, i)
    return table + [("lnf_g", (d,), None), ("lnf_b", (d,), 0.0)]


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
    reference's alike (both hold the served bfloat16 draws): an
    attention layer's `out_W` gets its `1 / (1 - lam0)`, Mamba's `A_log`
    and the step's bias are made, and the three float32 leaves stay
    float32."""
    z = sizes(cfg)
    out = {}
    for name, leaf in leaves.items():
        layer = int(name[1:name.index("_")]) if name[0] == "L" else -1
        if name.endswith("_out_W") and layer_kind(cfg, layer) in (
                "swa", "full", "cross"):
            leaf = (leaf.astype(jnp.float32) / (1.0 - lam0(layer))).astype(
                jnp.bfloat16).astype(leaf.dtype)
        elif name.endswith("_dt_b"):
            u = jax.scipy.stats.norm.cdf(leaf.astype(jnp.float32))
            dt = jnp.exp(math.log(DT_MIN)
                         + u * (math.log(DT_MAX) - math.log(DT_MIN)))
            leaf = dt + jnp.log(-jnp.expm1(-dt))
        elif name.endswith("_A_log"):
            leaf = jnp.broadcast_to(jnp.log(jnp.arange(
                1, z["n"] + 1, dtype=jnp.float32))[:, None], leaf.shape)
        if name.endswith(FLOAT32):
            leaf = leaf.astype(jnp.float32)
        out[name] = leaf
    return out


def make_params(cfg: dict, seed: int, dtype=jnp.bfloat16) -> dict:
    """The whole flat dict in the served type, a layer to a jitted call
    (one call for 7.7 GB would hold every leaf's float32 draw at
    once)."""
    key = weights.seed_key(seed)
    out = weights.make_leaves(
        key, indexed(cfg, {"tok_emb", "lnf_g", "lnf_b"}), dtype)
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


def cache_bytes(cfg: dict, batch: int, total: int, itemsize: int = 2) -> dict:
    """What the sessions' caches hold, by kind, from the configuration's
    shapes: bytes that grow with the position (the one full-attention
    cache), rolling window bytes, state bytes (the convolution's tail in
    the served type, the state in float32)."""
    z = sizes(cfg)
    kinds = [layer_kind(cfg, i) for i in range(cfg["num_hidden_layers"])]
    row = 2 * z["hkv"] * z["hd"] * itemsize
    window = min(cfg["sliding_window"], total)
    state = z["e"] * ((z["taps"] - 1) * itemsize + z["n"] * 4)
    return {"growing": batch * total * row * kinds.count("full"),
            "rolling": batch * window * row * kinds.count("swa"),
            "state": batch * state * kinds.count("ssm")}
