"""Weights from the seed: one table of leaves, each drawn on the device
from a key of its own, so that the program's copy (bfloat16, one jitted
call) and the reference's (one layer at a time, float32) hold the same
values without either taking anything from the other.

Layout and scales follow the program's flat parameter dict
(`models/transformer.init_transformer`): `tok_emb` N(0, 0.02), the fused
q|k|v matrix, the output projection and SwiGLU's gate (`ff1`) and up
(`ff3`) N(0, 1/d), down (`ff2`) N(0, 1/d_ff), RMSNorm gains 1.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def seed_key(seed: int):
    """A key that keeps every bit of a seed wider than 31 bits
    (`PRNGKey` alone wraps them away)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed % (1 << 31)),
                              seed >> 31)


def layer_leaves(cfg: dict, i: int) -> list:
    d, dff = cfg["hidden_size"], cfg["intermediate_size"]
    hd = d // cfg["num_attention_heads"]
    qkv = (cfg["num_attention_heads"] + 2 * cfg["num_key_value_heads"]) * hd
    p = f"L{i}_"
    return [(p + "qkv_W", (d, qkv), d ** -0.5),
            (p + "out_W", (d, d), d ** -0.5),
            (p + "ff1_W", (d, dff), d ** -0.5),
            (p + "ff3_W", (d, dff), d ** -0.5),
            (p + "ff2_W", (dff, d), dff ** -0.5),
            (p + "ln1_g", (d,), None),
            (p + "ln2_g", (d,), None)]


def leaf_table(cfg: dict) -> list:
    """Every leaf as (name, shape, std); std None is a gain of ones. A
    leaf's position in this list is what its key is folded from."""
    d = cfg["hidden_size"]
    table = [("tok_emb", (cfg["vocab_size"], d), 0.02)]
    for i in range(cfg["num_hidden_layers"]):
        table += layer_leaves(cfg, i)
    return table + [("lnf_g", (d,), None)]


def make_leaf(key, index: int, shape, std, dtype):
    if std is None:
        return jnp.ones(shape, dtype)
    draw = jax.random.normal(jax.random.fold_in(key, index), shape,
                             jnp.float32)
    return (std * draw).astype(dtype)


@functools.partial(jax.jit, static_argnames=("table", "dtype", "via"))
def make_leaves(key, table: tuple, dtype, via=None):
    """Leaves of ``table`` ((index, name, shape, std) rows) in ``dtype``;
    ``via`` rounds through another type first (the reference holds the
    served bfloat16 values in float32)."""
    out = {}
    for index, name, shape, std in table:
        leaf = make_leaf(key, index, shape, std, via or dtype)
        out[name] = leaf.astype(dtype)
    return out


def indexed(cfg: dict, names=None) -> tuple:
    rows = tuple((i, n, s, std) for i, (n, s, std)
                 in enumerate(leaf_table(cfg)))
    if names is not None:
        rows = tuple(r for r in rows if r[1] in names)
    return rows


def make_params(cfg: dict, seed: int, dtype=jnp.bfloat16) -> dict:
    """The whole flat dict in one jitted call, in the served type."""
    return make_leaves(seed_key(seed), indexed(cfg), dtype)


def token_rows(seed: int, index: int, rows: int, length: int,
               vocab: int) -> np.ndarray:
    """Batch ``index`` of the run: (rows, length) ids from the seed. Rows
    all differ (ids are uniform draws of ``length`` >> 1 positions)."""
    rng = np.random.default_rng([int(seed), int(index)])
    return rng.integers(0, vocab, (rows, length), dtype=np.int32)
