"""The plain reference: Mistral's published forward pass, its loss and
gradients, and Adam, in straightforward `jax.numpy` and float32 with
every matmul at `highest` precision. No kernel, no cache, no batching
tricks; it imports nothing of the program and takes no array from it.
The weights come from the seed through `weights.py` (the served
bfloat16 values, held in float32).

Departures from the published model, the same as the configuration
files state: the output head is tied to the embedding. Attention runs
one (row, key-value head) group at a time and the training loss one row
at a time, each rematerialised in the backward pass, so that float32 at
the timed sizes fits one chip; neither changes a number.

`mode` computes the weight matmuls in a lower precision (operands
quantised and dequantised, straight-through gradient): that is the
control, the reference put in the program's place.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from . import weights

HI = lax.Precision.HIGHEST
LAYER_KEYS = ("qkv_W", "out_W", "ff1_W", "ff3_W", "ff2_W", "ln1_g", "ln2_g")


@dataclasses.dataclass(frozen=True)
class Dims:
    d: int
    heads: int
    kv_heads: int
    d_ff: int
    vocab: int
    layers: int
    window: int
    theta: float
    eps: float
    attn_blocks: int = 1    # > 1 plants a fault, see `_attention`

    @staticmethod
    def of(cfg: dict) -> "Dims":
        return Dims(cfg["hidden_size"], cfg["num_attention_heads"],
                    cfg["num_key_value_heads"], cfg["intermediate_size"],
                    cfg["vocab_size"], cfg["num_hidden_layers"],
                    cfg.get("sliding_window") or 0,
                    float(cfg["rope_theta"]), float(cfg["rms_norm_eps"]))


def _lower(x, axis: int, mode):
    """``x`` as the lower precision ``mode`` holds it, scaled along
    ``axis``; the gradient passes straight through."""
    if mode is None:
        return x
    if mode == "bf16":
        q = x.astype(jnp.bfloat16).astype(jnp.float32)
    else:
        top = {"int8": 127.0, "fp8": 448.0, "int4": 7.0}[mode]
        s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / top
        s = jnp.where(s == 0, 1.0, s)
        if mode == "fp8":
            q = (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s
        else:
            q = jnp.round(x / s) * s
    return x + lax.stop_gradient(q - x)


def _mm(x, w, mode):
    return jnp.matmul(_lower(x, -1, mode), _lower(w, 0, mode), precision=HI)


def _rms(x, g, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def _rope(x, pos, theta):
    """Rotate-half convention, as the published implementation."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(q, k, v, window: int, blocks: int = 1):
    """Causal softmax attention over at most the last ``window`` keys.
    q (B, L, H, hd); k, v (B, L, Hkv, hd); query head h reads key-value
    head h // (H / Hkv). ``blocks`` > 1 plants the fault of a sequence
    sharded over that many chips whose exchange of keys is left out: a
    query sees only the keys of its own block."""
    b, l, h, hd = q.shape
    hkv = k.shape[2]
    g = h // hkv
    qg = q.reshape(b, l, hkv, g, hd).transpose(0, 2, 3, 1, 4)
    qg = qg.reshape(b * hkv, g, l, hd)
    kg = k.transpose(0, 2, 1, 3).reshape(b * hkv, l, hd)
    vg = v.transpose(0, 2, 1, 3).reshape(b * hkv, l, hd)
    i, j = jnp.arange(l)[:, None], jnp.arange(l)[None, :]
    seen = j <= i
    if window:
        seen = seen & (i - j < window)
    if blocks > 1:
        seen = seen & (i // (l // blocks) == j // (l // blocks))

    def group(qkv):
        qq, kk, vv = qkv
        s = jnp.einsum("gqd,kd->gqk", qq, kk, precision=HI) * hd ** -0.5
        p = jax.nn.softmax(jnp.where(seen, s, -1e30), axis=-1)
        return jnp.einsum("gqk,kd->gqd", p, vv, precision=HI)

    out = lax.map(jax.checkpoint(group), (qg, kg, vg))
    out = out.reshape(b, hkv, g, l, hd).transpose(0, 3, 1, 2, 4)
    return out.reshape(b, l, h * hd)


def layer(w: dict, x, pos, dims: Dims, mode=None):
    b, l, d = x.shape
    h, hkv = dims.heads, dims.kv_heads
    hd = d // h
    qkv = _mm(_rms(x, w["ln1_g"], dims.eps), w["qkv_W"], mode)
    q = qkv[..., :h * hd].reshape(b, l, h, hd)
    k = qkv[..., h * hd:(h + hkv) * hd].reshape(b, l, hkv, hd)
    v = qkv[..., (h + hkv) * hd:].reshape(b, l, hkv, hd)
    a = _attention(_rope(q, pos, dims.theta), _rope(k, pos, dims.theta), v,
                   dims.window, dims.attn_blocks)
    x = x + _mm(a, w["out_W"], mode)
    y = _rms(x, w["ln2_g"], dims.eps)
    gated = jax.nn.silu(_mm(y, w["ff1_W"], mode)) * _mm(y, w["ff3_W"], mode)
    return x + _mm(gated, w["ff2_W"], mode)


def _logits(x, lnf_g, tok_emb, dims: Dims, mode):
    return _mm(_rms(x, lnf_g, dims.eps), tok_emb.T, mode)


def row_loss(params: dict, tokens, targets, dims: Dims, mode=None):
    """Summed next-token negative log-likelihood of one row of ids."""
    pos = jnp.arange(tokens.shape[0])
    x = params["tok_emb"][tokens][None]
    for i in range(dims.layers):
        w = {k: params[f"L{i}_{k}"] for k in LAYER_KEYS}
        x = layer(w, x, pos, dims, mode)
    logp = jax.nn.log_softmax(
        _logits(x[0], params["lnf_g"], params["tok_emb"], dims, mode), axis=-1)
    return -jnp.sum(jnp.take_along_axis(logp, targets[:, None], -1))


def loss(params: dict, tokens, targets, dims: Dims, mode=None):
    """Mean next-token negative log-likelihood over every position of
    every row; one row at a time, each rematerialised in the backward
    pass, so that float32 at the timed sizes fits whatever the batch."""
    one = jax.checkpoint(functools.partial(row_loss, dims=dims, mode=mode))
    rows = lax.map(lambda row: one(params, *row), (tokens, targets))
    return jnp.sum(rows) / tokens.size


@functools.partial(jax.jit, static_argnames=("dims", "mode"))
def loss_and_grads(params, tokens, targets, dims: Dims, mode=None):
    return jax.value_and_grad(loss)(params, tokens, targets, dims, mode)


@jax.jit
def leaf_norms(tree: dict) -> dict:
    return {k: jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32))))
            for k, v in tree.items()}


SAMPLE_EVERY = 16


@jax.jit
def sample_rows(tree: dict) -> dict:
    """Every 16th row of each matrix, vectors whole, in float32: enough of
    a gradient to measure its noise leaf by leaf, small enough to keep on
    the host while the window runs."""
    return {k: (v[::SAMPLE_EVERY] if v.ndim > 1 else v).astype(jnp.float32)
            for k, v in tree.items()}


@functools.partial(jax.jit, donate_argnums=(0, 1, 2))
def _adam_leaf(p, m, v, g, t, lr, b1, b2, eps):
    """Adam (Kingma & Ba: bias-corrected moments, epsilon outside the
    root) on one leaf, in place."""
    m = b1 * m + (1 - b1) * g
    v = b2 * v + (1 - b2) * g * g
    step = (m / (1 - b1 ** t)) / (jnp.sqrt(v / (1 - b2 ** t)) + eps)
    return p - lr * step, m, v


@functools.partial(jax.jit, static_argnames=("row",))
def _change_norm(p, key, row: tuple):
    index, _, shape, std = row
    start = weights.make_leaf(key, index, shape, std, jnp.bfloat16)
    return jnp.sqrt(jnp.sum(jnp.square(p - start.astype(jnp.float32))))


def train_readings(cfg: dict, seed: int, batches: list, adam: dict,
                   mode=None, half_batch=False, attn_blocks=1) -> dict:
    """Follow ``len(batches)`` training steps from the seed and return
    each step's loss, of the first gradient every leaf's norm and a
    sample of its rows, and the norm of every leaf's change over all the
    steps. ``batches`` are (tokens, targets) arrays as the window feeds
    them. ``half_batch`` plants the fault of a step that leaves half of
    its rows (rounded down) out and takes the mean over the rest, which
    is also what a data-parallel pair gives whose gradients are never
    summed; ``attn_blocks`` that of `_attention`."""
    dims = dataclasses.replace(Dims.of(cfg), attn_blocks=attn_blocks)
    key, table = weights.seed_key(seed), weights.indexed(cfg)
    params = weights.make_leaves(key, table, jnp.float32, via=jnp.bfloat16)
    m = jax.tree.map(jnp.zeros_like, params)
    v = jax.tree.map(jnp.zeros_like, params)
    out = {"loss": [], "delta": {}}
    for n, (tokens, targets) in enumerate(batches):
        if half_batch:
            keep = (tokens.shape[0] + 1) // 2
            tokens, targets = tokens[:keep], targets[:keep]
        value, grads = loss_and_grads(params, jnp.asarray(tokens),
                                      jnp.asarray(targets), dims, mode)
        out["loss"].append(float(value))
        if n == 0:
            out["grad1"] = {k: float(x) for k, x in leaf_norms(grads).items()}
            out["sample1"] = {k: np.asarray(x)
                              for k, x in sample_rows(grads).items()}
        for name in list(grads):
            params[name], m[name], v[name] = _adam_leaf(
                params[name], m[name], v[name], grads.pop(name),
                jnp.float32(n + 1), adam["lr"], adam["b1"], adam["b2"],
                adam["eps"])
    for row in table:
        out["delta"][row[1]] = float(_change_norm(params[row[1]], key, row))
    return out


@functools.partial(jax.jit, static_argnames=("dims", "mode"))
def _layer_jit(w, x, pos, dims, mode):
    return layer(w, x, pos, dims, mode)


@functools.partial(jax.jit, static_argnames=("dims", "mode", "first"))
def _tail_logits(x, lnf_g, tok_emb, dims, mode, first):
    return _logits(x[:, first:], lnf_g, tok_emb, dims, mode)


def decode_logit_gaps(cfg: dict, seed: int, tokens: np.ndarray,
                      prompt_len: int, modes=()) -> dict:
    """One full forward pass over each served row (prompt and served
    tokens, (R, T) ids), a layer's weights at a time. Returns under
    "served" the gap, at every generated position, by which the served
    token's logit lies below the reference's best, and under each of
    ``modes`` the same gap for the token that this lower precision puts
    first at that position."""
    dims = Dims.of(cfg)
    key = weights.seed_key(seed)
    fed = jnp.asarray(tokens[:, :-1])
    served = jnp.asarray(tokens[:, prompt_len:])
    pos = jnp.arange(fed.shape[1])

    def leaves(names):
        return weights.make_leaves(key, weights.indexed(cfg, names),
                                   jnp.float32, via=jnp.bfloat16)

    emb = leaves({"tok_emb", "lnf_g"})
    streams = {m: emb["tok_emb"][fed] for m in (None, *modes)}
    for i in range(dims.layers):
        w = leaves({f"L{i}_{k}" for k in LAYER_KEYS})
        w = {k[len(f"L{i}_"):]: v for k, v in w.items()}
        for m in streams:
            streams[m] = _layer_jit(w, streams[m], pos, dims, m)
    logits = {m: _tail_logits(x, emb["lnf_g"], emb["tok_emb"], dims, m,
                              prompt_len - 1) for m, x in streams.items()}
    ref = logits[None]
    best = jnp.max(ref, axis=-1)

    def gap(chosen):
        return np.asarray(
            best - jnp.take_along_axis(ref, chosen[..., None], -1)[..., 0])

    out = {"served": gap(served)}
    for m in modes:
        out[m] = gap(jnp.argmax(logits[m], axis=-1))
    return out
