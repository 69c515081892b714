"""The plain reference for command-a-plus-05-2026 (`model_type:
cohere2_moe`): its forward pass in straightforward `jax.numpy` and
float32 with every matmul at `highest` precision. No kernel, no cache,
no batching of sessions: a query attends every key it may see, a block
of queries at a time, a kv head's group of query heads at a time. It
imports nothing of the program and takes no array from it; the weights
come from the seed through `weights_cmdaplus.py`'s table, a group of a
layer's leaves at a time (the served bfloat16 values, held in float32),
in the checkpoint's own order of q and k.

    h = LN(x) = (x - mean x) / sqrt(var x + eps) * g        (no bias)
    x' = x + Attn_i(h) + MoE(h)                             (parallel block)
    Attn_i: 128 query heads over 8 kv heads of 128; in a sliding_attention
        layer q and k turned by GPT-J's interleaved rope (pairs 2j, 2j + 1
        by theta^(-2j/128)) and a query sees the 4096 positions up to its
        own; in the full_attention layer no positional encoding, every
        position up to its own
    MoE(h) = sum over the top 8 of s (s_e / sum of the 8 s) E_e(h)
             + 1/4 sum_j S_j(h),  s = sigmoid(h W_r) over 128 experts
    logits = LN(x) tok_emb^T

The share is the configuration's: the router runs over
`router_experts`, the experts `[first_expert_held, first_expert_held +
num_experts)` are computed and what the others would add is left out;
the four shared experts are whole; the vocabulary is the slice.

Two passes, as for the latent models. `context_pass` runs the forward
over one context and keeps, for every layer, what its tokens are to a
later query (keys, turned where the layer turns them, and values).
`tails_pass` runs tails that go on from that context, each seeing the
context and itself. A tails pass can be told which experts its tokens
go to (`forced`: the program's own choice), so that a choice between
near-tied scores is compared as a choice (`routing_miss`) and the
logits under the same one.

`mode` lowers the precision of every weight matmul of a pass (the
control); `fault` plants in a tails pass what a wrong program would do,
over the sound context: `rope_on_full` (the full layer turned as the
window layers are, its cached keys too), `sequential` (the block run
as x + Attn(LN x), then + MoE(LN of that)), `shared_sum` (the shared
experts summed, not averaged).
"""

from __future__ import annotations

import dataclasses
import functools
import sys

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from . import weights, weights_cmdaplus
from .reference_dsv32 import (HI, QUERY_BLOCK, _mm, _padded, _visible,
                              logit_gaps, routing_miss)

__all__ = ["Dims", "FAULTS", "context_pass", "tails_pass", "forward",
           "experts", "logit_gaps", "routing_miss"]

FAULTS = ("rope_on_full", "sequential", "shared_sum")


@dataclasses.dataclass(frozen=True)
class Dims:
    d: int
    heads: int
    kv_heads: int
    head_dim: int
    window: int
    full: tuple         # by layer: the full_attention layers
    router_experts: int
    held_first: int
    held: int
    top_k: int
    n_shared: int
    ff: int
    vocab: int
    layers: int
    theta: float
    eps: float

    @staticmethod
    def of(cfg: dict) -> "Dims":
        layers = cfg["num_hidden_layers"]
        return Dims(
            cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["head_dim"],
            cfg["sliding_window"],
            tuple(cfg["layer_types"][i] == "full_attention"
                  for i in range(layers)),
            cfg["router_experts"], cfg["first_expert_held"],
            cfg["num_experts"], cfg["num_experts_per_tok"],
            cfg["num_shared_experts"], cfg["intermediate_size"],
            cfg["vocab_size"], layers, float(cfg["rope_theta"]),
            float(cfg["layer_norm_eps"]))


def _ln(x, g, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    return (x - mu) * lax.rsqrt(var + eps) * g


def rope_gptj(x, pos, dims: Dims):
    """Turn x (N, heads, head_dim) by the positions (N,): the pairs
    (2j, 2j + 1), pair j by theta^(-2j/head_dim) a position."""
    hd = dims.head_dim
    freqs = dims.theta ** (-np.arange(0, hd, 2, dtype=np.float64) / hd)
    ang = pos.astype(jnp.float32)[:, None] * jnp.asarray(freqs,
                                                         jnp.float32)[None]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x0, x1 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x0 * cos - x1 * sin, x1 * cos + x0 * sin],
                     -1).reshape(x.shape)


def key_rows(w: dict, y, pos, dims: Dims, turned: bool, mode=None) -> dict:
    """What the tokens with normed block input y (N, d) are to a query:
    keys (turned where the layer turns them) and values, (N, kv heads,
    head_dim) each."""
    n, hq = y.shape[0], dims.heads * dims.head_dim
    kv = _mm(y, w["qkv_W"][:, hq:], mode)
    k, v = (kv[:, j * dims.kv_heads * dims.head_dim:
               (j + 1) * dims.kv_heads * dims.head_dim].reshape(
                   n, dims.kv_heads, dims.head_dim) for j in (0, 1))
    return {"k": rope_gptj(k, pos, dims) if turned else k, "v": v}


def attention(w: dict, y, pos_q, seg_q, keys: dict, dims: Dims,
              window: int, turned: bool, mode=None):
    """Attention of the queries with normed block input y (Q, d) over
    all of ``keys`` (`key_rows` of N tokens, with their ``pos`` and
    ``seg``) that each may see, within ``window`` positions where it is
    not 0. A kv head's group of query heads at a time, each by blocks
    of queries. Returns out (Q, d)."""
    n, h, hkv, hd = y.shape[0], dims.heads, dims.kv_heads, dims.head_dim
    g = h // hkv
    cut = lambda a: a.reshape(n // QUERY_BLOCK, QUERY_BLOCK,  # noqa: E731
                              *a.shape[1:])
    q = _mm(y, w["qkv_W"][:, :h * hd], mode).reshape(n, h, hd)
    if turned:
        q = rope_gptj(q, pos_q, dims)
    # query head j reads kv head j // g
    q = q.reshape(n, hkv, g, hd).transpose(1, 0, 2, 3)
    pos, seg = keys["pos"], keys["seg"]

    def group(args):
        qg, kg, vg = args

        def attend(block):
            qb, pq, sq = block
            seen = _visible(pq, sq, pos, seg)
            if window:
                seen &= pos[None, :] > pq[:, None] - window
            s = jnp.einsum("qgd,nd->gqn", qb, kg, precision=HI) * hd ** -0.5
            p = jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf), -1)
            return jnp.einsum("gqn,nd->qgd", p, vg, precision=HI)

        return lax.map(attend, (cut(qg), cut(pos_q), cut(seg_q))).reshape(
            n, g, hd)

    o = lax.map(group, (q, keys["k"].transpose(1, 0, 2),
                        keys["v"].transpose(1, 0, 2)))
    return _mm(o.transpose(1, 0, 2, 3).reshape(n, h * hd), w["out_W"], mode)


def route(w: dict, h, dims: Dims, forced=None):
    """(expert (N, k), weight (N, k), own (N, k)): sigmoid scores over
    all experts, the k highest chosen on the scores alone, weights the
    chosen scores over their sum. ``own`` is the router's choice; where
    a row of ``forced`` names experts (none negative), ``expert`` is
    that row and the weights are the router's scores of those."""
    sc = jax.nn.sigmoid(jnp.matmul(h, w["moe_router_W"], precision=HI))
    _, own = lax.top_k(sc, dims.top_k)
    expert = own
    if forced is not None:
        expert = jnp.where(jnp.all(forced >= 0, axis=-1, keepdims=True),
                           forced, own)
    picked = jnp.take_along_axis(sc, expert, axis=-1)
    return expert, picked / jnp.sum(picked, axis=-1, keepdims=True), own


def _swiglu(x, wg, wu, wd, mode):
    return _mm(jax.nn.silu(_mm(x, wg, mode)) * _mm(x, wu, mode), wd, mode)


def experts(w: dict, h, dims: Dims, mode=None, fault: str = "", forced=None,
            shared: bool = True):
    """The held experts' part of the routed sum, every held expert over
    the tokens that chose it, and (``shared``) the shared experts'
    mean: each its own SwiGLU of width `ff`, a slice of the leaves that
    hold them side by side. Returns (out, the router's own choice)."""
    expert, weight, own = route(w, h, dims, forced)
    out = jnp.zeros_like(h)
    for e in range(dims.held):
        g = jnp.sum(jnp.where(expert == dims.held_first + e, weight, 0.0), -1)
        (rows,) = jnp.nonzero(g > 0, size=h.shape[0], fill_value=0)
        count = jnp.sum(g > 0)

        def body(i, out, e=e, rows=rows, g=g, count=count):
            idx = lax.dynamic_slice(rows, (i * QUERY_BLOCK,), (QUERY_BLOCK,))
            live = i * QUERY_BLOCK + jnp.arange(QUERY_BLOCK) < count
            y = _swiglu(h[idx], w["moe_wg"][e], w["moe_wu"][e],
                        w["moe_wd"][e], mode)
            return out.at[idx].add(jnp.where(live, g[idx], 0.0)[:, None] * y)

        out = lax.fori_loop(0, -(-count // QUERY_BLOCK), body, out)
    if shared:
        f = dims.ff
        parts = [_swiglu(h, w["moe_sg"][:, j * f:(j + 1) * f],
                         w["moe_su"][:, j * f:(j + 1) * f],
                         w["moe_sd"][j * f:(j + 1) * f], mode)
                 for j in range(dims.n_shared)]
        total = functools.reduce(jnp.add, parts)
        out = out + (total if fault == "shared_sum"
                     else total / dims.n_shared)
    return out, own


@functools.partial(jax.jit, static_argnames=("dims", "window", "turned",
                                             "turn_held", "mode"))
def attention_part(w, x, pos_q, seg_q, held, dims, window, turned,
                   turn_held, mode):
    """Attn(LN(x)) of the queries x over the keys ``held`` (earlier
    tokens' `key_rows` with ``pos`` and ``seg``; turned here where
    ``turn_held``) and their own. Returns (attention's output, the
    queries' own rows, that output's size against the stream's: the
    share the weight scales are set for)."""
    y = _ln(x, w["ln1_g"], dims.eps)
    rows = key_rows(w, y, pos_q, dims, turned, mode)
    if turn_held:
        held = dict(held, k=rope_gptj(held["k"], held["pos"], dims))
    keys = {k: jnp.concatenate([held[k], v]) for k, v in
            dict(rows, pos=pos_q, seg=seg_q).items()}
    a = attention(w, y, pos_q, seg_q, keys, dims, window, turned, mode)
    return a, rows, jnp.sqrt(jnp.mean(a * a) / jnp.mean(x * x))


@functools.partial(jax.jit, static_argnames=("dims", "mode", "fault"))
def ffn_part(w, x, a, dims, mode, fault, forced):
    """The layer's output from its input x and attention's output a:
    x + a + MoE(LN(x)), or under the fault `sequential` x + a +
    MoE(LN(x + a)). Returns (it, the router's own choice)."""
    if fault == "sequential":
        x, a = x + a, 0.0
    out, own = experts(w, _ln(x, w["ln1_g"], dims.eps), dims, mode, fault,
                       forced)
    return x + a + out, own


@functools.partial(jax.jit, static_argnames=("dims", "mode"))
def _logits(x, lnf_g, tok_emb, dims, mode):
    return _mm(_ln(x, lnf_g, dims.eps), tok_emb.T, mode)


class Weights:
    """The seed's leaves in float32, in the checkpoint's order, a group
    at a time."""

    def __init__(self, cfg: dict, seed: int):
        self.cfg, self.key = cfg, weights.seed_key(seed)

    def leaves(self, names, prefix="") -> dict:
        drawn = weights.make_leaves(
            self.key, weights_cmdaplus.indexed(self.cfg, names), jnp.float32,
            via=jnp.bfloat16)
        return {k[len(prefix):]: v for k, v
                in weights_cmdaplus.finish(self.cfg, drawn).items()}

    def layer(self, i: int, ffn: bool) -> dict:
        """Layer i's attention leaves, or its expert layer's; both have
        the layer's one norm."""
        names = weights_cmdaplus.layer_names(self.cfg, i)
        of_ffn = {k for k in names if "_moe_" in k}
        norm = {f"L{i}_ln1_g"}
        return self.leaves((of_ffn if ffn else names - of_ffn) | norm,
                           f"L{i}_")


def _layer_kind(dims: Dims, i: int, fault: str = "") -> dict:
    full = dims.full[i]
    return {"window": 0 if full else dims.window,
            "turned": not full or fault == "rope_on_full",
            "turn_held": full and fault == "rope_on_full"}


def context_pass(cfg: dict, seed: int, context: np.ndarray,
                 mode=None) -> list:
    """The forward over one context (T,): for every layer what its
    tokens are to a later query (`key_rows`, float32). A layer's weights
    at a time. Sound unless ``mode`` lowers its precision: what a
    program of that precision would have prefilled (the control)."""
    dims, draw = Dims.of(cfg), Weights(cfg, seed)
    t = context.shape[0]
    ids = jnp.asarray(_padded(context.astype(np.int32), 0))
    pos = jnp.asarray(_padded(np.arange(t, dtype=np.int32), 0))
    # a padded token is a segment of its own: it sees itself only
    seg = jnp.asarray(_padded(np.zeros(t, np.int32), -1))
    x = draw.leaves({"tok_emb"})["tok_emb"][ids]
    none = {"k": jnp.zeros((0, dims.kv_heads, dims.head_dim)),
            "v": jnp.zeros((0, dims.kv_heads, dims.head_dim)),
            "pos": jnp.zeros((0,), jnp.int32),
            "seg": jnp.zeros((0,), jnp.int32)}
    state = []
    for i in range(dims.layers):
        a, rows, _ = attention_part(draw.layer(i, False), x, pos, seg, none,
                                    dims, mode=mode, **_layer_kind(dims, i))
        x, _ = ffn_part(draw.layer(i, True), x, a, dims, mode, "", None)
        state.append({k: v[:t] for k, v in rows.items()})
    return state


def tails_pass(cfg: dict, seed: int, state: list, tails: np.ndarray,
               runs: list) -> list:
    """The forward over tails (R, n) that go on from a context
    (`context_pass`'s ``state`` of T tokens): tail r at positions T ..
    T + n - 1 sees the context and itself. Every entry of ``runs`` is
    one such forward, a dict of `mode` (the precision of every weight
    matmul: the control), `fault` (one of `FAULTS`) and `forced`:
    experts (layers, R, n, k) that the tails' routing is to use in
    place of the router's own choice, which is still returned. The runs
    share a layer's weights, drawn once. Returns for each a dict:
    `logits` (R, n, vocab) and `experts` (layers, R, n, k), the router's
    own choice. The first run's share of attention's output in the
    stream, layer by layer, goes on standard error."""
    dims, draw = Dims.of(cfg), Weights(cfg, seed)
    t, (r, n) = state[0]["k"].shape[0], tails.shape
    ids = jnp.asarray(_padded(tails.reshape(-1).astype(np.int32), 0))
    pos = jnp.asarray(_padded(np.tile(t + np.arange(n, dtype=np.int32), r),
                              0))
    seg = jnp.asarray(_padded(np.repeat(1 + np.arange(r, dtype=np.int32), n),
                              -1))
    q = ids.shape[0]
    held_pos = {"pos": jnp.arange(t, dtype=jnp.int32),
                "seg": jnp.zeros((t,), jnp.int32)}
    ends = draw.leaves({"tok_emb", "lnf_g"})
    xs = [ends["tok_emb"][ids] for _ in runs]
    forced = []
    for run in runs:
        experts_of = np.full((dims.layers, q, dims.top_k), -1, np.int32)
        if run.get("forced") is not None:
            experts_of[:, :r * n] = run["forced"].reshape(dims.layers, r * n,
                                                         -1)
        forced.append(experts_of)
    own_choice = [[] for _ in runs]
    shares = []
    for i in range(dims.layers):
        held = dict(state[i], **held_pos)
        w = draw.layer(i, False)
        attended = []
        for j, run in enumerate(runs):
            a, _, share = attention_part(
                w, xs[j], pos, seg, held, dims, mode=run.get("mode"),
                **_layer_kind(dims, i, run.get("fault", "")))
            attended.append(a)
            if j == 0:
                shares.append(float(share))
        w = draw.layer(i, True)
        for j, run in enumerate(runs):
            xs[j], own = ffn_part(w, xs[j], attended[j], dims,
                                  run.get("mode"), run.get("fault", ""),
                                  jnp.asarray(forced[j][i]))
            own_choice[j].append(np.asarray(own)[:r * n].reshape(r, n, -1))
    print("reference: attention's output against the stream, by layer: "
          + " ".join(f"{s:.3f}" for s in shares), file=sys.stderr)
    return [{"logits": np.asarray(_logits(
                xs[j][:r * n], ends["lnf_g"], ends["tok_emb"], dims,
                run.get("mode"))).reshape(r, n, -1),
             "experts": np.stack(own_choice[j])}
            for j, run in enumerate(runs)]


def forward(cfg: dict, seed: int, context: np.ndarray, tails: np.ndarray,
            **run) -> dict:
    """One run of `tails_pass` after `context_pass`: a full causal
    forward over ``context + tail`` for every tail."""
    return tails_pass(cfg, seed, context_pass(cfg, seed, context), tails,
                      [run])[0]
