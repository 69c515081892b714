"""The plain reference for DeepSeek-V3.2-Exp: its published forward
pass in straightforward `jax.numpy` and float32 with every matmul at
`highest` precision. No kernel, no cache, no absorbed form: keys and
values are built for every head (DeepSeek-V2, section 2.1), the
indexer scores every key and the `index_topk` best are kept
(DeepSeek-V3.2-Exp, section 2; `inference/model.py`), the router is
V3's (sigmoid, selection bias, group-limited top-k, normalised and
scaled weights) with one shared expert. It imports nothing of the
program and takes no array from it; the weights come from the seed
through `weights_dsv32.py`'s table, a layer at a time (the served
bfloat16 values, held in float32).

The share is the configuration's: the router runs over
`router_experts`, the experts `[first_expert_held, first_expert_held +
n_routed_experts)` are computed and what the others would add is left
out; the vocabulary is the slice.

Two passes. `context_pass` runs the sound forward over one context
and keeps, for every layer, what its tokens are to a later query (the
latent, the rope key, the indexer's key). `tails_pass` runs tails that
go on from that context, each seeing the context and itself: together
a full causal forward over `context + tail` for every tail, with the
context computed once. A tails pass can be told which keys its
attention reads and which experts its tokens go to (`forced`: the
program's own choices), so that a choice made between near-tied scores
is compared as a choice (`selection_miss`, `routing_miss`) and the
logits are compared under the same one.

`mode` lowers the precision of every weight matmul of a pass (the
control); `fault` plants in a tails pass what a wrong program would
do: `newest_keys` (no indexer: the newest `index_topk` positions),
`no_shared`, `no_bias` (the selection bias dropped), `no_scale` (the
routed scaling factor dropped), over the sound context: the least such
a fault can read.
"""

from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from . import weights, weights_dsv32

HI = lax.Precision.HIGHEST
FAULTS = ("newest_keys", "no_shared", "no_bias", "no_scale")
QUERY_BLOCK = 64
HEAD_GROUP = 16


@dataclasses.dataclass(frozen=True)
class Dims:
    d: int
    heads: int
    q_rank: int
    kv_rank: int
    nope: int
    rope: int
    v: int
    index_heads: int
    index_dim: int
    index_topk: int
    router_experts: int
    held_first: int
    held: int
    top_k: int
    groups: int
    topk_groups: int
    route_scale: float
    vocab: int
    layers: int
    first_dense: int
    theta: float
    eps: float
    yarn_factor: float
    yarn_original: int
    beta_fast: float
    beta_slow: float
    mscale_all_dim: float

    @staticmethod
    def of(cfg: dict) -> "Dims":
        y = cfg["rope_scaling"]
        return Dims(
            cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["q_lora_rank"], cfg["kv_lora_rank"],
            cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
            cfg["v_head_dim"], cfg["index_n_heads"], cfg["index_head_dim"],
            cfg["index_topk"], cfg["router_experts"],
            cfg["first_expert_held"], cfg["n_routed_experts"],
            cfg["num_experts_per_tok"], cfg["n_group"], cfg["topk_group"],
            float(cfg["routed_scaling_factor"]), cfg["vocab_size"],
            cfg["num_hidden_layers"], cfg["first_k_dense_replace"],
            float(cfg["rope_theta"]), float(cfg["rms_norm_eps"]),
            float(y["factor"]), y["original_max_position_embeddings"],
            float(y["beta_fast"]), float(y["beta_slow"]),
            float(y["mscale_all_dim"]))


def _lower(x, axis: int, mode):
    """``x`` as the lower precision ``mode`` holds it, scaled along
    ``axis`` (`reference.py`'s control)."""
    if mode is None:
        return x
    top = {"int8": 127.0, "fp8": 448.0}[mode]
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / top
    s = jnp.where(s == 0, 1.0, s)
    if mode == "fp8":
        return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s
    return jnp.round(x / s) * s


def _mm(x, w, mode=None):
    return jnp.matmul(_lower(x, -1, mode), _lower(w, 0, mode), precision=HI)


def _rms(x, g, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def _layer_norm(x, g, b, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    return (x - mu) * lax.rsqrt(var + eps) * g + b


def yarn_frequencies(dims: Dims) -> np.ndarray:
    """Rotary frequencies under YaRN as DeepSeek-V3 computes them
    (`precompute_freqs_cis`): dimension pair i turns theta^(-2i/dim) a
    position; pairs below `low` keep that, pairs above `high` are
    divided by the factor, linear between."""
    dim, base = dims.rope, dims.theta
    freqs = 1.0 / base ** (np.arange(0, dim, 2) / dim)

    def correction_dim(rotations):
        return (dim * math.log(dims.yarn_original / (rotations * 2 * math.pi))
                / (2 * math.log(base)))

    low = max(math.floor(correction_dim(dims.beta_fast)), 0)
    high = min(math.ceil(correction_dim(dims.beta_slow)), dim - 1)
    ramp = np.clip((np.arange(dim // 2) - low)
                   / (0.001 if low == high else high - low), 0, 1)
    smooth = 1 - ramp
    return freqs / dims.yarn_factor * (1 - smooth) + freqs * smooth


def softmax_scale(dims: Dims) -> float:
    m = 0.1 * dims.mscale_all_dim * math.log(dims.yarn_factor) + 1.0
    return (dims.nope + dims.rope) ** -0.5 * m * m


def _rope(x, pos, dims: Dims):
    """Rotate the last axis (`rope` wide) of x (N, ..., rope) by the
    positions; pairs are (i, i + rope/2), the repo's convention for
    every model (with random weights a fixed permutation of the
    checkpoint's interleaved pairs)."""
    half = dims.rope // 2
    ang = pos.astype(jnp.float32)[:, None] * jnp.asarray(
        yarn_frequencies(dims), jnp.float32)[None, :]
    shape = (x.shape[0],) + (1,) * (x.ndim - 2) + (half,)
    cos, sin = jnp.cos(ang).reshape(shape), jnp.sin(ang).reshape(shape)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _visible(pos_q, seg_q, pos, seg):
    """(Q, N): which tokens a query sees."""
    same = (seg[None, :] == 0) | (seg[None, :] == seg_q[:, None])
    return same & (pos[None, :] <= pos_q[:, None])


def kth_highest(scores, k: int):
    """The k-th highest value of each row, by bisection on the floats'
    bit patterns (a sort of 64 x 32k scores takes the chip 3.5 ms, and
    a pass makes 2,570 of them). An IEEE float's bits, with the sign
    bit flipped where it is clear and all bits flipped where it is set,
    order as unsigned integers the way the floats order."""
    top = jnp.uint32(2 ** 31)
    bits = lax.bitcast_convert_type(scores, jnp.uint32)
    order = jnp.where(bits >= top, ~bits, bits + top)

    def halve(_, bounds):
        lo, hi = bounds                 # the answer lies in [lo, hi]
        mid = lo + (hi - lo) // jnp.uint32(2) + (hi - lo) % jnp.uint32(2)
        enough = jnp.sum(order >= mid[:, None], axis=-1) >= k
        return (jnp.where(enough, mid, lo),
                jnp.where(enough, hi, mid - jnp.uint32(1)))

    rows = scores.shape[0]
    lo, _ = lax.fori_loop(0, 32, halve, (
        jnp.zeros(rows, jnp.uint32), jnp.full(rows, ~jnp.uint32(0))))
    back = jnp.where(lo >= top, lo - top, ~lo)
    return lax.bitcast_convert_type(back, jnp.float32)


def select_keys(scores, visible, pos_q, pos, dims: Dims, newest: bool):
    """(Q, N) mask of the `index_topk` highest-scoring visible keys of
    each query, all of them where fewer are visible; equal scores go to
    the key that comes first. ``newest`` plants the fault: no indexer,
    the newest `index_topk` positions."""
    if newest:
        return visible & (pos[None, :] > pos_q[:, None] - dims.index_topk)
    k = min(dims.index_topk, scores.shape[1])
    scores = jnp.where(visible, scores, -jnp.inf)
    kth = kth_highest(scores, k)[:, None]
    above = scores > kth
    tied = scores == kth
    # of the keys tied at the k-th score, the first ones fill what is left
    room = k - jnp.sum(above, axis=-1, keepdims=True)
    return (above | (tied & (jnp.cumsum(tied, axis=-1) <= room))) & visible


def key_rows(w: dict, y, pos, dims: Dims, mode=None) -> dict:
    """What the tokens with normed block input y (N, d) are to a query:
    the normed latent, the rotated rope key that all heads share, and
    the indexer's key."""
    kv = _mm(y, w["kva_W"], mode)
    k_i = _layer_norm(_mm(y, w["ik_W"], mode), w["ik_g"], w["ik_b"], dims.eps)
    return {"c_kv": _rms(kv[:, :dims.kv_rank], w["kv_g"], dims.eps),
            "k_r": _rope(kv[:, dims.kv_rank:], pos, dims),
            "k_i": jnp.concatenate([_rope(k_i[:, :dims.rope], pos, dims),
                                    k_i[:, dims.rope:]], -1)}


def attention(w: dict, y, pos_q, seg_q, keys: dict, dims: Dims, mode=None,
              newest: bool = False, forced=None):
    """Multi-head latent attention of the queries with normed block
    input y (Q, d) over the keys the indexer selects among ``keys``
    (`key_rows` of N tokens, with their ``pos`` and ``seg``). Returns
    (out (Q, d), chosen (Q, N) mask: the indexer's own choice). Where a
    row of ``forced`` (Q, N) holds anything, the query attends those
    keys instead (its own choice is still what is returned). The
    indexer first, a block of queries at a time; then the heads,
    `HEAD_GROUP` at a time (so that float32 keys and values of 32k
    positions fit), each again by blocks of queries."""
    n, h = y.shape[0], dims.heads
    blocks = n // QUERY_BLOCK
    cut = lambda a: a.reshape(blocks, QUERY_BLOCK, *a.shape[1:])  # noqa: E731
    pos, seg = keys["pos"], keys["seg"]
    c_q = _rms(_mm(y, w["qa_W"], mode), w["qa_g"], dims.eps)

    # the indexer
    q_i = _mm(c_q, w["iq_W"], mode).reshape(n, dims.index_heads,
                                            dims.index_dim)
    q_i = jnp.concatenate([_rope(q_i[..., :dims.rope], pos_q, dims),
                           q_i[..., dims.rope:]], -1)
    w_i = _mm(y, w["iw_W"], mode) * (dims.index_heads ** -0.5
                                     * dims.index_dim ** -0.5)

    def choose(args):
        qib, wib, pq, sq = args
        s_i = jnp.einsum("qjd,nd->qjn", qib, keys["k_i"], precision=HI)
        s_i = jnp.einsum("qjn,qj->qn", jax.nn.relu(s_i), wib, precision=HI)
        return select_keys(s_i, _visible(pq, sq, pos, seg), pq, pos, dims,
                           newest)

    chosen = lax.map(choose, (cut(q_i), cut(w_i), cut(pos_q), cut(seg_q)))
    read = chosen
    if forced is not None:
        forced = cut(forced)
        read = jnp.where(jnp.any(forced, axis=-1, keepdims=True), forced,
                         chosen)

    # the heads, a group at a time: a group's keys and values are built,
    # used and dropped
    scale = softmax_scale(dims)
    n_keys = pos.shape[0]
    hg = min(HEAD_GROUP, h)
    grouped = lambda m, width: m.reshape(  # noqa: E731
        m.shape[0], h // hg, hg * width).transpose(1, 0, 2)
    w_qb = grouped(w["qb_W"], dims.nope + dims.rope)
    w_kvb = grouped(w["kvb_W"], dims.nope + dims.v)

    def group(weights):
        q = _mm(c_q, weights[0], mode).reshape(n, hg, dims.nope + dims.rope)
        q = jnp.concatenate([q[..., :dims.nope],
                             _rope(q[..., dims.nope:], pos_q, dims)], -1)
        kvh = _mm(keys["c_kv"], weights[1], mode).reshape(
            n_keys, hg, dims.nope + dims.v)
        k = jnp.concatenate(
            [kvh[..., :dims.nope],
             jnp.broadcast_to(keys["k_r"][:, None, :],
                              (n_keys, hg, dims.rope))], -1)
        v = kvh[..., dims.nope:]

        def attend(args):
            qb, read_b = args
            s = jnp.einsum("qhd,nhd->hqn", qb, k, precision=HI) * scale
            p = jax.nn.softmax(jnp.where(read_b[None], s, -jnp.inf), -1)
            return jnp.einsum("hqn,nhd->qhd", p, v, precision=HI)

        return lax.map(attend, (cut(q), read)).reshape(n, hg, dims.v)

    o = lax.map(group, (w_qb, w_kvb))                  # (groups, Q, hg, v)
    o = o.transpose(1, 0, 2, 3).reshape(n, h * dims.v)
    return _mm(o, w["out_W"], mode), chosen.reshape(n, n_keys)


def route(w: dict, x, dims: Dims, fault: str = "", forced=None):
    """(expert (N, k), weight (N, k), own (N, k)) of DeepSeek-V3's
    router: ``own`` is its choice; where a row of ``forced`` (N, k)
    names experts (none is negative), ``expert`` is that row instead
    and the weights are the router's scores of those."""
    sc = jax.nn.sigmoid(jnp.matmul(x, w["moe_router_W"], precision=HI))
    n, e = sc.shape
    choice = sc if fault == "no_bias" else sc + w["moe_router_b"]
    per = e // dims.groups
    group = jnp.sum(lax.top_k(choice.reshape(n, dims.groups, per), 2)[0], -1)
    _, keep = lax.top_k(group, dims.topk_groups)
    kept = jnp.any(jnp.arange(dims.groups)[None, :, None]
                   == keep[:, None, :], axis=-1)               # (N, groups)
    choice = jnp.where(jnp.repeat(kept, per, axis=1), choice, -jnp.inf)
    _, own = lax.top_k(choice, dims.top_k)
    expert = own
    if forced is not None:
        expert = jnp.where(jnp.all(forced >= 0, axis=-1, keepdims=True),
                           forced, own)
    picked = jnp.take_along_axis(sc, expert, axis=-1)
    weight = picked / jnp.sum(picked, axis=-1, keepdims=True)
    if fault != "no_scale":
        weight = weight * dims.route_scale
    return expert, weight, own


def _swiglu(x, wg, wu, wd, mode):
    return _mm(jax.nn.silu(_mm(x, wg, mode)) * _mm(x, wu, mode), wd, mode)


def experts(w: dict, x, dims: Dims, mode=None, fault: str = "", forced=None):
    """The held experts' part of `sum_i g_i E_i(x)` and the shared
    expert: every held expert over the tokens that chose it. Returns
    (out, the router's own choice (N, k))."""
    expert, weight, own = route(w, x, dims, fault, forced)
    out = jnp.zeros_like(x)
    for e in range(dims.held):
        g = jnp.sum(jnp.where(expert == dims.held_first + e, weight, 0.0), -1)
        (rows,) = jnp.nonzero(g > 0, size=x.shape[0], fill_value=0)
        count = jnp.sum(g > 0)

        def body(i, out, e=e, rows=rows, g=g, count=count):
            idx = lax.dynamic_slice(rows, (i * QUERY_BLOCK,), (QUERY_BLOCK,))
            live = i * QUERY_BLOCK + jnp.arange(QUERY_BLOCK) < count
            y = _swiglu(x[idx], w["moe_wg"][e], w["moe_wu"][e],
                        w["moe_wd"][e], mode)
            return out.at[idx].add(jnp.where(live, g[idx], 0.0)[:, None] * y)

        out = lax.fori_loop(0, -(-count // QUERY_BLOCK), body, out)
    if fault != "no_shared":
        out = out + _swiglu(x, w["moe_sg"], w["moe_su"], w["moe_sd"], mode)
    return out, own


@functools.partial(jax.jit, static_argnames=("dims", "mode", "newest"))
def attention_part(w, x, pos_q, seg_q, held, dims, mode, newest, forced):
    """x + Attn(RMSNorm(x)) of the queries x over the keys ``held``
    (earlier tokens' `key_rows` with ``pos`` and ``seg``) and their own.
    Returns (x, the queries' own rows, and where ``forced`` is given the
    indexer's choice (Q, N), else None: a context's is a gigabyte that
    nothing reads)."""
    y = _rms(x, w["ln1_g"], dims.eps)
    rows = key_rows(w, y, pos_q, dims, mode)
    keys = {k: jnp.concatenate([held[k], v]) for k, v in
            dict(rows, pos=pos_q, seg=seg_q).items()}
    a, chosen = attention(w, y, pos_q, seg_q, keys, dims, mode, newest,
                          forced)
    return x + a, rows, None if forced is None else chosen


@functools.partial(jax.jit, static_argnames=("dense", "dims", "mode",
                                             "fault"))
def ffn_part(w, x, dense, dims, mode, fault, forced):
    """x + FFN(RMSNorm(x)): the dense SwiGLU or the expert layer (and
    then the router's own choice, else None)."""
    y = _rms(x, w["ln2_g"], dims.eps)
    if dense:
        return x + _swiglu(y, w["ff1_W"], w["ff3_W"], w["ff2_W"], mode), None
    out, own = experts(w, y, dims, mode, fault, forced)
    return x + out, own


@functools.partial(jax.jit, static_argnames=("dims", "mode"))
def _logits(x, lnf_g, head_w, dims, mode):
    return _mm(_rms(x, lnf_g, dims.eps), head_w, mode)


def _padded(a: np.ndarray, fill) -> np.ndarray:
    """``a`` with its first axis filled up to whole query blocks."""
    pad = -a.shape[0] % QUERY_BLOCK
    return np.concatenate([a, np.full((pad,) + a.shape[1:], fill, a.dtype)])


class Weights:
    """The seed's leaves in float32, a group at a time."""

    def __init__(self, cfg: dict, seed: int):
        self.cfg, self.key = cfg, weights.seed_key(seed)

    def leaves(self, names, prefix="") -> dict:
        drawn = weights.make_leaves(
            self.key, weights_dsv32.indexed(self.cfg, names), jnp.float32,
            via=jnp.bfloat16)
        return {k[len(prefix):]: v for k, v
                in weights_dsv32.finish(self.cfg, drawn).items()}

    def layer(self, i: int, ffn: bool) -> dict:
        """Layer i's attention leaves, or its FFN's: one group at a time
        is as much float32 as fits beside a context's keys and values."""
        names = weights_dsv32.layer_names(self.cfg, i)
        of_ffn = {k for k in names
                  if "_ff" in k or "_moe_" in k or "_ln2_" in k}
        return self.leaves(of_ffn if ffn else names - of_ffn, f"L{i}_")


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
    none = {"c_kv": jnp.zeros((0, dims.kv_rank)),
            "k_r": jnp.zeros((0, dims.rope)),
            "k_i": jnp.zeros((0, dims.index_dim)),
            "pos": jnp.zeros((0,), jnp.int32),
            "seg": jnp.zeros((0,), jnp.int32)}
    state = []
    for i in range(dims.layers):
        x, rows, _ = attention_part(draw.layer(i, False), x, pos, seg, none,
                                    dims, mode, False, None)
        x, _ = ffn_part(draw.layer(i, True), x, i < dims.first_dense, dims,
                        mode, "", None)
        state.append({k: v[:t] for k, v in rows.items()})
    return state


def tails_pass(cfg: dict, seed: int, state: list, tails: np.ndarray,
               runs: list) -> list:
    """The forward over tails (R, n) that go on from a context
    (`context_pass`'s ``state`` of T tokens): tail r at positions T ..
    T + n - 1 sees the context and itself. Every entry of ``runs`` is
    one such forward, a dict of `mode` (the precision of every weight
    matmul: the control), `fault` (what a wrong program would do, one
    of `FAULTS`) and `forced`: (selected (layers, R, n, K) cache
    positions with -1 for none, experts (expert layers, R, n, k)) that
    the tails' attention and routing are to use in place of the
    indexer's and the router's own choice, which are still returned.
    The runs share a layer's weights, drawn once. Returns for each a
    dict: `logits` (R, n, vocab); `selected` (layers, R, n, T + n)
    masks over positions (the context's, then the tail's own): the
    indexer's own choice; `experts` (expert layers, R, n, k): the
    router's own."""
    dims, draw = Dims.of(cfg), Weights(cfg, seed)
    t, (r, n) = state[0]["c_kv"].shape[0], tails.shape
    ids = jnp.asarray(_padded(tails.reshape(-1).astype(np.int32), 0))
    pos = jnp.asarray(_padded(np.tile(t + np.arange(n, dtype=np.int32), r),
                              0))
    seg = jnp.asarray(_padded(np.repeat(1 + np.arange(r, dtype=np.int32), n),
                              -1))
    q = ids.shape[0]
    held_pos = {"pos": jnp.arange(t, dtype=jnp.int32),
                "seg": jnp.zeros((t,), jnp.int32)}
    ends = draw.leaves({"tok_emb", "lnf_g", "head_W"})
    xs = [ends["tok_emb"][ids] for _ in runs]
    forced_keys = [_forced_masks(run.get("forced"), t, r, n, q, dims)
                   for run in runs]
    out = [{"selected": [], "experts": []} for _ in runs]
    for i in range(dims.layers):
        held = dict(state[i], **held_pos)
        moe = i - dims.first_dense
        w = draw.layer(i, False)
        for j, run in enumerate(runs):
            xs[j], _, chosen = attention_part(
                w, xs[j], pos, seg, held, dims, run.get("mode"),
                run.get("fault") == "newest_keys",
                jnp.asarray(forced_keys[j][0][i]))
            chosen = np.asarray(chosen)[:r * n].reshape(r, n, -1)
            mine = np.stack([chosen[k, :, t + k * n:t + (k + 1) * n]
                             for k in range(r)])
            out[j]["selected"].append(
                np.concatenate([chosen[:, :, :t], mine], axis=-1))
        w = draw.layer(i, True)
        for j, run in enumerate(runs):
            xs[j], own = ffn_part(
                w, xs[j], moe < 0, dims, run.get("mode"),
                run.get("fault", ""),
                None if moe < 0 else jnp.asarray(forced_keys[j][1][moe]))
            if own is not None:
                out[j]["experts"].append(
                    np.asarray(own)[:r * n].reshape(r, n, -1))
    for j, run in enumerate(runs):
        logits = _logits(xs[j][:r * n], ends["lnf_g"], ends["head_W"], dims,
                         run.get("mode"))
        out[j] = {"logits": np.asarray(logits).reshape(r, n, -1),
                  "selected": np.stack(out[j]["selected"]),
                  "experts": np.stack(out[j]["experts"])
                  if out[j]["experts"] else np.zeros((0, r, n, dims.top_k),
                                                     np.int32)}
    return out


def _forced_masks(forced, t: int, r: int, n: int, q: int, dims: Dims):
    """`tails_pass`'s ``forced`` as it is handed to the layers: (masks
    (layers, Q, T + Q) over the keys, experts (expert layers, Q, k)),
    the padded queries' rows empty (their own choice stands), as every
    row is where nothing is forced."""
    masks = np.zeros((dims.layers, q, t + q), bool)
    experts = np.full((dims.layers - dims.first_dense, q, dims.top_k), -1,
                      np.int32)
    if forced is None:
        return masks, experts
    selected, routed = forced
    layers = selected.shape[0]
    flat = selected.reshape(layers, r * n, -1)
    # a position in the tail is that tail's own token
    first = t + (np.arange(r * n) // n * n)[None, :, None]
    key = np.where(flat < t, flat, first + flat - t)
    rows = np.broadcast_to(np.arange(r * n)[None, :, None], flat.shape)
    layer = np.broadcast_to(np.arange(layers)[:, None, None], flat.shape)
    ok = flat >= 0
    masks[layer[ok], rows[ok], key[ok]] = True
    experts[:, :r * n] = routed.reshape(routed.shape[0], r * n, -1)
    return masks, experts


def forward(cfg: dict, seed: int, context: np.ndarray, tails: np.ndarray,
            **run) -> dict:
    """One run of `tails_pass` after `context_pass`: a full causal
    forward over ``context + tail`` for every tail."""
    return tails_pass(cfg, seed, context_pass(cfg, seed, context), tails,
                      [run])[0]


def logit_gaps(ref_logits: np.ndarray, chosen: np.ndarray) -> np.ndarray:
    """By how much the chosen token's logit lies below the reference's
    best, at every position: (R, n)."""
    best = ref_logits.max(axis=-1)
    return best - np.take_along_axis(ref_logits, chosen[..., None], -1)[..., 0]


def selection_miss(selected: np.ndarray, ref_selected: np.ndarray) -> float:
    """The share of selected positions that the reference's selection
    lacks. ``selected`` (layers, R, n, K) positions, -1 for none;
    ``ref_selected`` (layers, R, n, T + n) masks."""
    valid = selected >= 0
    hit = np.take_along_axis(ref_selected, np.where(valid, selected, 0), -1)
    return float(np.sum(valid & ~hit) / max(1, np.sum(valid)))


def routing_miss(experts: np.ndarray, ref_experts: np.ndarray) -> float:
    """The share of a token's routed experts that the reference's
    router did not choose for it. Both (expert layers, R, n, k)."""
    hit = np.any(experts[..., :, None] == ref_experts[..., None, :], axis=-1)
    return float(1.0 - hit.mean()) if hit.size else 0.0


def mask_positions(mask: np.ndarray, k: int) -> np.ndarray:
    """A (..., P) selection mask as (..., k) positions, -1 padded: what
    `selection_miss` takes as ``selected``."""
    order = np.argsort(~mask, axis=-1, kind="stable")[..., :k]
    return np.where(np.take_along_axis(mask, order, -1), order, -1)
