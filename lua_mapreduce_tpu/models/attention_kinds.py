"""What a decoder layer's attention is (its MIXER, where the layer has
no attention): a KIND owns its weights, what a position caches, the
cache's format and the attention over it. :class:`GroupedQuery` and
:class:`Latent` are attention over caches of their own;
:class:`StateSpace`, :class:`Cross` and :class:`GatedMemory` are the
further layers of a hybrid stack (a recurrent state; attention over
ANOTHER layer's cache; a gate on another layer's activation);
:class:`KimiDelta` is linear attention with a recurrent state, beside
latent layers in a stack with an attention pattern.
``models/transformer.attention_kind(cfg, i)`` says which one layer ``i``
has, and nothing there knows more of a kind than the methods below.
``cfg`` is the model's ``TransformerConfig``; ``p`` is the layer's
parameter and cache prefix (``L{i}``), ``y`` the normed layer input.

    init(keys, dtype, p)                     the layer's attention weights
    full(params, p, y, pos, attn_fn)         -> (out, rows)
    chunk(params, p, y, pos, caches, start)  -> (out, caches)
    step(params, p, y, t, caches, kv_q8)     -> (out, (caches, selected))
    empty(p, b, total, dtype, kv_q8)         -> the layer's empty caches
    padded(p, rows, total, dtype)            -> prefill's public caches
    scanned(p, caches, p_len, total, kv_q8)  -> the public ones, as scanned
    turn_start(p, caches, start)             -> leaves to replace (often none)

``full`` attends a whole sequence (``y`` (B, L, d) at positions ``pos``)
and returns what it cached of it; ``chunk`` and ``step`` write their
positions into ``caches`` (every layer's, as ``empty`` and ``scanned``
lay them out) and attend what the caches hold by then, ``step`` for the
one position ``t`` with the cache positions it selected (None where the
kind reads them all). ``out`` is the attention's output through its out
projection, for the residual. ``turn_start`` is called once before a
turn's scan (``decode_from``) with the position the turn starts from: a
kind whose caches a turn changes for good (a state, a rolling buffer:
nothing in them says what they held before) keeps ONE snapshot beside
them and puts it back when the turn before started from the same
position, so that a turn can be taken back and made again. A kind with
``shares`` set takes part in
a hybrid stack's hand-over: ``full``, ``chunk`` and ``step`` are then
given ``handed=``, the dict in which the layers of ONE forward leave
what later layers of the same forward read (``memory``: a state-space
layer's scan output at the current positions; ``kv``: a full-attention
layer's rows, for the cross layers of a full-sequence forward, which
has no caches). This module sits below ``models/transformer.py`` and
never imports it.
"""

from __future__ import annotations

import contextlib
import dataclasses
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from lua_mapreduce_tpu.ops import kda as _kda
from lua_mapreduce_tpu.ops import sparse_mla as _sparse
from lua_mapreduce_tpu.ops import ssm as _ssm
from lua_mapreduce_tpu.ops.decode import (cache_attention, decode_attention,
                                          quantize_kv)
from lua_mapreduce_tpu.ops.mla_decode import (mla_causal_attention,
                                              mla_decode_attention)
from lua_mapreduce_tpu.ops.q8 import q8_matmul
from lua_mapreduce_tpu.utils.profiling import scope

Params = Dict[str, jnp.ndarray]


def kv_heads(cfg) -> int:
    """Effective kv head count (n_kv_heads, defaulting to n_heads)."""
    hkv = cfg.n_kv_heads or cfg.n_heads
    if cfg.n_heads % hkv:
        raise ValueError(f"n_kv_heads={hkv} must divide "
                         f"n_heads={cfg.n_heads}")
    return hkv


def head_dim(cfg) -> int:
    return cfg.head_dim or cfg.d_model // cfg.n_heads


def _dense(key, shape, dtype):
    return jax.random.normal(key, shape, dtype) / np.sqrt(shape[0])


def _mm(params: Params, key: str, y):
    """``y @ params[key]`` — through the weight-only int8 kernel when
    the param dict carries a quantized entry (``key::q8`` +
    ``key::scale``, see ``quantize_lm``). The branch is on dict
    STRUCTURE, so it is resolved at trace time and costs nothing."""
    qk = key + "::q8"
    if qk in params:
        shp = y.shape
        out = q8_matmul(y.reshape(-1, shp[-1]), params[qk],
                        params[key + "::scale"])
        return out.reshape(*shp[:-1], out.shape[-1])
    return y @ params[key]


def _layer_norm(x, g, b, eps=1e-5):
    """LayerNorm with a gain, and a bias unless ``b`` is None."""
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.var(x, axis=-1, keepdims=True)
    out = (x - mu) * lax.rsqrt(var + eps) * g
    return out if b is None else out + b


def _rms_norm(x, g, eps):
    ms = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * lax.rsqrt(ms + eps) * g


def _norm(params: Params, name: str, x, cfg):
    """The block norm: pre-LN (scale+bias), RMSNorm (scale only) or
    ``ln_gain``, LayerNorm with a scale and no bias."""
    g = params[f"{name}_g"]
    if cfg.norm == "rms":
        return _rms_norm(x, g, cfg.norm_eps)
    if cfg.norm == "ln_gain":
        return _layer_norm(x, g, None, cfg.norm_eps)
    return _layer_norm(x, g, params[f"{name}_b"], cfg.norm_eps)


def _yarn_freqs(la, base: float) -> np.ndarray:
    """The rope frequencies of ``la.rope_dim`` under YaRN (Peng et al.
    2023, as DeepSeek-V3 applies it): frequencies whose wavelength fits
    the original context ``beta_fast`` times or more are kept, those
    that fit it ``beta_slow`` times or fewer are divided by the factor,
    with a linear ramp between."""
    dim = la.rope_dim
    freqs = base ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
    if la.rope_factor == 1.0:
        return freqs.astype(np.float32)

    def turns_to_dim(turns):
        return (dim * np.log(la.rope_original / (turns * 2 * np.pi))
                / (2 * np.log(base)))

    low = max(np.floor(turns_to_dim(la.beta_fast)), 0)
    high = min(np.ceil(turns_to_dim(la.beta_slow)), dim - 1)
    ramp = np.clip((np.arange(dim // 2) - low) / max(high - low, 1e-3), 0, 1)
    return (freqs / la.rope_factor * ramp
            + freqs * (1 - ramp)).astype(np.float32)


def _rope(x, pos, base: float, freqs=None):
    """Rotary embedding: rotate each (i, i+hd/2) pair of head dims by
    pos·base^(-2i/hd), or by ``freqs`` where given. x (B, L, H*, hd) —
    broadcasts over ANY head
    count (q and GQA's smaller k alike); pos (L,) global positions.
    Rotation-half convention; angles in f32, result in x.dtype."""
    half = x.shape[-1] // 2
    if freqs is None:
        freqs = base ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[:, None] * freqs[None, :]  # (L, half)
    cos = jnp.cos(ang)[None, :, None, :]
    sin = jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :half].astype(jnp.float32), \
        x[..., half:].astype(jnp.float32)
    return jnp.concatenate([x1 * cos - x2 * sin,
                            x2 * cos + x1 * sin], axis=-1).astype(x.dtype)


def _rolls(cfg, cache_len: int) -> bool:
    """Whether caches of ``cache_len`` slots are a ROLLING buffer: they
    are where they are as long as the window (position p lives in slot
    p mod window). The one rule for every decode; where the window
    covers the whole decode, p mod window is p and the two layouts are
    one. ``cfg`` is whatever has the ``window``: a kind, or a
    configuration with one window for all layers."""
    return bool(cfg.window) and cache_len == cfg.window


def _cache_shape(cfg, total: int) -> tuple:
    """(roll, cache_len) of a decode over ``total`` positions. A sliding
    window makes the cache a rolling buffer of ``window`` slots: the
    scan carry is O(w) instead of O(total), the serving memory the
    window exists for. Rolling containment IS the window mask — slot
    contents are exactly the positions (t-w, t], so the only masking
    left is "slot not yet filled" during the first w steps."""
    cache_len = min(cfg.window, total) if cfg.window else total
    return _rolls(cfg, cache_len), cache_len


def _put(caches: Params, rows: Params, at: tuple) -> Params:
    """``caches`` with each of ``rows`` written into its leaf from
    ``at`` (the leading indices; the rest start at 0) on."""
    return {**caches, **{
        name: lax.dynamic_update_slice(
            caches[name], row, at + (0,) * (row.ndim - len(at)))
        for name, row in rows.items()}}


@dataclasses.dataclass(frozen=True)
class _Kind:
    cfg: object

    # names of what a layer caches, in the order ``full`` returns them
    leaves = ()
    # whether the kind takes part in a hybrid stack's hand-over: its
    # forms are then given ``handed=`` (the module's docstring)
    shares = False
    # whether prefill's public caches are the scan's own: a chunked
    # prefill fills the scan's caches, so it is for such kinds
    one_form = False

    def names(self, p: str) -> list:
        return [f"{p}_{leaf}" for leaf in self.leaves]

    def chunk(self, params, p, y, pos, caches, start):
        raise ValueError(f"{type(self).__name__} has no chunk form")

    def turn_start(self, p: str, caches: Params, start) -> Params:
        """Nothing to put back: a turn from ``start`` writes positions
        from ``start`` on and reads none it has not written."""
        return {}

    def padded(self, p: str, rows, total: int, dtype) -> Params:
        """``full``'s rows as :func:`prefill` hands them out: leaves
        (B, total, ...), zeros beyond the sequence."""
        out = {}
        for name, leaf in zip(self.names(p), rows):
            pad = ((0, 0), (0, total - leaf.shape[1])) \
                + ((0, 0),) * (leaf.ndim - 2)
            out[name] = jnp.pad(leaf, pad).astype(dtype)
        return out


def _with_snapshot(leaves: Params, p: str) -> Params:
    """``leaves`` (a layer's caches that a turn changes for good) with
    room for one snapshot of them: ``<leaf>0``, and ``L{i}_at0``, the
    position the snapshot was taken at (-1: none yet)."""
    return {**leaves,
            **{n + "0": jnp.zeros_like(x) for n, x in leaves.items()},
            f"{p}_at0": jnp.full((), -1, jnp.int32)}


def _taken_back(names, p: str, caches: Params, start) -> Params:
    """:meth:`_Kind.turn_start` for leaves ``names`` kept by
    :func:`_with_snapshot`: where the snapshot is of ``start`` (the turn
    before started here too) it is put back; else the leaves stand at
    ``start`` (the caller's word, as for every cache) and are what is
    kept. Either way both then hold the caches as of ``start``."""
    same = caches[f"{p}_at0"] == start
    out = {f"{p}_at0": jnp.asarray(start, jnp.int32)}
    for n in names:
        out[n] = out[n + "0"] = jnp.where(same, caches[n + "0"], caches[n])
    return out


def _rolled(c, p_len: int, cache_len: int):
    """(B, H, S, D) rows of positions 0 .. S-1, of which the first
    ``p_len`` are a prompt's, as a rolling buffer of ``cache_len``
    slots holds them."""
    # positions 0..p_len-1 land in slots 0..p_len-1 and the rows are
    # zero beyond them: a plain truncation IS the rolling layout
    src = slice(cache_len)
    if p_len >= cache_len:
        # fold the prompt into it: slot j holds the LAST prompt position
        # that is j (mod w). Scale entries (kv_q8) are (B, H_kv, S): the
        # same slot axis, the same fold.
        j = jnp.arange(cache_len)
        src = p_len - 1 - ((p_len - 1 - j) % cache_len)
    return c[:, :, src]


def _differential(params: Params, p: str, a, depth: int, eps: float):
    """Differential attention's second half (Ye et al. 2024,
    arXiv:2410.05258): ``a`` (B, Q, pairs, 2 g, 2 D) float32 holds, for
    each key/value pair-head and each of its ``g`` query pairs, the two
    softmaxes' sums over the 2 D-wide values ``[v1 | v2]``. Returns
    ``RMSNorm(a1 - lam a2) * (1 - lam0)`` as (B, Q, pairs * g * 2 D):
    ``lam = exp(lq1 . lk1) - exp(lq2 . lk2) + lam0`` from the layer's
    four vectors, ``lam0 = 0.8 - 0.6 exp(-0.3 depth)``."""
    b, q_len, pairs, rows, width = a.shape
    lam0 = 0.8 - 0.6 * float(np.exp(-0.3 * depth))
    lq1, lk1, lq2, lk2 = params[f"{p}_lam"].astype(jnp.float32)
    lam = jnp.exp(jnp.sum(lq1 * lk1)) - jnp.exp(jnp.sum(lq2 * lk2)) + lam0
    a = a.reshape(b, q_len, pairs, rows // 2, 2, width)
    diff = a[..., 0, :] - lam * a[..., 1, :]
    diff = _rms_norm(diff, params[f"{p}_sub_g"].astype(jnp.float32), eps)
    return (diff * (1.0 - lam0)).reshape(b, q_len, -1)


def _pair_queries(q):
    """(B, L, pairs, 2 g, D) queries, rows alternating the pair's first
    and second query, as 2 D-wide rows against ``[k1 | k2]``: ``[q1 |
    0]`` and ``[0 | q2]``."""
    first = (jnp.arange(q.shape[3]) % 2 == 0)[:, None]
    zero = jnp.zeros_like(q)
    return jnp.concatenate([jnp.where(first, q, zero),
                            jnp.where(first, zero, q)], axis=-1)


def _biased(params: Params, name: str, y):
    """``y @ W`` and the bias where the dict holds one."""
    out = _mm(params, f"{name}_W", y)
    bias = params.get(f"{name}_b")
    return out if bias is None else out + bias


@dataclasses.dataclass(frozen=True)
class GroupedQuery(_Kind):
    """Grouped-query attention over (k, v) caches: a fused qkv
    projection, rope on q and k, the caller's attention over a full
    sequence and ``ops/decode.py``'s over the cache. Public caches are
    (B, S, H_kv, D); the scan carries (B, H_kv, S, D) — per-(batch,
    head) rows contiguous, the ops/decode.py layout contract —, int8
    with f32 scales a row (``L{i}_{k,v}s``) under ``kv_q8``, and a
    rolling buffer of ``window`` slots where the window is shorter than
    the decode.

    ``window`` is the layer's own (0 = full causal); ``nope``: q and k
    are not rotated, whatever ``cfg.rope`` says (the full layers of
    ``cfg.attn_pattern``). In a stack with a pattern the cache has ONE
    form, (B, H_kv, S, D), rolling where windowed, as a differential
    pair's has (below), and the two kinds run under ``lm.swa`` and
    ``lm.full``. ``differential``
    (arXiv:2410.05258): heads pair up, ``(q1, q2)`` = heads ``(2 j, 2 j
    + 1)`` and ``(k1, k2)``, ``(v1, v2)`` = kv heads ``(2 m, 2 m + 1)``,
    query pair ``j`` on kv pair ``j // g``; the layer's output is
    ``RMSNorm(softmax(q1 k1) [v1|v2] - lam softmax(q2 k2) [v1|v2])``
    (:func:`_differential`; ``depth`` sets ``lam0``). A pair is held as
    ONE row twice as wide, ``[k1|k2]`` and ``[v1|v2]``, and meets the
    queries ``[q1|0]`` and ``[0|q2]``: the same kernel at H_kv / 2
    heads, 2 g query rows and 2 D. Such a cache has one form,
    (B, H_kv / 2, S, 2 D), rolling where windowed: prefill hands it out
    as the scan carries it, it has no int8 form, and its attention over
    a full sequence is its own (the caller's ``attn_fn`` knows one
    window and one width). ``shares``: later layers read this layer's
    keys and values (:class:`Cross`), so a full-sequence forward hands
    them on."""
    window: int = 0
    differential: bool = False
    depth: int = 0
    shares: bool = False
    nope: bool = False

    leaves = ("k", "v")

    @property
    def one_form(self) -> bool:
        return self.differential or bool(self.cfg.attn_pattern)

    def _shape(self) -> tuple:
        """(kv rows, query rows a kv row, a row's width, the scores'
        scale or None for the width's own) as attention meets them."""
        cfg, hkv, hd = self.cfg, kv_heads(self.cfg), head_dim(self.cfg)
        g = cfg.n_heads // hkv
        if self.differential:
            return hkv // 2, 2 * g, 2 * hd, hd ** -0.5
        return hkv, g, hd, None

    def init(self, keys, dtype, p: str) -> Params:
        cfg, hd = self.cfg, head_dim(self.cfg)
        cols = (cfg.n_heads + 2 * kv_heads(cfg)) * hd
        out = {f"{p}_qkv_W": _dense(next(keys), (cfg.d_model, cols), dtype),
               f"{p}_out_W": _dense(next(keys), (cfg.n_heads * hd,
                                                 cfg.d_model), dtype)}
        if self.differential:
            out.update(_differential_init(next(keys), dtype, p, hd))
            out[f"{p}_qkv_b"] = jnp.zeros((cols,), dtype)
            out[f"{p}_out_b"] = jnp.zeros((cfg.d_model,), dtype)
        return out

    def project(self, params: Params, p: str, y, pos):
        """q (B, L, H, hd) and the rows (k, v), each (B, L, H_kv, hd),
        of ``y`` at ``pos``. With rope k is the ROTATED one: what
        attention consumes and what the cache stores. Differential: q
        (B, L, H_kv / 2, 2 g, 2 hd) and rows (B, L, H_kv / 2, 2 hd)."""
        cfg = self.cfg
        b, l, _ = y.shape
        h, hkv, hd = cfg.n_heads, kv_heads(cfg), head_dim(cfg)
        qkv = _biased(params, f"{p}_qkv", y)  # (B, L, (H+2Hkv)·hd) MXU
        q = qkv[..., :h * hd].reshape(b, l, h, hd)
        k = qkv[..., h * hd:(h + hkv) * hd].reshape(b, l, hkv, hd)
        v = qkv[..., (h + hkv) * hd:].reshape(b, l, hkv, hd)
        if cfg.rope and not self.nope:
            q = _rope(q, pos, cfg.rope_base)
            k = _rope(k, pos, cfg.rope_base)
        if self.differential:
            rows, g, width, _ = self._shape()
            q = _pair_queries(q.reshape(b, l, rows, g, hd))
            k, v = k.reshape(b, l, rows, width), v.reshape(b, l, rows, width)
        return q, (k, v)

    def _out(self, params: Params, p: str, a, y):
        """Attention's sums ``a`` (B, Q, ...) through what follows them:
        the differential half where the kind has it, the out
        projection."""
        if self.differential:
            a = _differential(params, p, a.astype(jnp.float32), self.depth,
                              self.cfg.norm_eps)
        a = a.astype(y.dtype).reshape(*y.shape[:2], -1)
        return _biased(params, f"{p}_out", a)

    def _scope(self):
        if not self.one_form:
            return contextlib.nullcontext()
        return scope("lm.swa" if self.window else "lm.full")

    def full(self, params: Params, p: str, y, pos, attn_fn, handed=None):
        """Through the caller's ``attn_fn`` (one window for every layer),
        or for a one-form cache attention of its own over its rows, at
        the layer's window."""
        with self._scope():
            q, rows = self.project(params, p, y, pos)
            if not self.one_form:
                return self._out(params, p, attn_fn(q, *rows), y), rows
            rows_kv, g, width, scale = self._shape()
            k, v = (jnp.transpose(row, (0, 2, 1, 3)) for row in rows)
            if self.shares:
                handed["kv"] = (k, v)
            a = cache_attention(q.reshape(*y.shape[:2], rows_kv, g, width),
                                k, v, pos, pos, window=self.window,
                                scale=scale)
            return self._out(params, p, a, y), rows

    def chunk(self, params: Params, p: str, y, pos, caches: Params, start,
              handed=None):
        """Over the scan's (B, H_kv, S, D) caches. A whole cache takes
        the chunk's rows, then the queries see it up to their own
        positions. A rolling buffer is attended BEFORE it is written
        (the chunk's later rows take the slots of positions that its
        earlier queries still see): the buffer as it stands, beside the
        chunk's own rows."""
        with self._scope():
            q, rows = self.project(params, p, y, pos)
            rows_kv, g, width, scale = self._shape()
            b, c = y.shape[:2]
            q = q.reshape(b, c, rows_kv, g, width)
            k, v = (jnp.transpose(row, (0, 2, 1, 3)) for row in rows)
            kn, vn = self.names(p)
            cache_len = caches[kn].shape[2]
            if not _rolls(self, cache_len):
                caches = _put(caches, {kn: k, vn: v}, (0, 0, start))
                a = cache_attention(
                    q, caches[kn], caches[vn], pos, jnp.arange(cache_len),
                    window=self.window, scale=scale, live=pos[-1] + 1)
                return self._out(params, p, a, y), caches
            slot = jnp.arange(cache_len)
            held = start - 1 - (start - 1 - slot) % cache_len
            beside = {n: jnp.concatenate([caches[n], row], axis=2)
                      for n, row in ((kn, k), (vn, v))}
            a = cache_attention(
                q, beside[kn], beside[vn], pos,
                jnp.concatenate([held, pos.astype(held.dtype)]),
                window=self.window, scale=scale)
            # slot j then holds the last position that is j (mod w):
            # one of the chunk's rows, or what it held
            last = pos[-1]
            then = last - (last - slot) % cache_len
            take = jnp.where(then >= start, cache_len + then - start, slot)
            caches = {**caches, **{n: jnp.take(beside[n], take, axis=2)
                                   for n in (kn, vn)}}
            return self._out(params, p, a, y), caches

    def step(self, params: Params, p: str, y, t, caches: Params,
             kv_q8: bool, handed=None):
        with self._scope():
            q, rows = self.project(params, p, y, t[None])
            rows_kv, g, width, scale = self._shape()
            b = y.shape[0]
            kn, vn = self.names(p)
            cache_len = caches[kn].shape[2]
            roll = _rolls(self, cache_len)
            # (B, 1, Hkv, D) → (B, Hkv, 1, D) cache-layout row
            k, v = (jnp.transpose(row, (0, 2, 1, 3)) for row in rows)
            # head index = (kv head, group member), kv-head major —
            # the grouping decode_attention's (B, Hkv, G, D) q expects
            q = q.reshape(b, rows_kv, g, width)
            slot = t % cache_len if roll else t
            scales = {}
            if kv_q8:
                (k, ks_row), (v, vs_row) = quantize_kv(k), quantize_kv(v)
                caches = _put(caches, {kn + "s": ks_row, vn + "s": vs_row},
                              (0, 0, slot))
                scales = {"k_scale": caches[kn + "s"],
                          "v_scale": caches[vn + "s"]}
            caches = _put(caches, {kn: k, vn: v}, (0, 0, slot))
            # fused decode attention (ops/decode.py): flash-decode
            # kernel on TPU, the identical einsum+mask+softmax
            # composition elsewhere. A cache that does not roll holds the
            # whole decode inside the window, so slot<=t IS the mask.
            a = decode_attention(q, caches[kn], caches[vn], t, roll=roll,
                                 backend="auto", scale=scale, **scales)
            return self._out(params, p, a[:, None], y), (caches, None)

    def empty(self, p: str, b: int, total: int, dtype,
              kv_q8: bool = False) -> Params:
        rows_kv, _, width, _ = self._shape()
        if self.one_form:
            _no_int8(kv_q8)
        shape = (b, rows_kv, _cache_shape(self, total)[1])
        out = {n: jnp.zeros(shape + (width,),
                            jnp.int8 if kv_q8 else dtype)
               for n in self.names(p)}
        if kv_q8:
            out.update({n + "s": jnp.zeros(shape, jnp.float32)
                        for n in self.names(p)})
        return out

    def padded(self, p: str, rows, total: int, dtype) -> Params:
        if not self.one_form:
            return super().padded(p, rows, total, dtype)
        # the one form: the scan's layout, rolled where it rolls
        roll, cache_len = _cache_shape(self, total)
        out = {}
        for name, leaf in zip(self.names(p), rows):
            p_len = leaf.shape[1]
            c = jnp.pad(jnp.transpose(leaf, (0, 2, 1, 3)).astype(dtype), (
                (0, 0), (0, 0), (0, max(0, cache_len - p_len)), (0, 0)))
            out[name] = _rolled(c, p_len, cache_len) if roll else c
        return out

    def scanned(self, p: str, caches: Params, p_len: int, total: int,
                kv_q8: bool = False) -> Params:
        """One transpose at the boundary, not one per step; quantized
        under ``kv_q8``; folded into the rolling layout where the
        window is shorter than ``total``. (A one-form cache is handed
        out so already.)"""
        roll, cache_len = _cache_shape(self, total)
        if self.one_form:
            _no_int8(kv_q8)
            out = {n: caches[n] for n in self.names(p)}
            return _with_snapshot(out, p) if roll else out
        out = {n: jnp.transpose(caches[n], (0, 2, 1, 3))
               for n in self.names(p)}
        if kv_q8:
            quant = {}
            for n, c in out.items():
                quant[n], quant[n + "s"] = quantize_kv(c)
            out = quant
        if not roll:
            return out
        return {n: _rolled(c, p_len, cache_len) for n, c in out.items()}

    def turn_start(self, p: str, caches: Params, start) -> Params:
        """A one-form layer's rolling buffer keeps a snapshot (a plain
        one is the caller's to copy, as ``decode_from`` says)."""
        if f"{p}_at0" not in caches:
            return {}
        return _taken_back(self.names(p), p, caches, start)


def _differential_init(key, dtype, p: str, hd: int) -> Params:
    """The differential half's own weights: the four ``lam`` vectors
    (lq1, lk1, lq2, lk2; N(0, 0.1) as the paper draws them) and the
    gain of the norm over a pair's 2 hd values."""
    return {f"{p}_lam": 0.1 * jax.random.normal(key, (4, hd), dtype),
            f"{p}_sub_g": jnp.ones((2 * hd,), dtype)}


def _no_int8(kv_q8: bool) -> None:
    if kv_q8:
        raise ValueError("kv_q8 quantizes plain grouped-query caches; a "
                         "latent cache, a differential pair's, an "
                         "attention pattern's and a recurrent state have "
                         "no int8 form")


# query rows (batch x positions) of a latent-attention forward over a
# full sequence that meet the cache at a time. With the indexer its
# (queries, heads, keys) scores exist for one such block; without it a
# block's queries in the latent's basis (H x R values each) and their
# float32 sums do
_QUERY_ROWS_INDEXED = 64
_QUERY_ROWS_WHOLE = 2048


class Latent(_Kind):
    """Multi-head latent attention (``cfg.latent``, a
    ``LatentAttention``): a position caches the row ``[c_kv | k_rope]``
    (B, S, kv_rank + rope_dim) for all heads, and attention runs in the
    absorbed form, where that row is key and value at once. Two parts
    are the model's to have or to lack. A query latent (``q_rank``):
    queries projected through a normed low-rank latent, or straight
    from the stream. An indexer (``index_top_k``): a second cached leaf,
    its key (B, S, index_dim), and a query attends the rows the indexer
    selects (``ops/sparse_mla.py``); without one the cache has the one
    leaf and a query reads every row up to its own
    (``ops/mla_decode.py``). ``qk_norm`` is an RMSNorm of every head's
    query before the rope. Without the config's ``rope`` (positions
    "none") the ``rope_dim`` part of the queries and of the shared key
    is carried unrotated. The scan carries the caches as prefill hands
    them out, in the model's own type: no latent cache has an int8 form
    (``kv_q8`` is refused), with or without the indexer."""
    one_form = True

    @property
    def leaves(self) -> tuple:
        return ("ckv", "ik") if self.cfg.latent.index_top_k else ("ckv",)

    def init(self, keys, dtype, p: str) -> Params:
        la, d, h = self.cfg.latent, self.cfg.d_model, self.cfg.n_heads
        q_out = h * (la.nope_dim + la.rope_dim)
        shapes = ({"qa_W": (d, la.q_rank), "qb_W": (la.q_rank, q_out)}
                  if la.q_rank else {"q_W": (d, q_out)})
        shapes.update({
            "kva_W": (d, la.kv_rank + la.rope_dim),
            "kvb_W": (la.kv_rank, h * (la.nope_dim + la.v_dim)),
            "out_W": (h * la.v_dim, d)})
        if la.index_top_k:
            shapes.update({
                "iq_W": (la.q_rank, la.index_heads * la.index_dim),
                "ik_W": (d, la.index_dim),
                "iw_W": (d, la.index_heads)})
        out = {f"{p}_{n}": _dense(k, shape, dtype)
               for (n, shape), k in zip(shapes.items(), jax.random.split(
                   next(keys), len(shapes)))}
        if la.q_rank:
            out[f"{p}_qa_g"] = jnp.ones((la.q_rank,), dtype)
        if la.qk_norm:
            out[f"{p}_q_g"] = jnp.ones((la.nope_dim + la.rope_dim,), dtype)
        out[f"{p}_kv_g"] = jnp.ones((la.kv_rank,), dtype)
        if la.index_top_k:
            out[f"{p}_ik_g"] = jnp.ones((la.index_dim,), dtype)
            out[f"{p}_ik_b"] = jnp.zeros((la.index_dim,), dtype)
        return out

    def _rope_head(self, x, pos):
        """Rope (YaRN frequencies) on the first ``rope_dim`` values of
        (B, L, H*, D) and none on the rest (none at all without the
        config's ``rope``)."""
        la, base = self.cfg.latent, self.cfg.rope_base
        if not self.cfg.rope:
            return x
        turned = _rope(x[..., :la.rope_dim], pos, base,
                       _yarn_freqs(la, base))
        return jnp.concatenate([turned, x[..., la.rope_dim:]], axis=-1)

    def rows(self, params: Params, p: str, y, pos) -> tuple:
        """What ``y`` (B, L, d) at ``pos`` caches, leaf by leaf: the row
        ``[c_kv | k_rope]`` (the normed latent, the rotated rope key
        that all heads share) and, with an indexer, its key (LayerNorm,
        rope on its first ``rope_dim`` values)."""
        cfg, la = self.cfg, self.cfg.latent
        with scope("lm.mla"):
            kv = _mm(params, f"{p}_kva_W", y)
            c = _rms_norm(kv[..., :la.kv_rank], params[f"{p}_kv_g"],
                          cfg.norm_eps)
            k_r = self._rope_head(kv[..., None, la.kv_rank:], pos)[..., 0, :]
            ckv = jnp.concatenate([c, k_r], axis=-1)
        if not la.index_top_k:
            return (ckv,)
        with scope("lm.indexer"):
            ik = _layer_norm(_mm(params, f"{p}_ik_W", y),
                             params[f"{p}_ik_g"], params[f"{p}_ik_b"],
                             cfg.norm_eps)
            ik = self._rope_head(ik[..., None, :], pos)[..., 0, :]
        return ckv, ik.astype(y.dtype)

    def attend(self, params: Params, p: str, y, pos, ckv, ik=None):
        """Latent attention of the queries ``y`` (B, Q, d) at positions
        ``pos`` over caches that hold these positions' own rows already.
        Absorbed form: ``q_nope`` is taken into the latent's basis
        through ``kvb_W``'s key half, a cached row is key and value at
        once, and the sum comes out through its value half. Returns (out
        (B, Q, d), the positions the indexer selected (B, Q, K), -1
        where the query sees fewer than K; None where there is no
        indexer and every row up to the query's own is read)."""
        cfg, la, h = self.cfg, self.cfg.latent, self.cfg.n_heads
        b, q_len, _ = y.shape
        freqs = _yarn_freqs(la, cfg.rope_base)
        m = 0.1 * la.mscale_all_dim * np.log(la.rope_factor) + 1.0
        scale = float((la.nope_dim + la.rope_dim) ** -0.5 * m * m)
        w_kv = params[f"{p}_kvb_W"].reshape(la.kv_rank, h,
                                            la.nope_dim + la.v_dim)
        with scope("lm.mla"):
            if la.q_rank:
                c_q = _rms_norm(_mm(params, f"{p}_qa_W", y),
                                params[f"{p}_qa_g"], cfg.norm_eps)
                q = _mm(params, f"{p}_qb_W", c_q)
            else:
                c_q, q = None, _mm(params, f"{p}_q_W", y)
            q = q.reshape(b, q_len, h, la.nope_dim + la.rope_dim)
            if la.qk_norm:
                q = _rms_norm(q, params[f"{p}_q_g"], cfg.norm_eps)
            q_rope = q[..., la.nope_dim:]
            if cfg.rope:
                q_rope = _rope(q_rope, pos, cfg.rope_base, freqs)
            q_lat = jnp.einsum("bqhn,chn->bqhc", q[..., :la.nope_dim],
                               w_kv[..., :la.nope_dim])
            q = jnp.concatenate([q_lat, q_rope], axis=-1)
        if la.index_top_k:
            with scope("lm.indexer"):
                q_i = _mm(params, f"{p}_iq_W", c_q).reshape(
                    b, q_len, la.index_heads, la.index_dim)
                q_i = self._rope_head(q_i, pos)
                w = _mm(params, f"{p}_iw_W", y) * float(
                    la.index_heads ** -0.5 * la.index_dim ** -0.5)
                idx, valid = _sparse.select_top_k(
                    _sparse.index_scores(q_i, w, ik, pos), la.index_top_k)
            with scope("lm.sparse"):
                o_lat = _sparse.sparse_latent_attention(
                    q, ckv, idx, valid, scale=scale, v_rank=la.kv_rank)
        else:
            with scope("lm.latent"):
                if q_len == 1:      # a decode step: the flash-decode kernel
                    o_lat = mla_decode_attention(
                        q[:, 0], ckv, pos[0], v_rank=la.kv_rank,
                        scale=scale, backend="auto")[:, None]
                else:
                    o_lat = mla_causal_attention(
                        q, ckv, pos, v_rank=la.kv_rank, scale=scale)
        with scope("lm.mla"):
            o = jnp.einsum("bqhc,chv->bqhv", o_lat.astype(y.dtype),
                           w_kv[..., la.nope_dim:])
            out = _mm(params, f"{p}_out_W",
                      o.reshape(b, q_len, h * la.v_dim))
        return out, jnp.where(valid, idx, -1) if la.index_top_k else None

    def attend_blocked(self, params: Params, p: str, y, pos, *caches):
        """:meth:`attend`'s output for every position of a sequence, a
        block of queries at a time."""
        b, l, d = y.shape
        rows = (_QUERY_ROWS_INDEXED if self.cfg.latent.index_top_k
                else _QUERY_ROWS_WHOLE)
        block = min(max(1, rows // b), l)
        if l == block:
            return self.attend(params, p, y, pos, *caches)[0]
        n = -(-l // block)
        pad = n * block - l             # padded queries repeat the last
        yb = jnp.pad(y, ((0, 0), (0, pad), (0, 0)), mode="edge")
        pb = jnp.pad(pos, (0, pad), mode="edge")
        out = lax.map(
            lambda blk: self.attend(params, p, blk[0], blk[1], *caches)[0],
            (yb.reshape(b, n, block, d).transpose(1, 0, 2, 3),
             pb.reshape(n, block)))
        return out.transpose(1, 0, 2, 3).reshape(b, n * block, d)[:, :l]

    def full(self, params: Params, p: str, y, pos, attn_fn):
        """Over what it caches, in its own form; ``attn_fn`` is left
        aside."""
        rows = self.rows(params, p, y, pos)
        return self.attend_blocked(params, p, y, pos, *rows), rows

    def _written(self, params: Params, p: str, y, pos, caches: Params,
                 start):
        """``caches`` with the rows of ``y`` at ``pos`` (from ``start``
        on) in them, and this layer's own."""
        caches = _put(caches, dict(zip(
            self.names(p), self.rows(params, p, y, pos))), (0, start))
        return caches, [caches[name] for name in self.names(p)]

    def chunk(self, params: Params, p: str, y, pos, caches: Params, start):
        caches, mine = self._written(params, p, y, pos, caches, start)
        return self.attend_blocked(params, p, y, pos, *mine), caches

    def step(self, params: Params, p: str, y, t, caches: Params,
             kv_q8: bool):
        """(``kv_q8`` was refused where the caches were made.)"""
        caches, mine = self._written(params, p, y, t[None], caches, t)
        out, selected = self.attend(params, p, y, t[None], *mine)
        return out, (caches, selected)

    def empty(self, p: str, b: int, total: int, dtype,
              kv_q8: bool = False) -> Params:
        la = self.cfg.latent
        _no_int8(kv_q8)
        return {name: jnp.zeros((b, total, width), dtype)
                for name, width in zip(self.names(p), (
                    la.kv_rank + la.rope_dim, la.index_dim))}

    def scanned(self, p: str, caches: Params, p_len: int, total: int,
                kv_q8: bool = False) -> Params:
        _no_int8(kv_q8)
        return {name: caches[name] for name in self.names(p)}


def _inner(cfg) -> tuple:
    """(inner width E, state N, taps K, dt rank R) of the stack's
    state-space layers."""
    hy = cfg.hybrid
    return (hy.ssm_expand * cfg.d_model, hy.ssm_state, hy.ssm_conv,
            hy.ssm_rank or -(-cfg.d_model // 16))


@dataclasses.dataclass(frozen=True)
class StateSpace(_Kind):
    """A Mamba-1 selective state-space mixer (``cfg.hybrid`` has its
    sizes; ``ops/ssm.py`` the scan):

        [u, z] = y W_in;  u = silu(conv1d(u) + b);  [dt, B, C] = u W_x
        delta = softplus(dt W_dt + b_dt);  A = -exp(A_log)
        h_t = exp(delta_t A) h_{t-1} + (delta_t u_t) outer B_t
        s_t = h_t C_t + D u_t;  out = (s_t silu(z_t)) W_out

    What a session caches has no axis of positions: the convolution's
    tail ``conv`` (B, K - 1, E), the last inputs before the next
    position, in the model's type, and the state ``ssm`` (B, N, E) in
    float32. ``hands``: the layer leaves ``s``, the scan's output
    BEFORE the ``z`` gate, as ``handed["memory"]`` for the gated memory
    units after it."""
    hands: bool = False

    leaves = ("conv", "ssm")
    shares = True
    one_form = True

    def init(self, keys, dtype, p: str) -> Params:
        d = self.cfg.d_model
        e, n, taps, r = _inner(self.cfg)
        k = iter(jax.random.split(next(keys), 5))
        # Mamba's own: A_log = log(1..N) a channel, the step's bias the
        # inverse softplus of a log-uniform draw in [0.001, 0.1]
        dt = jnp.exp(jax.random.uniform(next(k), (e,), jnp.float32,
                                        np.log(1e-3), np.log(1e-1)))
        f32 = jnp.float32
        return {
            f"{p}_in_W": _dense(next(k), (d, 2 * e), dtype),
            f"{p}_conv_W": _dense(next(k), (taps, e), dtype),
            f"{p}_conv_b": jnp.zeros((e,), dtype),
            f"{p}_x_W": _dense(next(k), (e, r + 2 * n), dtype),
            f"{p}_dt_W": _dense(next(k), (r, e), dtype),
            f"{p}_dt_b": (dt + jnp.log(-jnp.expm1(-dt))).astype(f32),
            f"{p}_A_log": jnp.broadcast_to(jnp.log(jnp.arange(
                1, n + 1, dtype=f32))[:, None], (n, e)),
            f"{p}_D": jnp.ones((e,), f32),
            f"{p}_out_W": _dense(next(keys), (e, d), dtype)}

    def _mix(self, params: Params, p: str, y, tail, h, handed):
        """The mixer over ``y`` (B, L, d) from the tail and state before
        it. Returns (out (B, L, d), the tail and the state after)."""
        e, n, _, r = _inner(self.cfg)
        with scope("lm.ssm"):
            uz = _mm(params, f"{p}_in_W", y)
            u, tail = _ssm.causal_conv(uz[..., :e], tail,
                                       params[f"{p}_conv_W"],
                                       params[f"{p}_conv_b"])
            u = jax.nn.silu(u).astype(y.dtype)
            dbc = _mm(params, f"{p}_x_W", u)
            delta = jax.nn.softplus(
                jnp.dot(dbc[..., :r], params[f"{p}_dt_W"],
                        preferred_element_type=jnp.float32)
                + params[f"{p}_dt_b"])
            a = -jnp.exp(params[f"{p}_A_log"].astype(jnp.float32))
            args = (a, dbc[..., r:r + n], dbc[..., r + n:], params[f"{p}_D"])
            if y.shape[1] == 1:     # a decode step
                h, s = _ssm.selective_step(h, u[:, 0], delta[:, 0], args[0],
                                           args[1][:, 0], args[2][:, 0],
                                           args[3])
                s = s[:, None]
            else:
                h, s = _ssm.selective_scan(h, u, delta, *args)
            if self.hands:
                handed["memory"] = s.astype(y.dtype)
            gated = s * jax.nn.silu(uz[..., e:].astype(jnp.float32))
            return (_mm(params, f"{p}_out_W", gated.astype(y.dtype)),
                    tail.astype(y.dtype), h)

    def full(self, params: Params, p: str, y, pos, attn_fn, handed):
        fresh = self.empty(p, y.shape[0], 0, y.dtype)
        out, tail, h = self._mix(params, p, y, *fresh.values(), handed)
        return out, (tail, h)

    def chunk(self, params: Params, p: str, y, pos, caches: Params, start,
              handed):
        cn, sn = self.names(p)
        out, tail, h = self._mix(params, p, y, caches[cn], caches[sn], handed)
        return out, {**caches, cn: tail, sn: h}

    def step(self, params: Params, p: str, y, t, caches: Params,
             kv_q8: bool, handed):
        out, caches = self.chunk(params, p, y, t[None], caches, t, handed)
        return out, (caches, None)

    def empty(self, p: str, b: int, total: int, dtype,
              kv_q8: bool = False) -> Params:
        _no_int8(kv_q8)
        e, n, taps, _ = _inner(self.cfg)
        cn, sn = self.names(p)
        return {cn: jnp.zeros((b, taps - 1, e), dtype),
                sn: jnp.zeros((b, n, e), _ssm.STATE_DTYPE)}

    def padded(self, p: str, rows, total: int, dtype) -> Params:
        return dict(zip(self.names(p), rows))

    def scanned(self, p: str, caches: Params, p_len: int, total: int,
                kv_q8: bool = False) -> Params:
        _no_int8(kv_q8)
        return _with_snapshot({name: caches[name] for name in self.names(p)},
                              p)

    def turn_start(self, p: str, caches: Params, start) -> Params:
        return _taken_back(self.names(p), p, caches, start)


def _l2_heads(x, eps: float = 1e-6):
    """Every head's vector (last axis) over its L2 norm, float32."""
    x = x.astype(jnp.float32)
    return x * lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + eps)


class KimiDelta(_Kind):
    """Kimi Delta Attention (Kimi Linear, arXiv:2510.26692), the linear
    layers of a stack whose ``attn_pattern`` says ``"kda"``;
    ``cfg.linear`` has the sizes (H heads of D, P = H D, the
    convolution's taps; the gates' low rank is D). With ``y`` the layer's
    normed input:

        q, k, v = silu(conv1d(y W_qkv))             (P each, depthwise, causal)
        q = l2norm(q) D^-1/2, k = l2norm(k)         (per head)
        g = -exp(A_log) softplus(y W_fa W_fb + dt_b)  (per key channel)
        beta = sigmoid(y W_b)                       (per head)
        S_t = (I - beta k k^T) diag(exp g) S_{t-1} + beta k v^T,  o = S_t^T q
        out = (RMSNorm_D(o) o_g * sigmoid(y W_ga W_gb)) W_out

    ``ops/kda.py`` runs the rule: chunked over a sequence, a Pallas step
    at decode. What a session caches has no axis of positions: the
    convolution's tail ``conv`` (B, taps - 1, 3 P), the last projected
    inputs in the model's type, and the state ``state`` (B, H, D, D) in
    float32, with ONE snapshot in the scan (:func:`_with_snapshot`).
    Everything the layer does runs under ``lm.kda``."""
    leaves = ("conv", "state")
    one_form = True

    def _sizes(self) -> tuple:
        la = self.cfg.linear
        return la.heads, la.head_dim, la.conv

    def init(self, keys, dtype, p: str) -> Params:
        d = self.cfg.d_model
        h, dk, taps = self._sizes()
        width = h * dk
        k = iter(jax.random.split(next(keys), 9))
        f32 = jnp.float32
        # the published initialisation: A ~ U(1, 16) a head, the decay's
        # bias the inverse softplus of a log-uniform draw in [1e-3, 0.1]
        dt = jnp.exp(jax.random.uniform(next(k), (width,), f32,
                                        np.log(1e-3), np.log(1e-1)))
        return {
            f"{p}_qkv_W": _dense(next(k), (d, 3 * width), dtype),
            f"{p}_conv_W": _dense(next(k), (taps, 3 * width), dtype),
            f"{p}_fa_W": _dense(next(k), (d, dk), dtype),
            f"{p}_fb_W": _dense(next(k), (dk, width), dtype),
            f"{p}_dt_b": (dt + jnp.log(-jnp.expm1(-dt))).astype(f32),
            f"{p}_A_log": jnp.log(jax.random.uniform(next(k), (h,), f32,
                                                     1.0, 16.0)),
            f"{p}_b_W": _dense(next(k), (d, h), dtype),
            f"{p}_ga_W": _dense(next(k), (d, dk), dtype),
            f"{p}_gb_W": _dense(next(k), (dk, width), dtype),
            f"{p}_o_g": jnp.ones((dk,), dtype),
            f"{p}_out_W": _dense(next(keys), (width, d), dtype)}

    def _low_rank(self, params: Params, p: str, name: str, y):
        """``y W_{name}a W_{name}b`` in float32."""
        return jnp.dot(_mm(params, f"{p}_{name}a_W", y),
                       params[f"{p}_{name}b_W"],
                       preferred_element_type=jnp.float32)

    def _mix(self, params: Params, p: str, y, tail, state):
        """The mixer over ``y`` (B, L, d) from the tail and state before
        it. Returns (out (B, L, d), the tail and the state after)."""
        h, dk, _ = self._sizes()
        b, l, _ = y.shape
        f32 = jnp.float32
        with scope("lm.kda"):
            qkv = _mm(params, f"{p}_qkv_W", y)
            c, tail = _ssm.causal_conv(qkv, tail, params[f"{p}_conv_W"],
                                       jnp.zeros((), f32))
            q, k, v = (x.reshape(b, l, h, dk) for x in
                       jnp.split(jax.nn.silu(c), 3, axis=-1))
            q, k = _l2_heads(q) * dk ** -0.5, _l2_heads(k)
            g = (-jnp.exp(params[f"{p}_A_log"].astype(f32))[:, None]
                 * jax.nn.softplus(self._low_rank(params, p, "f", y)
                                   + params[f"{p}_dt_b"]).reshape(
                                       b, l, h, dk))
            beta = jax.nn.sigmoid(_mm(params, f"{p}_b_W", y).astype(f32))
            if l == 1:      # a decode step: the kernel
                state, o = _kda.kda_step(state, q[:, 0], k[:, 0], v[:, 0],
                                         g[:, 0], beta[:, 0])
                o = o[:, None]
            else:
                o, state = _kda.kda_chunked(q, k, v, g, beta, state)
            gate = jax.nn.sigmoid(self._low_rank(params, p, "g", y))
            o = (_rms_norm(o, params[f"{p}_o_g"].astype(f32),
                           self.cfg.norm_eps).reshape(b, l, h * dk) * gate)
            return (_mm(params, f"{p}_out_W", o.astype(y.dtype)),
                    tail.astype(y.dtype), state)

    def full(self, params: Params, p: str, y, pos, attn_fn):
        fresh = self.empty(p, y.shape[0], 0, y.dtype)
        out, tail, state = self._mix(params, p, y, *fresh.values())
        return out, (tail, state)

    def chunk(self, params: Params, p: str, y, pos, caches: Params, start):
        cn, sn = self.names(p)
        out, tail, state = self._mix(params, p, y, caches[cn], caches[sn])
        return out, {**caches, cn: tail, sn: state}

    def step(self, params: Params, p: str, y, t, caches: Params,
             kv_q8: bool):
        """(``kv_q8`` was refused where the caches were made.)"""
        out, caches = self.chunk(params, p, y, t[None], caches, t)
        return out, (caches, None)

    def empty(self, p: str, b: int, total: int, dtype,
              kv_q8: bool = False) -> Params:
        _no_int8(kv_q8)
        h, dk, taps = self._sizes()
        cn, sn = self.names(p)
        return {cn: jnp.zeros((b, taps - 1, 3 * h * dk), dtype),
                sn: jnp.zeros((b, h, dk, dk), _kda.STATE_DTYPE)}

    def padded(self, p: str, rows, total: int, dtype) -> Params:
        return dict(zip(self.names(p), rows))

    def scanned(self, p: str, caches: Params, p_len: int, total: int,
                kv_q8: bool = False) -> Params:
        _no_int8(kv_q8)
        return _with_snapshot({name: caches[name] for name in self.names(p)},
                              p)

    def turn_start(self, p: str, caches: Params, start) -> Params:
        return _taken_back(self.names(p), p, caches, start)


class _Cacheless(_Kind):
    """A mixer that caches nothing of its own."""
    shares = True
    one_form = True

    def empty(self, p: str, b: int, total: int, dtype,
              kv_q8: bool = False) -> Params:
        return {}

    def padded(self, p: str, rows, total: int, dtype) -> Params:
        return {}

    def scanned(self, p: str, caches: Params, p_len: int, total: int,
                kv_q8: bool = False) -> Params:
        return {}


@dataclasses.dataclass(frozen=True)
class Cross(_Cacheless):
    """Cross-attention of a decoder that shares one cache (YOCO, Sun et
    al. 2024): a query projection and an out projection of its own;
    keys and values are layer ``source``'s, read where that layer left
    them (``caches[L{source}_k]``, ``_v``, which every form is handed;
    in a full-sequence forward, which has no caches, ``handed["kv"]``).
    Causal: a position reads the rows up to its own. Differential, as
    :class:`GroupedQuery`'s pairs are (the source's cache holds pairs).
    Where ``y`` holds fewer positions than ``pos`` names, they are the
    LAST ones (a prefill runs these layers for the last position
    only)."""
    source: int = 0
    depth: int = 0

    def init(self, keys, dtype, p: str) -> Params:
        cfg, hd = self.cfg, head_dim(self.cfg)
        width = cfg.n_heads * hd
        return {f"{p}_q_W": _dense(next(keys), (cfg.d_model, width), dtype),
                f"{p}_q_b": jnp.zeros((width,), dtype),
                f"{p}_out_W": _dense(next(keys), (width, cfg.d_model), dtype),
                f"{p}_out_b": jnp.zeros((cfg.d_model,), dtype),
                **_differential_init(next(keys), dtype, p, hd)}

    def _queries(self, params: Params, p: str, y):
        cfg, hd = self.cfg, head_dim(self.cfg)
        pairs = kv_heads(cfg) // 2
        q = _biased(params, f"{p}_q", y).reshape(
            *y.shape[:2], pairs, cfg.n_heads // pairs, hd)
        return _pair_queries(q), hd ** -0.5

    def _out(self, params: Params, p: str, a, y):
        a = _differential(params, p, a.astype(jnp.float32), self.depth,
                          self.cfg.norm_eps)
        return _biased(params, f"{p}_out", a.astype(y.dtype))

    def _over(self, params: Params, p: str, y, pos, k, v, live=None):
        with scope("lm.cross"):
            q, scale = self._queries(params, p, y)
            a = cache_attention(q, k, v, pos[-y.shape[1]:],
                                jnp.arange(k.shape[2]), scale=scale,
                                live=live)
            return self._out(params, p, a, y)

    def full(self, params: Params, p: str, y, pos, attn_fn, handed):
        return self._over(params, p, y, pos, *handed["kv"]), ()

    def chunk(self, params: Params, p: str, y, pos, caches: Params, start,
              handed):
        src = f"L{self.source}"
        return self._over(params, p, y, pos, caches[f"{src}_k"],
                          caches[f"{src}_v"], live=pos[-1] + 1), caches

    def step(self, params: Params, p: str, y, t, caches: Params,
             kv_q8: bool, handed):
        src = f"L{self.source}"
        with scope("lm.cross"):
            q, scale = self._queries(params, p, y)
            a = decode_attention(q[:, 0], caches[f"{src}_k"],
                                 caches[f"{src}_v"], t, backend="auto",
                                 scale=scale)
            return self._out(params, p, a[:, None], y), (caches, None)


class GatedMemory(_Cacheless):
    """A gated memory unit (Ren et al. 2025, arXiv:2507.06607): the
    memory ``m`` an earlier state-space layer left of the SAME
    positions (``handed["memory"]``, its scan's output before the
    gate), gated by the layer's own input: ``out = (m * silu(y W_1))
    W_2``. No state, no cache; where ``y`` holds fewer positions than
    the memory, they are the last ones."""

    def init(self, keys, dtype, p: str) -> Params:
        d, e = self.cfg.d_model, _inner(self.cfg)[0]
        return {f"{p}_in_W": _dense(next(keys), (d, e), dtype),
                f"{p}_out_W": _dense(next(keys), (e, d), dtype)}

    def _gate(self, params: Params, p: str, y, handed):
        with scope("lm.gmu"):
            m = handed["memory"][:, -y.shape[1]:]
            gate = jax.nn.silu(_mm(params, f"{p}_in_W", y))
            return _mm(params, f"{p}_out_W", m * gate)

    def full(self, params: Params, p: str, y, pos, attn_fn, handed):
        return self._gate(params, p, y, handed), ()

    def chunk(self, params: Params, p: str, y, pos, caches: Params, start,
              handed):
        return self._gate(params, p, y, handed), caches

    def step(self, params: Params, p: str, y, t, caches: Params,
             kv_q8: bool, handed):
        return self._gate(params, p, y, handed), (caches, None)
