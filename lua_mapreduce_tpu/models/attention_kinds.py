"""What a decoder layer's attention is: a KIND owns its weights, what a
position caches, the cache's format and the attention over it. Two
exist, :class:`GroupedQuery` and :class:`Latent`;
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

``full`` attends a whole sequence (``y`` (B, L, d) at positions ``pos``)
and returns what it cached of it; ``chunk`` and ``step`` write their
positions into ``caches`` (every layer's, as ``empty`` and ``scanned``
lay them out) and attend what the caches hold by then, ``step`` for the
one position ``t`` with the cache positions it selected (None where the
kind reads them all). ``out`` is the attention's output through its out
projection, for the residual. This module sits below
``models/transformer.py`` and never imports it.
"""

from __future__ import annotations

import dataclasses
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from lua_mapreduce_tpu.ops import sparse_mla as _sparse
from lua_mapreduce_tpu.ops.decode import decode_attention, quantize_kv
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
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.var(x, axis=-1, keepdims=True)
    return (x - mu) * lax.rsqrt(var + eps) * g + b


def _rms_norm(x, g, eps):
    ms = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * lax.rsqrt(ms + eps) * g


def _norm(params: Params, name: str, x, cfg):
    """The block norm: pre-LN (scale+bias) or RMSNorm (scale only)."""
    g = params[f"{name}_g"]
    if cfg.norm == "rms":
        return _rms_norm(x, g, cfg.norm_eps)
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
    one."""
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

    def names(self, p: str) -> list:
        return [f"{p}_{leaf}" for leaf in self.leaves]

    def chunk(self, params, p, y, pos, caches, start):
        raise ValueError("chunk is for single-device latent attention")

    def padded(self, p: str, rows, total: int, dtype) -> Params:
        """``full``'s rows as :func:`prefill` hands them out: leaves
        (B, total, ...), zeros beyond the sequence."""
        out = {}
        for name, leaf in zip(self.names(p), rows):
            pad = ((0, 0), (0, total - leaf.shape[1])) \
                + ((0, 0),) * (leaf.ndim - 2)
            out[name] = jnp.pad(leaf, pad).astype(dtype)
        return out


class GroupedQuery(_Kind):
    """Grouped-query attention over (k, v) caches: a fused qkv
    projection, rope on q and k, the caller's attention over a full
    sequence and ``ops/decode.py``'s over the cache. Public caches are
    (B, S, H_kv, D); the scan carries (B, H_kv, S, D) — per-(batch,
    head) rows contiguous, the ops/decode.py layout contract —, int8
    with f32 scales a row (``L{i}_{k,v}s``) under ``kv_q8``, and a
    rolling buffer of ``window`` slots where the window is shorter than
    the decode."""
    leaves = ("k", "v")

    def init(self, keys, dtype, p: str) -> Params:
        cfg, hd = self.cfg, head_dim(self.cfg)
        cols = (cfg.n_heads + 2 * kv_heads(cfg)) * hd
        return {f"{p}_qkv_W": _dense(next(keys), (cfg.d_model, cols), dtype),
                f"{p}_out_W": _dense(next(keys), (cfg.n_heads * hd,
                                                  cfg.d_model), dtype)}

    def project(self, params: Params, p: str, y, pos):
        """q (B, L, H, hd) and the rows (k, v), each (B, L, H_kv, hd),
        of ``y`` at ``pos``. With rope k is the ROTATED one: what
        attention consumes and what the cache stores."""
        cfg = self.cfg
        b, l, _ = y.shape
        h, hkv, hd = cfg.n_heads, kv_heads(cfg), head_dim(cfg)
        qkv = _mm(params, f"{p}_qkv_W", y)  # (B, L, (H+2Hkv)·hd) MXU
        q = qkv[..., :h * hd].reshape(b, l, h, hd)
        k = qkv[..., h * hd:(h + hkv) * hd].reshape(b, l, hkv, hd)
        v = qkv[..., (h + hkv) * hd:].reshape(b, l, hkv, hd)
        if cfg.rope:
            q = _rope(q, pos, cfg.rope_base)
            k = _rope(k, pos, cfg.rope_base)
        return q, (k, v)

    def full(self, params: Params, p: str, y, pos, attn_fn):
        q, rows = self.project(params, p, y, pos)
        a = attn_fn(q, *rows).reshape(*y.shape[:2], -1)
        return _mm(params, f"{p}_out_W", a), rows

    def step(self, params: Params, p: str, y, t, caches: Params,
             kv_q8: bool):
        q, rows = self.project(params, p, y, t[None])
        b, _, h, hd = q.shape
        hkv = kv_heads(self.cfg)
        kn, vn = self.names(p)
        cache_len = caches[kn].shape[2]
        roll = _rolls(self.cfg, cache_len)
        # (B, 1, Hkv, D) → (B, Hkv, 1, D) cache-layout row
        k, v = (jnp.transpose(row, (0, 2, 1, 3)) for row in rows)
        # head index = (kv head, group member), kv-head major —
        # the grouping decode_attention's (B, Hkv, G, D) q expects
        q = q.reshape(b, hkv, h // hkv, hd)
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
                             backend="auto", **scales)
        a = a.astype(y.dtype).reshape(b, 1, h * hd)
        return _mm(params, f"{p}_out_W", a), (caches, None)

    def empty(self, p: str, b: int, total: int, dtype,
              kv_q8: bool = False) -> Params:
        cfg = self.cfg
        shape = (b, kv_heads(cfg), _cache_shape(cfg, total)[1])
        out = {n: jnp.zeros(shape + (head_dim(cfg),),
                            jnp.int8 if kv_q8 else dtype)
               for n in self.names(p)}
        if kv_q8:
            out.update({n + "s": jnp.zeros(shape, jnp.float32)
                        for n in self.names(p)})
        return out

    def scanned(self, p: str, caches: Params, p_len: int, total: int,
                kv_q8: bool = False) -> Params:
        """One transpose at the boundary, not one per step; quantized
        under ``kv_q8``; folded into the rolling layout where the
        window is shorter than ``total``."""
        roll, cache_len = _cache_shape(self.cfg, total)
        out = {n: jnp.transpose(caches[n], (0, 2, 1, 3))
               for n in self.names(p)}
        if kv_q8:
            quant = {}
            for n, c in out.items():
                quant[n], quant[n + "s"] = quantize_kv(c)
            out = quant
        if not roll:
            return out
        # positions 0..p_len-1 land in slots 0..p_len-1 and the prefill
        # cache is already zero-padded beyond them — a plain truncation IS
        # the rolling layout
        src = slice(cache_len)
        if p_len >= cache_len:
            # fold the prompt cache into it: slot j holds the LAST prompt
            # position ≡ j (mod w). Scale entries (kv_q8) are
            # (B, H_kv, S) — same slot axis, same fold.
            j = jnp.arange(cache_len)
            src = p_len - 1 - ((p_len - 1 - j) % cache_len)
        return {n: c[:, :, src] for n, c in out.items()}


def _no_int8(kv_q8: bool) -> None:
    if kv_q8:
        raise ValueError("kv_q8 quantizes grouped-query caches; a latent "
                         "cache has no int8 form")


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
    query before the rope. The scan carries the caches as prefill hands
    them out, in the model's own type: no latent cache has an int8 form
    (``kv_q8`` is refused), with or without the indexer."""

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
        (B, L, H*, D) and none on the rest."""
        la, base = self.cfg.latent, self.cfg.rope_base
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
            q_rope = _rope(q[..., la.nope_dim:], pos, cfg.rope_base, freqs)
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
