"""Fused decode attention — one query position against the KV cache.

The serving-side gap DESIGN §13 quantifies: the decode scan's per-step
attention reads the ENTIRE padded cache (0.5 GB at the bench shape)
through an XLA einsum+mask+softmax+einsum chain shaped badly for the
TPU — a (B, 1) query has no q axis to tile onto the MXU, the mask and
f32 score row materialize per step, and slots beyond the current
position are streamed only to be masked. This kernel is the
flash-decode form of §9's playbook: stream the cache ONCE through VMEM
in (block_s, D) tiles, fold scores into an online-softmax accumulator,
and — because the grid's chunk axis is driven by a SCALAR-PREFETCHED
position ``t`` — clamp dead chunks onto the live range so their DMAs
are elided entirely (the §9 dead-tile trick, dynamic this time).
Cache traffic per step drops from O(S) to O(t), and the masked-score
materialization disappears.

Layout contract: callers hold decode caches as (B, H_kv, S, D) — the
per-(batch, head) cache rows are contiguous, so the kernel (and XLA)
stream them without a per-step transpose. ``models/transformer.py``'s
``greedy_decode`` owns that layout; its public ``prefill`` contract
stays (B, S, H_kv, D) and is transposed ONCE at the boundary.

The XLA path reproduces the previous in-scan composition
operation-for-operation (same dot dtypes, same f32 softmax, same
where-mask), so ``backend="xla"`` — the off-TPU resolution — is
bit-identical to the code it replaced and every token-exactness pin
keeps meaning what it meant.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from lua_mapreduce_tpu.ops import out_struct, resolve_backend
from lua_mapreduce_tpu.ops.attention import _LANES, _tile_mask

_NEG_INF = -1e30


def _rows(scr):
    """(G, _LANES) lane-replicated scratch → (G, 1) row values (lanes
    all equal; max is exact — §9's row-state convention)."""
    return jnp.max(scr[...], axis=-1, keepdims=True)


def quantize_kv(x, axis: int = -1):
    """Per-row symmetric int8 quantization of cache rows: ``x``
    (..., D) → (int8 rows, f32 scales (...,)). Row scale = amax/127 —
    the KV-cache twin of ops/q8.py's per-channel weight scheme (the
    cache is written once per position, so the scale granularity is
    the position row)."""
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=axis)
    scale = jnp.maximum(amax, 1e-8) / 127.0
    q = jnp.clip(jnp.round(x.astype(jnp.float32)
                           / scale[..., None]), -127, 127)
    return q.astype(jnp.int8), scale


def _decode_xla(q, k, v, t, roll: bool, k_scale=None, v_scale=None):
    """Reference composition — exactly the ops the decode scan ran
    in-line before this module existed (models/transformer.py), with
    the q-length-1 axis dropped and the (B, H_kv, S, D) cache layout.
    With int8 caches (``k_scale``/``v_scale`` per (B, H_kv, S) row)
    the scales factor OUT of both contractions — s columns scale by
    k_scale, p rows by v_scale — and the p·v operands round at bf16,
    the same algebra and rounding points as the kernel. Returns f32
    (B, H_kv, G, D)."""
    b, hkv, g, d = q.shape
    s_len = k.shape[2]
    if k_scale is None:
        s = jnp.einsum("bkgd,bkmd->bkgm", q, k,
                       preferred_element_type=jnp.float32)
    else:
        # int8 rows are exact in bf16 (integers ≤ 256), so this dot is
        # the f32 product-accumulation of (q, k_q8) — the scale then
        # restores magnitudes per column
        s = jnp.einsum("bkgd,bkmd->bkgm", q.astype(jnp.bfloat16),
                       k.astype(jnp.bfloat16),
                       preferred_element_type=jnp.float32)
        s = s * k_scale[:, :, None, :]
    s = s / jnp.sqrt(jnp.float32(d))
    seen = jnp.arange(s_len)[None, None, None, :]
    if roll:
        # rolling containment IS the window (models/transformer.py):
        # mask only slots not yet filled; a full cache is all-visible
        vis = (seen <= t) | (t >= s_len)
    else:
        # the SHARED mask definition (attention.py _tile_mask) at
        # row = t: decode's windowed case is roll (window < total),
        # so window here is structurally 0
        vis = _tile_mask(t, seen, True, 0, s_len)
    s = jnp.where(vis, s, _NEG_INF)
    w = jax.nn.softmax(s, axis=-1)
    if v_scale is None:
        return jnp.einsum("bkgm,bkmd->bkgd", w.astype(v.dtype), v,
                          preferred_element_type=jnp.float32)
    w = (w * v_scale[:, :, None, :]).astype(jnp.bfloat16)
    return jnp.einsum("bkgm,bkmd->bkgd", w, v.astype(jnp.bfloat16),
                      preferred_element_type=jnp.float32)


def _decode_kernel(t_ref, q_ref, k_ref, v_ref, *rest,
                   block_s, s_len, scale, roll, n_chunks, q8):
    """One (batch·kv-head) row: fold cache chunk ``ki`` into the
    online-softmax state. Row state is lane-replicated (G, _LANES)
    per §9's Mosaic legality rule. With ``q8``, k/v arrive int8 and
    two extra (block_s, 1) scale refs follow — the k scale multiplies
    score COLUMNS after the dot, the v scale folds into p before the
    value dot, so no dequantized tile ever materializes."""
    if q8:
        ks_ref, vs_ref, o_ref, acc, m_scr, l_scr = rest
    else:
        o_ref, acc, m_scr, l_scr = rest
    ki = pl.program_id(1)
    t = t_ref[0]

    @pl.when(ki == 0)
    def _():
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc[...] = jnp.zeros_like(acc)

    @pl.when(ki * block_s <= t)
    def _():
        q = q_ref[0]                                   # (G, D)
        k = k_ref[0]                                   # (block_s, D)
        v = v_ref[0]                                   # (block_s, D)
        if q8:
            # int8 rows are exact in bf16 (integers ≤ 256): the dot is
            # the exact product-accumulation, scales restore magnitude
            q = q.astype(jnp.bfloat16)
            k = k.astype(jnp.bfloat16)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)          # (G, block_s)
        if q8:
            s = s * ks_ref[0][:, 0][None, :]
        s = s * scale
        col = ki * block_s + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 1)
        vis = col < s_len
        live = (col <= t) | (t >= s_len) if roll else (col <= t)
        s = jnp.where(vis & live, s, _NEG_INF)
        # ragged final block: out-of-bounds v rows hold unspecified
        # values (NaN in interpret mode); their p weight is exp(-inf)=0
        # but 0·NaN = NaN, so the rows must be zeroed before the dot
        row = ki * block_s + jax.lax.broadcasted_iota(
            jnp.int32, (block_s, 1), 0)
        v = jnp.where(row < s_len, v, 0).astype(v.dtype)

        m_prev = _rows(m_scr)                          # (G, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)                         # (G, block_s)
        l_prev = _rows(l_scr)
        l_new = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
        if q8:
            # OOB scale lanes are unspecified like OOB v rows — zero
            # them for the same 0·NaN reason
            vs = jnp.where(row[:, 0] < s_len, vs_ref[0][:, 0], 0.0)
            p = p * vs[None, :]
            pv = jax.lax.dot_general(
                p.astype(jnp.bfloat16), v.astype(jnp.bfloat16),
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
        else:
            pv = jax.lax.dot_general(
                p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
        acc[...] = acc[...] * alpha + pv
        m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(ki == n_chunks - 1)
    def _():
        o_ref[0] = acc[...] / jnp.maximum(_rows(l_scr), 1e-30)


@functools.partial(
    jax.jit,
    static_argnames=("roll", "block_s", "interpret"))
def _decode_pallas(q, k, v, t, roll: bool = False, block_s: int = 512,
                   interpret: bool = False, k_scale=None, v_scale=None):
    b, hkv, g, d = q.shape
    s_len = k.shape[2]
    q8 = k_scale is not None
    block_s = min(block_s, max(128, -(-s_len // 128) * 128))
    # ceil-divided grid, NO padding: k/v ride the decode scan's carry,
    # so a jnp.pad here would copy the whole cache every generated
    # token — the O(S) per-step traffic this kernel exists to kill.
    # Pallas masks the ragged final block itself; its out-of-bounds
    # lanes surface as undefined values in `s`, which the explicit
    # `col < s_len` mask sends to -inf before they touch the softmax.
    n_chunks = -(-s_len // block_s)
    qb = q.reshape(b * hkv, g, d)
    kb = k.reshape(b * hkv, s_len, d)
    vb = v.reshape(b * hkv, s_len, d)
    scale = 1.0 / float(d) ** 0.5
    tarr = jnp.asarray(t, jnp.int32).reshape(1)

    def chunk(ki, t_ref):
        # dead-chunk DMA elision, dynamic form: chunks past the live
        # position clamp onto the last live chunk — consecutive equal
        # indices skip the copy; compute is pl.when-guarded anyway
        return jnp.minimum(ki, jnp.maximum(t_ref[0], 0) // block_s)

    qspec = pl.BlockSpec((1, g, d), lambda r, ki, t_ref: (r, 0, 0),
                         memory_space=pltpu.VMEM)
    cspec = pl.BlockSpec((1, block_s, d),
                         lambda r, ki, t_ref: (r, chunk(ki, t_ref), 0),
                         memory_space=pltpu.VMEM)
    in_specs = [qspec, cspec, cspec]
    operands = [tarr, qb, kb, vb]
    if q8:
        # scales ride as (rows, S, 1) so the (block_s, 1) block keeps
        # Mosaic's trailing-dims rule (1 == array's own trailing dim)
        sspec = pl.BlockSpec(
            (1, block_s, 1),
            lambda r, ki, t_ref: (r, chunk(ki, t_ref), 0),
            memory_space=pltpu.VMEM)
        in_specs += [sspec, sspec]
        operands += [k_scale.reshape(b * hkv, s_len, 1),
                     v_scale.reshape(b * hkv, s_len, 1)]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b * hkv, n_chunks),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, g, d), lambda r, ki, t_ref: (r, 0, 0),
                               memory_space=pltpu.VMEM),
        scratch_shapes=[pltpu.VMEM((g, d), jnp.float32),
                        pltpu.VMEM((g, _LANES), jnp.float32),
                        pltpu.VMEM((g, _LANES), jnp.float32)],
    )
    out = pl.pallas_call(
        functools.partial(_decode_kernel, block_s=block_s, s_len=s_len,
                          scale=scale, roll=roll, n_chunks=n_chunks,
                          q8=q8),
        grid_spec=grid_spec,
        out_shape=out_struct((b * hkv, g, d), jnp.float32, qb, kb, vb),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="_decode_pallas",
    )(*operands)
    return out.reshape(b, hkv, g, d)


def decode_attention(q, k, v, t, *, roll: bool = False,
                     backend: str = "auto", block_s: int = 512,
                     k_scale=None, v_scale=None):
    """One decode position's attention against the KV cache.

    q: (B, H_kv, G, D) — the G query heads grouped under each kv head
    (G = 1 is plain MHA); k, v: (B, H_kv, S, D) caches; ``t``: scalar
    int32 current position. Slots with index > t are invisible unless
    ``roll`` and the rolling cache is full (every slot then holds a
    live position — models/transformer.py's rolling-containment rule).

    int8 KV cache: pass k/v as int8 with ``k_scale``/``v_scale`` f32
    per-row scales, shape (B, H_kv, S) — :func:`quantize_kv` produces
    them. Cache HBM traffic halves (the dominant decode byte stream);
    the scales factor out of both contractions so neither path
    materializes a dequantized cache. Returns f32 (B, H_kv, G, D).
    """
    if (k_scale is None) != (v_scale is None):
        raise ValueError("k_scale and v_scale must be passed together")
    backend = resolve_backend(backend, "decode_attention")
    if backend == "xla":
        return _decode_xla(q, k, v, t, roll, k_scale, v_scale)
    return _decode_pallas(q, k, v, t, roll=roll, block_s=block_s,
                          interpret=backend == "pallas_interpret",
                          k_scale=k_scale, v_scale=v_scale)
