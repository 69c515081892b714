"""Fused decode attention — one query position against the KV cache.

A decode step's attention is a stream: a (B, 1) query has no q axis to
tile onto the MXU, so what the call costs is reading the cache once.
This kernel is the flash-decode form: stream the cache through VMEM in
tiles, fold scores into an online-softmax accumulator, and — because
the grid's chunk axis is driven by a SCALAR-PREFETCHED position ``t`` —
clamp dead chunks onto the live range so their DMAs are elided entirely
(the dead-tile trick of ``ops/attention.py``, dynamic this time). Cache
traffic per step is O(t), not O(S), and no masked score row or
dequantized tile materializes.

The tiling rule (``_tiles``; PERF.md section 6, PR 31): a grid step
costs the v5e about 0.3 us whatever it carries, so a step carries
``r`` (batch, kv-head) rows by ``block_s`` positions, a K and a V tile
of about a megabyte each, and not the 128 KB of one row's 512 positions
it carried before: at the two decode cells' shapes (bf16, D 128: 256
rows of 512 positions, 64 rows of 4096) one row a step read 152 and 282
us a call, 54% and 58% of the cache's bytes at 819 GB/s; eight rows a
step read 90 and 179 us, 91% and 92%, which is where XLA's own
composition of the same call stands with the cache full (91 and 181
us). Four rows a step already reach it; sixteen and thirty-two lose a
percent again; chunks longer than 512 buy what rows buy and no more, so
the chunk stays at the elision's granularity wherever rows can fill
the tile. The body's masks and the pass that zeroes a ragged chunk's V
rows showed nothing on the clock at any tiling (the copy hides them);
the V pass is compiled only for a cache whose length is ragged.

Layout contract: callers hold decode caches as (B, H_kv, S, D) — the
per-(batch, head) cache rows are contiguous, so the kernel (and XLA)
stream them without a per-step transpose, and ``r`` rows of one chunk
are ``r`` contiguous runs. ``models/transformer.py``'s ``greedy_decode``
owns that layout; its public ``prefill`` contract stays (B, S, H_kv, D)
and is transposed ONCE at the boundary.

The XLA path is the reference composition (same dot dtypes, same f32
softmax, same where-mask), so ``backend="xla"`` — the off-TPU
resolution — is what every token-exactness pin is held against.
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
    """(r, G, _LANES) lane-replicated scratch → (r, G, 1) row values
    (lanes all equal; max is exact — §9's row-state convention)."""
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


def _decode_xla(q, k, v, t, roll: bool, k_scale=None, v_scale=None,
                scale=None):
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
    s = s / jnp.sqrt(jnp.float32(d)) if scale is None else s * scale
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


# A grid step costs about 0.3 us on the v5e whatever it carries (two DMAs
# started and waited for, the body's fixed work), so a step carries a K
# and a V tile of about a megabyte each: 2.6 us of copy at 819 GB/s.
_TILE_BYTES = 1 << 20
# unrolled copies of the body's two matmuls in one step
_MAX_ROWS = 16
# what the kernel may hold in VMEM (the v5e's default scoped limit, said
# out loud so that every generation compiles the same tiling), and the
# part of it `_tiles` lets `_vmem_bytes` reach: the rest is the
# compiler's own (spills, the matmuls' staging)
_VMEM_LIMIT = 16 << 20
_VMEM_BUDGET = _VMEM_LIMIT * 3 // 4


def _pad(n: int, to: int) -> int:
    return -(-n // to) * to


def _vmem_bytes(r: int, block_s: int, d: int, itemsize: int, g: int,
                q8: bool) -> int:
    """VMEM one grid step of ``_decode_kernel`` holds at ``r`` rows and
    ``block_s`` positions a step, counted as Mosaic lays it out: the
    last dim padded to 128 lanes, the one before to a 32-byte sublane
    group (8 float32, 16 bfloat16, 32 int8). Every block is double-
    buffered by the pipeline; the body's temporaries are the float32
    score rows and, with int8 caches, the bfloat16 copies of both
    tiles."""
    lanes = _pad(d, 128)
    tile = r * _pad(block_s, 32 // itemsize) * lanes * itemsize
    q_out = r * lanes * (_pad(g, 16) * 2 + _pad(g, 8) * 4)  # bf16 q, f32 out
    total = 2 * (2 * tile + q_out)
    scores = r * _pad(g, 8) * _pad(block_s, 128) * 4
    total += 4 * scores                             # s, p, masks
    total += r * _pad(g, 8) * (lanes + 2 * 128) * 4  # acc, m, l
    if q8:
        # (r, 1, block_s) float32 scales: one sublane used of eight
        total += 2 * 2 * r * 8 * _pad(block_s, 128) * 4
        total += 2 * r * _pad(block_s, 16) * lanes * 2
    return total


def _tiles(rows: int, s_len: int, d: int, itemsize: int, g: int,
           q8: bool, block_s: int = 512) -> tuple:
    """How much cache one grid step carries: ``(r, block_s)``, blocks
    of ``r`` (batch, kv-head) rows by ``block_s`` positions. A pure
    function of what the call can see (never of ``t``, which is
    traced).

    ``block_s`` comes in as the chunk asked for (the granularity of the
    dead-chunk elision) and is first cut to the cache's own length.
    ``r`` is the largest divisor of ``rows`` whose K tile stays within
    ``_TILE_BYTES`` and whose step fits ``_VMEM_BUDGET``. Where rows
    alone leave the tile under half the target (few rows, or a prime
    number of them) the chunk doubles while it stays within an eighth
    of the row: a longer chunk reads up to one chunk more of dead cache
    at small ``t``, half a chunk on average, so at most a sixteenth of
    the row. ``(1, block_s)``, the tiling before rows were grouped, is
    the answer for a shape that fits nothing else."""
    block_s = min(block_s, max(128, _pad(s_len, 128)))

    def ok(r, bs):
        return (r * bs * d * itemsize <= _TILE_BYTES
                and _vmem_bytes(r, bs, d, itemsize, g, q8) <= _VMEM_BUDGET)

    r = max(x for x in range(1, min(rows, _MAX_ROWS) + 1)
            if rows % x == 0 and (x == 1 or ok(x, block_s)))
    while 2 * block_s * 8 <= s_len and ok(r, 2 * block_s):
        block_s *= 2
    return r, block_s


def _decode_kernel(t_ref, q_ref, k_ref, v_ref, *rest,
                   block_s, s_len, scale, roll, n_chunks, q8):
    """``r`` (batch·kv-head) rows: fold cache chunk ``ki`` of each into
    its online-softmax state, both contractions batched over the rows.
    Row state is lane-replicated (r, G, _LANES) per §9's Mosaic
    legality rule. With ``q8``, k/v arrive int8 and two extra
    (r, 1, block_s) scale refs follow, positions on the lanes as the
    scores have them — the k scale multiplies score COLUMNS after the
    dot, the v scale folds into p before the value dot, so no
    dequantized tile ever materializes."""
    if q8:
        ks_ref, vs_ref, o_ref, acc, m_scr, l_scr = rest
    else:
        o_ref, acc, m_scr, l_scr = rest
    ki = pl.program_id(1)
    t = t_ref[0]
    # the final block of a cache that is no multiple of block_s hangs
    # over its end: known statically, so only such a cache pays for the
    # pass over the V tile (no cell's does)
    ragged = s_len % block_s != 0

    @pl.when(ki == 0)
    def _():
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc[...] = jnp.zeros_like(acc)

    # chunks that start past t are dead: their DMAs are elided by the
    # index map, their compute by this guard
    @pl.when(ki * block_s <= t)
    def _():
        q = q_ref[...]                                 # (r, G, D)
        k = k_ref[...]                                 # (r, block_s, D)
        v = v_ref[...]                                 # (r, block_s, D)
        if q8:
            # int8 rows are exact in bf16 (integers ≤ 256): the dot is
            # the exact product-accumulation, scales restore magnitude
            q = q.astype(jnp.bfloat16)
            k = k.astype(jnp.bfloat16)
            v = v.astype(jnp.bfloat16)
            vs = vs_ref[...]                           # (r, 1, block_s)
        s = jax.lax.dot_general(
            q, k, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)          # (r, G, block_s)
        if q8:
            s = s * ks_ref[...]
        s = s * scale
        col = ki * block_s + jax.lax.broadcasted_iota(
            jnp.int32, (1, 1, block_s), 2)
        live = (col <= t) | (t >= s_len) if roll else (col <= t)
        if ragged:
            # out-of-bounds v rows (and scale lanes) hold unspecified
            # values (NaN in interpret mode); their p weight is
            # exp(-inf)=0 but 0·NaN = NaN, so they are zeroed before
            # the dot
            live &= col < s_len
            row = ki * block_s + jax.lax.broadcasted_iota(
                jnp.int32, (1, block_s, 1), 1)
            v = jnp.where(row < s_len, v, 0).astype(v.dtype)
            if q8:
                vs = jnp.where(col < s_len, vs, 0.0)
        s = jnp.where(live, s, _NEG_INF)

        m_prev = _rows(m_scr)                          # (r, G, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)                         # (r, G, block_s)
        l_new = _rows(l_scr) * alpha + jnp.sum(p, axis=-1, keepdims=True)
        if q8:
            p = p * vs
        pv = jax.lax.dot_general(
            p.astype(v.dtype), v, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)          # (r, G, D)
        acc[...] = acc[...] * alpha + pv
        m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(ki == n_chunks - 1)
    def _():
        o_ref[...] = acc[...] / jnp.maximum(_rows(l_scr), 1e-30)


@functools.partial(
    jax.jit,
    static_argnames=("roll", "block_s", "interpret", "scale"))
def _decode_pallas(q, k, v, t, roll: bool = False, block_s: int = 512,
                   interpret: bool = False, k_scale=None, v_scale=None,
                   scale=None):
    b, hkv, g, d = q.shape
    s_len = k.shape[2]
    rows = b * hkv
    q8 = k_scale is not None
    r, block_s = _tiles(rows, s_len, d, k.dtype.itemsize, g, q8, block_s)
    # ceil-divided grid, NO padding: k/v ride the decode scan's carry,
    # so a jnp.pad here would copy the whole cache every generated
    # token — the O(S) per-step traffic this kernel exists to kill.
    # Pallas masks the ragged final block itself; its out-of-bounds
    # lanes surface as undefined values in `s`, which the explicit
    # `col < s_len` mask sends to -inf before they touch the softmax.
    n_chunks = -(-s_len // block_s)
    qb = q.reshape(rows, g, d)
    kb = k.reshape(rows, s_len, d)
    vb = v.reshape(rows, s_len, d)
    scale = 1.0 / float(d) ** 0.5 if scale is None else float(scale)
    tarr = jnp.asarray(t, jnp.int32).reshape(1)

    def chunk(ki, t_ref):
        # dead-chunk DMA elision, dynamic form: chunks past the live
        # position clamp onto the last live chunk — consecutive equal
        # indices skip the copy; compute is pl.when-guarded anyway
        return jnp.minimum(ki, jnp.maximum(t_ref[0], 0) // block_s)

    qspec = pl.BlockSpec((r, g, d), lambda i, ki, t_ref: (i, 0, 0),
                         memory_space=pltpu.VMEM)
    cspec = pl.BlockSpec((r, block_s, d),
                         lambda i, ki, t_ref: (i, chunk(ki, t_ref), 0),
                         memory_space=pltpu.VMEM)
    in_specs = [qspec, cspec, cspec]
    operands = [tarr, qb, kb, vb]
    if q8:
        # scales ride as (rows, 1, S): positions on the lanes, where
        # the score columns they multiply are, and one sublane of
        # eight in VMEM where (rows, S, 1) would pad to 128 lanes
        sspec = pl.BlockSpec(
            (r, 1, block_s),
            lambda i, ki, t_ref: (i, 0, chunk(ki, t_ref)),
            memory_space=pltpu.VMEM)
        in_specs += [sspec, sspec]
        operands += [k_scale.reshape(rows, 1, s_len),
                     v_scale.reshape(rows, 1, s_len)]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(rows // r, n_chunks),
        in_specs=in_specs,
        out_specs=qspec,
        scratch_shapes=[pltpu.VMEM((r, g, d), jnp.float32),
                        pltpu.VMEM((r, g, _LANES), jnp.float32),
                        pltpu.VMEM((r, g, _LANES), jnp.float32)],
    )
    out = pl.pallas_call(
        functools.partial(_decode_kernel, block_s=block_s, s_len=s_len,
                          scale=scale, roll=roll, n_chunks=n_chunks,
                          q8=q8),
        grid_spec=grid_spec,
        out_shape=out_struct((rows, g, d), jnp.float32, qb, kb, vb),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name="_decode_pallas",
    )(*operands)
    return out.reshape(b, hkv, g, d)


def decode_attention(q, k, v, t, *, roll: bool = False,
                     backend: str = "auto", block_s: int = 512,
                     k_scale=None, v_scale=None, scale=None):
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
    materializes a dequantized cache. ``scale`` multiplies the scores
    (default ``D ** -0.5``; a caller whose rows are wider than its
    heads says its own). Returns f32 (B, H_kv, G, D).
    """
    if (k_scale is None) != (v_scale is None):
        raise ValueError("k_scale and v_scale must be passed together")
    backend = resolve_backend(backend, "decode_attention")
    if backend == "xla":
        return _decode_xla(q, k, v, t, roll, k_scale, v_scale, scale)
    return _decode_pallas(q, k, v, t, roll=roll, block_s=block_s,
                          interpret=backend == "pallas_interpret",
                          k_scale=k_scale, v_scale=v_scale, scale=scale)


# float32 scores of one key block that `cache_attention` lets exist at a
# time
_SCORE_BYTES = 256 << 20


def cache_attention(q, k, v, q_pos, k_pos, *, window: int = 0, scale=None,
                    live=None):
    """Several query positions against a cache: a prefill chunk's, or a
    whole sequence's against its own rows. XLA's composition, a block of
    keys at a time folded into an online softmax, so the scores exist
    for one block only.

    q: (B, Q, H_kv, G, D); k, v: (B, H_kv, S, D) in the decode layout;
    ``q_pos`` (Q,) int32 the queries' positions; ``k_pos`` (S,) int32
    the position each slot holds, negative where it holds none (a
    rolling buffer's slots are in no order of position). A query sees
    the slots whose position is at most its own and, with ``window``,
    fewer than ``window`` behind it; every query sees one at least (its
    own). ``live``, where given (traced), says that slots from ``live``
    on hold nothing any query sees: their blocks are not read. Returns
    f32 (B, Q, H_kv, G, D)."""
    b, q_len, hkv, g, d = q.shape
    s_len = k.shape[2]
    rows = q_len * g
    kb = max(128, _SCORE_BYTES // (4 * b * hkv * rows) // 128 * 128)
    kb = min(kb, s_len)
    scale = d ** -0.5 if scale is None else scale
    qf = jnp.transpose(q, (0, 2, 1, 3, 4)).reshape(b, hkv, rows, d)
    row_pos = jnp.repeat(q_pos.astype(jnp.int32), g)[:, None]
    k_pos = k_pos.astype(jnp.int32)

    def fold(i, state):
        m, l, acc = state
        # the last block starts where it still fits; what it then
        # shares with the block before is masked out
        start = jnp.minimum(i * kb, s_len - kb)
        kc = jax.lax.dynamic_slice(k, (0, 0, start, 0), (b, hkv, kb, d))
        vc = jax.lax.dynamic_slice(v, (0, 0, start, 0), (b, hkv, kb, d))
        pos = jax.lax.dynamic_slice(k_pos, (start,), (kb,))[None, :]
        seen = ((pos >= 0) & (pos <= row_pos)
                & (start + jnp.arange(kb)[None, :] >= i * kb))
        if window:
            seen &= row_pos - pos < window
        s = jnp.einsum("bhmd,bhnd->bhmn", qf, kc,
                       preferred_element_type=jnp.float32) * scale
        s = jnp.where(seen, s, _NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        p = jnp.where(seen, jnp.exp(s - m_new), 0.0)
        l = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc = acc * alpha + jnp.einsum(
            "bhmn,bhnd->bhmd", p.astype(v.dtype), vc,
            preferred_element_type=jnp.float32)
        return m_new, l, acc

    init = (jnp.full((b, hkv, rows, 1), _NEG_INF, jnp.float32),
            jnp.zeros((b, hkv, rows, 1), jnp.float32),
            jnp.zeros((b, hkv, rows, d), jnp.float32))
    n = -(-s_len // kb) if live is None else (live + kb - 1) // kb
    _, l, acc = jax.lax.fori_loop(0, n, fold, init)
    out = (acc / jnp.maximum(l, 1e-30)).reshape(b, hkv, q_len, g, d)
    return jnp.transpose(out, (0, 2, 1, 3, 4))
