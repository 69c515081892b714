"""Absorbed latent attention over a whole cache: the flash-decode form
for one position, and the causal form for a block of them.

A latent cache holds one row ``[c_kv | k_rope]`` a position for ALL
heads. In the absorbed form (the query already multiplied into the
latent's basis) that row is key and value at once: a head's score is
the row against the query's ``R = kv_rank + rope_dim`` values, and the
weighted sum is over the row's first ``v_rank`` values, the latent. So
a cached row should cross the bus once. XLA's composition cannot do
that: it has one einsum for the scores and one for the values, each
reading the cache, with the float32 scores of every head between them.

:func:`mla_decode_attention` is the decode step's: ``_mla_decode_pallas``
streams the cache through VMEM in tiles of a megabyte or two, each tile
used for both contractions with the row's heads as the M of both
matmuls, folds the scores into an online-softmax state, and clamps the
chunks past the scalar-prefetched position ``t`` onto the live range so
that their copies are elided (``ops/decode.py``'s trick). Only the chunk
that holds ``t`` is masked; the chunks before it are whole and pay for
no mask. ``_mla_decode_xla`` is the composition for other platforms and
the oracle. :func:`latent_row_put` writes the step's row into the cache
before the kernel reads it: on the kernel's backend in place, a 128-lane
tile column a session, where XLA's dynamic-update-slice costs a tile
write for each of the row's values.

:func:`mla_causal_attention` is the prefill's, in XLA: a block of
queries against the cache up to the block's last position and no
further, a key block at a time with the same online softmax, so that a
long prompt's (queries, keys) scores exist for one pair of blocks and
the work is the causal half.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from lua_mapreduce_tpu.ops import out_struct, resolve_backend
from lua_mapreduce_tpu.ops.attention import _LANES
from lua_mapreduce_tpu.ops.decode import _pad

_NEG_INF = -1e30

# A grid step costs the v5e about 0.3 us whatever it carries (PERF.md
# section 6, PR 31), so a step carries a tile of a megabyte or two. At
# (16, 32832, 576) bfloat16, us a call (the cache's bytes at 819 GB/s:
# 738): one row x 512 positions (0.59 MB) 1,086, x 1024 882, x 2048 839, x
# 4096 867; two rows x 512 858, x 1024 (2.36 MB) 825, x 2048 833; four
# rows x 512 840, x 1024 832; eight x 512 847 (PERF.md section 5, PR 32).
_TILE_BYTES = 5 << 19
# the chunk asked for: the granularity of the dead-chunk elision
_CHUNK = 1024
_MAX_ROWS = 8
# what the kernel may hold in VMEM, said out loud so that every
# generation compiles the same tiling, and the part of it `_tiles` lets
# `_vmem_bytes` reach
_VMEM_LIMIT = 32 << 20
_VMEM_BUDGET = _VMEM_LIMIT * 3 // 4


def _vmem_bytes(r: int, block_s: int, width: int, v_rank: int, heads: int,
                itemsize: int) -> int:
    """VMEM one grid step of ``_mla_decode_kernel`` holds, counted as
    Mosaic lays it out (lanes padded to 128, sublanes to a 32-byte
    group): the double-buffered cache tile (positions on the lanes),
    query and output blocks, the float32 scores with their exponentials
    and the bfloat16 weights, and the state."""
    sub = 32 // itemsize
    tile = r * _pad(width, sub) * _pad(block_s, 128) * itemsize
    q = r * _pad(heads, sub) * _pad(width, 128) * itemsize
    out = r * _pad(heads, 8) * _pad(v_rank, 128) * 4
    scores = r * _pad(heads, 8) * _pad(block_s, 128) * 4
    state = out + 2 * r * _pad(heads, 8) * _LANES * 4
    # the edge chunk's zeroed copy of the tile counts once
    return 2 * (tile + q + out) + tile + 3 * scores + state


def _tiles(b: int, s_len: int, width: int, v_rank: int, heads: int,
           itemsize: int) -> tuple:
    """How much cache one grid step carries: ``(r, block_s)``, ``r``
    batch rows by ``block_s`` positions; ``ops/decode._tiles``' rule. A
    pure function of what the call can see (never of ``t``, which is
    traced). The chunk is ``_CHUNK`` cut to the cache's own length;
    ``r`` is the largest divisor of ``b`` whose tile stays within
    ``_TILE_BYTES`` and whose step fits ``_VMEM_BUDGET``. Where rows
    alone leave the tile under half the target (few rows, a prime
    number of them) the chunk doubles while it stays within an eighth
    of the cache: a longer chunk reads at most a sixteenth of the row
    dead."""
    block_s = min(_CHUNK, max(128, _pad(s_len, 128)))

    def ok(r, bs):
        return (r * bs * width * itemsize <= _TILE_BYTES
                and _vmem_bytes(r, bs, width, v_rank, heads, itemsize)
                <= _VMEM_BUDGET)

    r = max(x for x in range(1, min(b, _MAX_ROWS) + 1)
            if b % x == 0 and (x == 1 or ok(x, block_s)))
    while 2 * block_s * 8 <= s_len and ok(r, 2 * block_s):
        block_s *= 2
    return r, block_s


def _rows(scr):
    """(r, H, _LANES) lane-replicated scratch -> (r, H, 1) row values."""
    return jnp.max(scr[...], axis=-1, keepdims=True)


def _mla_decode_kernel(t_ref, q_ref, c_ref, o_ref, acc, m_scr, l_scr, *,
                       block_s, s_len, v_rank, n_chunks):
    """``r`` batch rows: fold cache chunk ``ki`` of each into its
    online-softmax state. The tile ``c`` (r, R, block_s), positions on
    the lanes, is read from VMEM for the scores (all R values) and for
    the weighted sum (the first ``v_rank``); the row's H heads are the
    M of both matmuls. State is lane-replicated (r, H, _LANES), Mosaic's
    legality rule for row values (``ops/decode.py``)."""
    ki = pl.program_id(1)
    t = t_ref[0]

    @pl.when(ki == 0)
    def _():
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc[...] = jnp.zeros_like(acc)

    def fold(c, live):
        s = lax.dot_general(
            q_ref[...], c, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)          # (r, H, block_s)
        if live is not None:
            s = jnp.where(live, s, _NEG_INF)
        m_prev = _rows(m_scr)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_new = _rows(l_scr) * alpha + jnp.sum(p, axis=-1, keepdims=True)
        pv = lax.dot_general(
            p.astype(c.dtype), c[:, :v_rank, :],
            (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)          # (r, H, v_rank)
        acc[...] = acc[...] * alpha + pv
        m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)

    # a chunk that ends at or before t is whole: no mask. The copies of
    # chunks that start past t are elided by the index map, their
    # compute by these guards
    @pl.when((ki + 1) * block_s <= t + 1)
    def _():
        fold(c_ref[...], None)

    # the one chunk that holds t: positions past t are masked; where it
    # hangs over the cache's end its columns there hold unspecified
    # values (NaN in interpret mode), and 0 x NaN is NaN, so they are
    # zeroed
    @pl.when((ki * block_s <= t) & (t + 1 < (ki + 1) * block_s))
    def _():
        c = c_ref[...]
        col = ki * block_s + lax.broadcasted_iota(
            jnp.int32, (1, 1, block_s), 2)
        if s_len % block_s:
            c = jnp.where(col < s_len, c, 0).astype(c.dtype)
        fold(c, col <= t)

    @pl.when(ki == n_chunks - 1)
    def _():
        o_ref[...] = acc[...] / jnp.maximum(_rows(l_scr), 1e-30)


@functools.partial(jax.jit, static_argnames=("v_rank", "interpret"))
def _mla_decode_pallas(q, cache, t, v_rank: int, interpret: bool = False):
    b, h, width = q.shape
    s_len = cache.shape[1]
    r, block_s = _tiles(b, s_len, width, v_rank, h, cache.dtype.itemsize)
    # The kernel reads the cache with the positions on the lanes, (B, R,
    # S). That is no copy where it matters: a row of R = 576 values is
    # no whole number of 128-lane tiles, so the chip keeps a (B, S, 576)
    # array with S minor as it is (the layout XLA prefers for the shape,
    # in the scan's carry too), and this transpose is a bitcast of it. A
    # kernel over (B, S, R) blocks made XLA re-lay the whole cache on
    # the way into the scan and out of it, 3 GB each way a call and as
    # much again in temporaries (compiled for a described v5e, PR 32).
    # Ceil-divided grid, no padding: the cache rides the scan's carry,
    # and a pad would copy it every generated token.
    ct = jnp.swapaxes(cache, 1, 2)
    n_chunks = -(-s_len // block_s)
    tarr = jnp.clip(jnp.asarray(t, jnp.int32), 0, s_len - 1).reshape(1)

    def chunk(ki, t_ref):
        # dead chunks clamp onto the chunk that holds t: consecutive
        # equal block indices skip the copy
        return jnp.minimum(ki, t_ref[0] // block_s)

    qspec = pl.BlockSpec((r, h, width), lambda i, ki, t_ref: (i, 0, 0),
                         memory_space=pltpu.VMEM)
    cspec = pl.BlockSpec((r, width, block_s),
                         lambda i, ki, t_ref: (i, 0, chunk(ki, t_ref)),
                         memory_space=pltpu.VMEM)
    ospec = pl.BlockSpec((r, h, v_rank), lambda i, ki, t_ref: (i, 0, 0),
                         memory_space=pltpu.VMEM)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b // r, n_chunks),
        in_specs=[qspec, cspec],
        out_specs=ospec,
        scratch_shapes=[pltpu.VMEM((r, h, v_rank), jnp.float32),
                        pltpu.VMEM((r, h, _LANES), jnp.float32),
                        pltpu.VMEM((r, h, _LANES), jnp.float32)],
    )
    return pl.pallas_call(
        functools.partial(_mla_decode_kernel, block_s=block_s, s_len=s_len,
                          v_rank=v_rank, n_chunks=n_chunks),
        grid_spec=grid_spec,
        out_shape=out_struct((b, h, v_rank), jnp.float32, q, ct),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name="_mla_decode_pallas",
    )(tarr, q, ct)


# the chip's tile of positions: the put writes the 128-position column
# that holds `t`
_TILE = 128
# a grid step of `_latent_row_put` carries at most this much of the cache
# (its tile column of `r` rows), and as much again out. us a put on a
# v5e, a loop of 10.2 us included, by rows a step
# (`benchmarks/results/latent_put_bench.json`): (32, 4160, 576) 1 38.3,
# 2 32.6, 4 28.8, 8 29.0, 16 27.2, 32 30.7 (XLA's dynamic-update-slice
# 91.5); (16, 32832, 576) 1 22.5, 2 20.6, 4 19.3, 8 18.5, 16 20.1 (50.2)
_PUT_BYTES = 3 << 19


def _put_rows(b: int, width: int, itemsize: int) -> int:
    """Rows a grid step of ``_latent_row_put`` carries: the largest
    divisor of ``b`` whose (r, width, 128) tile column stays within
    ``_PUT_BYTES``."""
    return max(r for r in range(1, b + 1)
               if b % r == 0 and (r == 1 or r * width * _TILE * itemsize
                                  <= _PUT_BYTES))


def _latent_row_put_kernel(t_ref, row_ref, c_ref, o_ref):
    """The tile column that holds ``t``, with lane ``t % 128`` of each
    row replaced by the new row (r, R, 1) and every other lane kept."""
    lane = lax.broadcasted_iota(jnp.int32, c_ref.shape, 2)
    o_ref[...] = jnp.where(lane == t_ref[0] % _TILE, row_ref[...],
                           c_ref[...])


@functools.partial(jax.jit, static_argnames=("rows", "interpret"))
def _latent_row_put(cache, row, t, rows: int | None = None,
                    interpret: bool = False):
    """``lax.dynamic_update_slice(cache, row, (0, t, 0))`` in place, for
    a cache (B, S, R) that ``_mla_decode_pallas`` reads, row (B, 1, R).

    On the chip such a cache is kept with the positions minor (the
    kernel's (B, R, S) view is a bitcast of it), so one position is one
    lane, at an unaligned index, in each of R / 16 tiles a session;
    XLA's dynamic-update-slice writes them a tile at a time, 2.5 us a
    session on a v5e. Here a grid step reads the one 128-lane tile
    column that holds ``t`` for ``rows`` sessions, replaces the lane and
    writes the column back over the same buffer (aliased in to out).
    Where that column hangs over the cache's end the positions past S
    are padding of the chip's tiles, dropped on the way out. ``t`` is
    clamped into the cache as the dynamic-update-slice clamps it."""
    b, s_len, width = cache.shape
    r = rows or _put_rows(b, width, cache.dtype.itemsize)
    ct = jnp.swapaxes(cache, 1, 2)
    tarr = jnp.clip(jnp.asarray(t, jnp.int32), 0, s_len - 1).reshape(1)
    column = pl.BlockSpec((r, width, _TILE),
                          lambda i, t_ref: (i, 0, t_ref[0] // _TILE),
                          memory_space=pltpu.VMEM)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b // r,),
        in_specs=[pl.BlockSpec((r, width, 1), lambda i, t_ref: (i, 0, 0),
                               memory_space=pltpu.VMEM),
                  column],
        out_specs=column,
    )
    out = pl.pallas_call(
        _latent_row_put_kernel,
        grid_spec=grid_spec,
        out_shape=out_struct(ct.shape, ct.dtype, ct, row),
        input_output_aliases={2: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
        name="_latent_row_put",
    )(tarr, jnp.swapaxes(row, 1, 2), ct)
    return jnp.swapaxes(out, 1, 2)


def latent_row_put(cache, row, t, *, backend: str = "auto"):
    """``cache`` (B, S, R) with the decode step's row (B, 1, R) written at
    position ``t``. On the latent flash-decode kernel's backend the
    in-place put of ``_latent_row_put``; elsewhere (and as its oracle)
    ``lax.dynamic_update_slice``."""
    backend = resolve_backend(backend, "mla_decode_attention")
    if backend == "xla":
        return lax.dynamic_update_slice(cache, row, (0, t, 0))
    return _latent_row_put(cache, row, t,
                           interpret=backend == "pallas_interpret")


def _mla_decode_xla(q, cache, t, v_rank: int):
    """The reference composition: scores of every head against every
    cached row, the ``slot <= t`` mask, a float32 softmax, and the
    weighted sum of the rows' latents, its weights rounded to the
    cache's type as the kernel rounds them."""
    s = jnp.einsum("bhr,bsr->bhs", q, cache,
                   preferred_element_type=jnp.float32)
    seen = jnp.arange(cache.shape[1])[None, None, :] <= t
    p = jax.nn.softmax(jnp.where(seen, s, _NEG_INF), axis=-1)
    return jnp.einsum("bhs,bsc->bhc", p.astype(cache.dtype),
                      cache[..., :v_rank],
                      preferred_element_type=jnp.float32)


def _scaled(q, scale: float):
    """The softmax scale goes into the (small) query once, not into
    every score."""
    return (q.astype(jnp.float32) * scale).astype(q.dtype)


def mla_decode_attention(q, cache, t, *, v_rank: int, scale: float,
                         backend: str = "auto"):
    """One decode position's absorbed latent attention over the cache.

    q (B, H, R): every head's query in the cache's basis (``[q_nope W_k
    | q_rope]``); cache (B, S, R): rows ``[c_kv | k_rope]``; ``t``:
    scalar int32, the position: slots with index > t are invisible.
    ``score = scale * q . row`` over all R values, softmax in float32
    over the slots <= t. Returns float32 (B, H, v_rank): the weighted
    sum of the rows' first ``v_rank`` values, for the caller to take
    out of the latent's basis."""
    backend = resolve_backend(backend, "mla_decode_attention")
    q = _scaled(q, scale)
    if backend == "xla":
        return _mla_decode_xla(q, cache, t, v_rank)
    return _mla_decode_pallas(q, cache, t, v_rank=v_rank,
                              interpret=backend == "pallas_interpret")


# float32 scores of one (query block, key block) pair that
# `mla_causal_attention` lets exist at a time
_SCORE_BYTES = 256 << 20


def mla_causal_attention(q, cache, pos, *, v_rank: int, scale: float):
    """A block of positions' absorbed latent attention over a cache
    that holds them already, causal.

    q (B, Q, H, R); cache (B, S, R); ``pos`` (Q,) int32 the queries'
    positions, rising (the last is the highest). Key blocks are read up
    to the one that holds ``pos[-1]`` and none past it, each folded
    into an online softmax, so the work is that of the keys the block
    can see and the scores exist a key block at a time. Returns float32
    (B, Q, H, v_rank)."""
    b, q_len, h, width = q.shape
    s_len = cache.shape[1]
    rows = q_len * h
    kb = max(128, _SCORE_BYTES // (4 * b * rows) // 128 * 128)
    kb = min(kb, 2048, s_len)
    qf = _scaled(q, scale).reshape(b, rows, width)
    row_pos = jnp.repeat(pos.astype(jnp.int32), h)[None, :, None]

    def fold(i, state):
        m, l, acc = state
        # the cache's last block starts where it still fits; what it
        # then shares with the block before is masked out
        start = jnp.minimum(i * kb, s_len - kb)
        c = lax.dynamic_slice(cache, (0, start, 0), (b, kb, width))
        key = start + jnp.arange(kb, dtype=jnp.int32)[None, None, :]
        s = jnp.einsum("bmr,bkr->bmk", qf, c,
                       preferred_element_type=jnp.float32)
        s = jnp.where((key <= row_pos) & (key >= i * kb), s, _NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new)
        l = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc = acc * alpha + jnp.einsum(
            "bmk,bkc->bmc", p.astype(cache.dtype), c[..., :v_rank],
            preferred_element_type=jnp.float32)
        return m_new, l, acc

    init = (jnp.full((b, rows, 1), _NEG_INF, jnp.float32),
            jnp.zeros((b, rows, 1), jnp.float32),
            jnp.zeros((b, rows, v_rank), jnp.float32))
    _, l, acc = lax.fori_loop(0, pos[-1] // kb + 1, fold, init)
    return (acc / jnp.maximum(l, 1e-30)).reshape(b, q_len, h, v_rank)
