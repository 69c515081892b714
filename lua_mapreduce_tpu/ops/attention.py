"""Fused flash attention — MXU matmuls with an online softmax in VMEM.

Completes the kernel library (SURVEY.md §2.4's APRIL-ANN-kernel role) for
the transformer family: one `pallas_call` computes softmax(QKᵀ·scale)·V
without ever materializing the (L, L) score matrix in HBM — scores live
in VMEM one (block_q, block_k) tile at a time, folded into running
(max, denominator, output) accumulators in f32 scratch. The ring
schedule (parallel/ring_attention.py) runs THIS kernel as its local
fold — ``return_lse`` exposes the mergeable-softmax state, and partial
attentions over disjoint KV shards combine by logaddexp weights — so
ring = flash with the KV loop distributed over ICI, literally.

Grid: (batch·heads, the row's live tiles): the inner (sequential) axis
walks a scalar-prefetched table of the (q-block, kv-block) pairs that
hold a visible score, a q block's kv blocks in a run, accumulating into
scratch and writing the normalized output tile on the run's last step —
the accumulator discipline of ops/matmul.py. A tile's class is decided
once, on the host, from its block indices (_class_grid): a dead tile
(above the causal diagonal, behind the window) is no step at all, an
interior tile (every pair visible) folds with no mask, and only an edge
tile (the diagonal, the window's far edge, the padded tail of columns)
compares global row/column indices.

Backward: fused too (FlashAttention-2 shape). The forward saves only
(q, k, v, o, per-row logsumexp); the backward re-materializes each
(block_q, block_k) probability tile in VMEM from those — p = exp(s −
lse) — and accumulates dq in one kernel (the forward's walk) and dk/dv
in a second (a kv block's live q blocks in a run). No (L, L) matrix
ever touches HBM in EITHER direction, so training through the kernel is
O(L·d) memory like inference — previously the custom VJP re-ran the XLA
composition, paying the O(L²) HBM the forward existed to avoid.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from lua_mapreduce_tpu.ops import out_struct, resolve_backend

_NEG_INF = -1e30

# Row-state arrays (running max / denominator / logsumexp / Δ) are
# lane-REPLICATED inside kernels. Mosaic requires every block's trailing
# two dims to be (divisible by 8, divisible by 128) or equal to the
# array's — a (1, block_q) row block fails that whenever b·h > 1, so
# per-row scalars ride as (block_q, _LANES) tiles whose lanes all hold
# the same value. Reads collapse lanes with a max (exact: all lanes
# equal); writes broadcast. 8 lanes, not 128: the block's lane dim then
# EQUALS the array's lane dim (the same legality clause head_dim < 128
# q/k/v blocks use), at 1/16th the HBM of full-width replication. CPU
# interpret mode never enforces any of this — round 3's suite was green
# while the kernel could not lower on the chip, which is exactly what
# the round-4 hardware window exposed.
_LANES = 8


def _row_read(ref):
    """(1, block_q, _LANES) lane-replicated ref → (block_q, 1) value."""
    return jnp.max(ref[0], axis=-1, keepdims=True)


def _lane_rep(x):
    """(bh, l) row array → (bh, l, _LANES) lane-replicated operand."""
    return jnp.broadcast_to(x[:, :, None], (*x.shape, _LANES))


def _tile_mask(rows, cols, causal: bool, window: int, seq_len: int,
               q_offset: int = 0):
    """Visibility of (row, col) score entries — THE mask definition,
    shared by the forward kernel, the backward tile re-materialization,
    and the XLA oracle so the three can never drift. ``window`` > 0
    additionally hides keys more than window-1 positions behind the
    query (sliding-window attention; implies causal). ``q_offset``
    shifts the query rows globally relative to the key columns — the
    banded-ring case where this call's q block sits q_offset positions
    AFTER its kv block (ring step i → offset i·L_loc, a STATIC value
    because the windowed ring unrolls its steps)."""
    rows = rows + q_offset
    valid = cols < seq_len
    if causal:
        valid = valid & (rows >= cols)
    if window:
        valid = valid & (rows - cols < window)
    return valid


# A tile's class, decided once on the host from its block indices — the
# kernels read it from the scalar-prefetched table and never ask again.
_FIRST, _LAST, _INTERIOR, _EDGE = 1, 2, 4, 8


def _class_grid(n_q: int, n_kv: int, *, block_q: int, block_k: int,
                causal: bool, window: int, seq_len: int,
                q_offset: int = 0):
    """(n_q, n_kv) classes of a (batch, head) row's tiles — _tile_mask's
    algebra on a tile's extreme rows and columns instead of on every
    element: 0 where NO pair is visible (dead: above the causal
    diagonal, wholly behind the window), _INTERIOR where EVERY pair is
    (the mask is the identity there, padded q rows included: it never
    looks at a row's own validity), _EDGE for the rest (the diagonal,
    the window's far edge, the ragged tail of columns)."""
    row_lo = np.arange(n_q)[:, None] * block_q + q_offset
    row_hi = row_lo + block_q - 1
    col_lo = np.arange(n_kv)[None, :] * block_k
    col_hi = col_lo + block_k - 1
    live = np.ones((n_q, n_kv), bool)
    interior = live & (col_hi < seq_len)
    if causal:
        live &= col_lo <= row_hi
        interior &= col_hi <= row_lo
    if window:
        live &= row_lo - col_hi < window
        interior &= row_hi - col_lo < window
    return np.where(live, np.where(interior, _INTERIOR, _EDGE), 0)


def tile_classes(l_q: int, l_k: int, block_q: int, block_k: int,
                 causal: bool, window: int = 0, q_offset: int = 0):
    """(dead, interior, edge) tile counts of one (batch, head) row at
    the given blocks — what the tile schedule skips, folds with no
    mask, and folds masked."""
    cls = _class_grid(-(-l_q // block_q), -(-l_k // block_k),
                      block_q=block_q, block_k=block_k, causal=causal,
                      window=window, seq_len=l_k, q_offset=q_offset)
    return tuple(int(np.sum(cls == c)) for c in (0, _INTERIOR, _EDGE))


# A step of the table is one int32, outer | inner | flags: the whole
# table is copied to scalar memory (1 MB on a v5e) before the kernel
# starts, and a long causal row has tens of thousands of live tiles.
_FLAG_BITS, _INNER_BITS, _OUTER_BITS = 4, 14, 13
_TABLE_STEPS = 200_000


def _tile_table(cls, group: int = 1):
    """The walk of a grid row over its LIVE tiles only, one int32 a
    step (read back by _step): the outer block (cls's row, whose
    scratch accumulates), the inner index (``g · n_inner + column``,
    every live column once for each of ``group`` members: the dkv
    kernel's walk over a kv head's q heads), and flags: the tile's
    class, _FIRST and _LAST on an outer block's first and last step. An
    outer block with no live tile keeps ONE step of class 0, which
    folds nothing and writes the block's zeros."""
    n_outer, n_inner = cls.shape
    steps = []
    for o, row in enumerate(cls):
        cols = np.flatnonzero(row)
        inner = (np.arange(group)[:, None] * n_inner + cols).ravel()
        flags = np.tile(row[cols], group)
        if not inner.size:
            inner, flags = np.zeros(1, int), np.zeros(1, int)
        flags[0] |= _FIRST
        flags[-1] |= _LAST
        steps.append(o << (_INNER_BITS + _FLAG_BITS)
                     | inner << _FLAG_BITS | flags)
    table = np.concatenate(steps).astype(np.int32)
    if (n_outer > 1 << _OUTER_BITS or group * n_inner > 1 << _INNER_BITS
            or table.size > _TABLE_STEPS):
        raise ValueError(
            f"flash attention: {n_outer} x {group * n_inner} blocks, "
            f"{table.size} live tiles a row, are more than the tile "
            f"table holds ({1 << _OUTER_BITS} x {1 << _INNER_BITS}, "
            f"{_TABLE_STEPS}); pass larger block_q / block_k")
    return table


def _step(table_ref, s):
    """(outer, inner, flags) of the table's step ``s``."""
    word = table_ref[s]
    return (word >> (_INNER_BITS + _FLAG_BITS),
            (word >> _FLAG_BITS) & ((1 << _INNER_BITS) - 1),
            word & ((1 << _FLAG_BITS) - 1))


def _table_bits(table, interpret: bool):
    """(the flag bits SOME step of the table has, the bits EVERY step
    has) — what _on builds a step's bodies from. The interpreter gets
    no bit of the second kind: it evaluates a kernel's top level op by
    op, where shard_map's vma typing refuses an op that mixes a varying
    block with a constant; inside a branch it does not look."""
    flags = table & ((1 << _FLAG_BITS) - 1)
    return (int(np.bitwise_or.reduce(flags)),
            0 if interpret else int(np.bitwise_and.reduce(flags)))


def _on(flag, bit: int, bits, body):
    """Run ``body`` on the steps whose flags have ``bit``. A bit no step
    of the table has builds no body at all; a bit every step has (a
    row of one tile is _FIRST, _LAST and its class at once; a call
    with no diagonal is all _INTERIOR) runs it with no branch, so the
    step stays one straight block the compiler schedules whole."""
    some, every = bits
    if every & bit:
        body()
    elif some & bit:
        pl.when(flag & bit != 0)(body)


def _walk(flag, bits, init, fold, finish):
    """One step of a kernel's walk: ``init`` on a run's first step,
    ``fold(masked)`` by the tile's class, ``finish`` on its last."""
    _on(flag, _FIRST, bits, init)
    _on(flag, _INTERIOR, bits, functools.partial(fold, False))
    _on(flag, _EDGE, bits, functools.partial(fold, True))
    _on(flag, _LAST, bits, finish)


def _attn_reference_xla(q, k, v, causal: bool, scale: float,
                        with_lse: bool = False, window: int = 0,
                        q_offset: int = 0):
    group = q.shape[2] // k.shape[2]
    if group > 1:                   # GQA: each kv head serves a group
        k = jnp.repeat(k, group, axis=2)
        v = jnp.repeat(v, group, axis=2)
    s = jnp.einsum("blhd,bmhd->bhlm", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    mask = None
    if causal or window:
        lq, lk = s.shape[-2], s.shape[-1]
        rows = jnp.arange(lq)[:, None]
        cols = jnp.arange(lk)[None, :]
        mask = _tile_mask(rows, cols, causal, window, lk, q_offset)
        s = jnp.where(mask, s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    if mask is not None:
        # a row with NO visible column (q_offset pushes it more than
        # `window` past every key — the banded-ring far block) must emit
        # ZERO, matching the kernel's convention (out 0, lse ≈ -inf, so
        # ring merges weight it out); softmax over an all-masked row
        # would otherwise return a meaningless uniform average
        p = jnp.where(jnp.any(mask, axis=-1)[None, None, :, None],
                      p, 0.0)
    out32 = jnp.einsum("bhlm,bmhd->blhd", p, v.astype(jnp.float32))
    if not with_lse:
        return out32.astype(q.dtype)
    lse = jax.scipy.special.logsumexp(s, axis=-1)       # (B, H, L)
    # f32 out, matching the pallas lse path's partial-merge contract
    return out32, jnp.transpose(lse, (0, 2, 1))         # (B, L, H)


def _tile_positions(qi, ki, block_q: int, block_k: int):
    """Global (rows, cols) of a tile's entries — only a masked (edge)
    body builds them."""
    rows = qi * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)
    cols = ki * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)
    return rows, cols


def _flash_kernel_nolse(table_ref, q_ref, k_ref, v_ref, o_ref, m_scr,
                        l_scr, acc_scr, **kw):
    """Inference variant: no lse output allocated or written at all —
    the plain forward (return_lse=False, outside any vjp) should not
    pay HBM for softmax state nobody reads."""
    _flash_kernel(table_ref, q_ref, k_ref, v_ref, o_ref, None, m_scr,
                  l_scr, acc_scr, **kw)


def _flash_kernel(table_ref, q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr,
                  l_scr, acc_scr, *, scale: float, causal: bool,
                  seq_len: int, block_q: int, block_k: int, bits,
                  window: int = 0, q_offset: int = 0):
    qi, ki, flag = _step(table_ref, pl.program_id(1))

    def init():
        m_scr[:] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    def fold(masked):
        # dots take the INPUT dtype (bf16×bf16→f32 is the MXU's native
        # mode — upcasting operands to f32 first quarters matmul
        # throughput); only the softmax bookkeeping runs in f32
        q = q_ref[0]                                    # (bq, d)
        k = k_ref[0]                                    # (bk, d)
        v = v_ref[0]

        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale

        if masked:
            # global positions: mask padded tail columns always, the
            # upper triangle when causal (padded q rows give garbage,
            # sliced off). An interior tile's mask is all true, so its
            # body has no iota, compare or select: the same values.
            valid = _tile_mask(*_tile_positions(qi, ki, block_q, block_k),
                               causal, window, seq_len, q_offset)
            s = jnp.where(valid, s, _NEG_INF)

        m_prev = jnp.max(m_scr[:], axis=-1, keepdims=True)  # (bq, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        if masked:
            p = jnp.where(valid, p, 0.0)
        alpha = jnp.exp(m_prev - m_new)
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
        l_prev = jnp.max(l_scr[:], axis=-1, keepdims=True)
        l_scr[:] = jnp.broadcast_to(
            l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True),
            l_scr.shape)
        # p folds back to the value dtype for the MXU; the f32 denominator
        # (summed above, BEFORE the downcast) keeps normalization exact
        acc_scr[:] = acc_scr[:] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    def finish():
        l_fin = jnp.maximum(jnp.max(l_scr[:], axis=-1, keepdims=True),
                            1e-30)                      # (bq, 1)
        o_ref[0] = (acc_scr[:] / l_fin).astype(o_ref.dtype)
        if lse_ref is not None:
            # per-row logsumexp: the ONLY softmax state the fused
            # backward needs (p re-materializes as exp(s - lse))
            lse = (jnp.max(m_scr[:], axis=-1, keepdims=True)
                   + jnp.log(l_fin))
            lse_ref[0] = jnp.broadcast_to(lse, lse_ref.shape[1:])

    _walk(flag, bits, init, fold, finish)


def _resolve_blocks(l: int, block_q, block_k, causal: bool, window: int,
                    q_offset: int):
    """The caller's blocks, else the ones the shape chooses (the sweep:
    benchmarks/flash_tune.py → results/flash_tune.json, v5e, at the
    train cells' shapes, forward + backward kernels, us a call).
    (512, 512) wins wherever a row has a diagonal: its edge tiles are
    masked and half empty, and twice as wide they are three quarters
    empty (a ring's diagonal hop at L 2048: 5,766 against 6,429 at
    (512, 1024); the one-chip cell's 4096: 16,155 against 15,906, level).
    A call whose tiles are ALL interior (the banded ring's off-diagonal
    hops, full attention over whole blocks) has nothing to mask and
    nothing empty, and a (512, 1024) tile halves its steps: 8,735 ->
    7,924. Smaller blocks lose everywhere, by 1.35x at (256, 512) to
    11x at (64, 128). Short sequences clamp down in _clamp_blocks."""
    for nm, v in (("block_q", block_q), ("block_k", block_k)):
        if v is not None and v <= 0:  # match ops/matmul.py's validation
            raise ValueError(f"{nm} must be positive, got {v}")
    if block_k is None:
        bq, bk = _clamp_blocks(l, block_q or 512, 1024)
        wide = _class_grid(-(-l // bq), -(-l // bk), block_q=bq,
                           block_k=bk, causal=causal, window=window,
                           seq_len=l, q_offset=q_offset)
        block_k = 1024 if (wide == _INTERIOR).all() else 512
    return _clamp_blocks(l, block_q or 512, block_k)


def _clamp_blocks(l: int, block_q: int, block_k: int):
    """Shared fwd/bwd block clamping — the backward re-derives the
    forward's padded geometry from (l, block_q, block_k) and the two
    must agree exactly (the saved lse is laid out in these blocks)."""
    return (min(block_q, max(8, -(-l // 8) * 8)),
            min(block_k, max(128, -(-l // 128) * 128)))


def _pad_seq(x, block: int):
    p = -x.shape[1] % block
    return jnp.pad(x, ((0, 0), (0, p), (0, 0))) if p else x


def _to_bh(x):
    """(B, L, H, D) → (B·H, L, D): one grid row per (batch, head)."""
    b, l, h, d = x.shape
    return jnp.transpose(x, (0, 2, 1, 3)).reshape(b * h, l, d)


def _kv_row(bh, h: int, hkv: int):
    """Grid row of the kv head serving q-grid-row ``bh`` (GQA): q head
    ``hq`` reads kv head ``hq // (h//hkv)``; identity when h == hkv."""
    if h == hkv:
        return bh
    group = h // hkv
    return (bh // h) * hkv + (bh % h) // group


@functools.partial(
    jax.jit, static_argnames=("causal", "block_q", "block_k", "interpret",
                              "with_lse", "window", "q_offset"))
def _flash_pallas(q, k, v, causal, block_q=None, block_k=None,
                  interpret=False, with_lse=False, window=0,
                  q_offset=0):
    b, l, h, d = q.shape
    hkv = k.shape[2]
    scale = 1.0 / float(d) ** 0.5

    block_q, block_k = _resolve_blocks(l, block_q, block_k, causal,
                                       window, q_offset)
    qb = _pad_seq(_to_bh(q), block_q)
    kb = _pad_seq(_to_bh(k), block_k)
    vb = _pad_seq(_to_bh(v), block_k)
    n_q = qb.shape[1] // block_q
    n_kv = kb.shape[1] // block_k

    kern = _flash_kernel if with_lse else _flash_kernel_nolse
    geom = dict(causal=causal, seq_len=l, block_q=block_q,
                block_k=block_k, window=window, q_offset=q_offset)
    cls = _class_grid(n_q, n_kv, **geom)
    table = _tile_table(cls)
    spec_q = pl.BlockSpec((1, block_q, d),
                          lambda bh, s, t: (bh, _step(t, s)[0], 0),
                          memory_space=pltpu.VMEM)
    spec_kv = pl.BlockSpec(
        (1, block_k, d),
        lambda bh, s, t: (_kv_row(bh, h, hkv), _step(t, s)[1], 0),
        memory_space=pltpu.VMEM)
    spec_lse = pl.BlockSpec((1, block_q, _LANES),
                            lambda bh, s, t: (bh, _step(t, s)[0], 0),
                            memory_space=pltpu.VMEM)
    # the lse path serves partial-merge callers (ring folds): its out
    # stays f32 so P merged partials round ONCE at the caller's final
    # cast, not once per ring step
    shape_o = out_struct(
        qb.shape, jnp.float32 if with_lse else q.dtype, qb, kb, vb)
    shape_lse = out_struct((b * h, qb.shape[1], _LANES), jnp.float32,
                           qb, kb, vb)
    res = pl.pallas_call(
        functools.partial(kern, scale=scale,
                          bits=_table_bits(table, interpret), **geom),
        # the inner axis walks the row's live (q block, kv block) pairs
        # from the table, a q block's pairs in a run: the out tile
        # stays put while its kv blocks fold into scratch
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b * h, table.size),
            in_specs=[spec_q, spec_kv, spec_kv],
            out_specs=[spec_q, spec_lse] if with_lse else [spec_q],
            scratch_shapes=[
                pltpu.VMEM((block_q, _LANES), jnp.float32),  # running max
                pltpu.VMEM((block_q, _LANES), jnp.float32),  # running denom
                pltpu.VMEM((block_q, d), jnp.float32),       # running output
            ]),
        out_shape=[shape_o, shape_lse] if with_lse else [shape_o],
        # a (batch, head) row carries no state to the next (scratch
        # re-inits on a q block's first step); only the walk accumulates
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="flash_pallas",
    )(table, qb, kb, vb)

    out = res[0]
    out = jnp.transpose(out[:, :l, :].reshape(b, h, l, d), (0, 2, 1, 3))
    if not with_lse:
        return out
    return out, res[1][:, :, 0]        # collapse the replicated lanes


def _bwd_tile(q, k, v, do, lse_ref, delta_ref, qi, ki, masked, *, scale,
              causal, seq_len, block_q, block_k, window=0, q_offset=0):
    """Re-materialize one (block_q, block_k) tile's p and ds in VMEM —
    the shared core of both backward kernels. Returns (p, ds) in f32.

    ds = p ∘ (do·vᵀ − Δ) · scale, with Δ_i = Σ_d do_id·o_id computed
    once outside (the standard FlashAttention-2 identity: the softmax
    jacobian term Σ_j p_ij dp_ij equals Δ_i because o = p·v)."""
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    lse = _row_read(lse_ref)                            # (bq, 1)
    p = jnp.exp(s - lse)
    if masked:          # an interior tile's mask is all true: no select
        p = jnp.where(
            _tile_mask(*_tile_positions(qi, ki, block_q, block_k),
                       causal, window, seq_len, q_offset), p, 0.0)
    dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    delta = _row_read(delta_ref)                        # (bq, 1)
    ds = p * (dp - delta) * scale
    return p, ds


def _flash_bwd_dq_kernel(table_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                         delta_ref, dq_ref, dq_scr, *, bits, **kw):
    """The forward's walk: a q block's live kv blocks in a run."""
    qi, ki, flag = _step(table_ref, pl.program_id(1))

    def init():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    def fold(masked):
        k = k_ref[0]
        _, ds = _bwd_tile(q_ref[0], k, v_ref[0], do_ref[0], lse_ref,
                          delta_ref, qi, ki, masked, **kw)
        # dq_i += ds_ij · k_j  (scale already folded into ds)
        dq_scr[:] += jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    def finish():
        dq_ref[0] = dq_scr[:].astype(dq_ref.dtype)

    _walk(flag, bits, init, fold, finish)


def _flash_bwd_dkv_kernel(table_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                          delta_ref, dk_ref, dv_ref, dk_scr, dv_scr, *,
                          n_q, bits, **kw):
    """Grid: (b·h_kv, steps): a kv block's run walks every live
    (q-head-in-group, q-block) pair, ``inner = g · n_q + qi``, whose
    gradients land in THIS kv head's (dk, dv) tile, so GQA's
    sum-over-group falls out of the same scratch accumulation that
    already summed over q blocks (group = 1 reduces to plain MHA)."""
    ki, inner, flag = _step(table_ref, pl.program_id(1))
    qi = inner % n_q

    def init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    def fold(masked):
        q = q_ref[0]
        do = do_ref[0]
        p, ds = _bwd_tile(q, k_ref[0], v_ref[0], do, lse_ref, delta_ref,
                          qi, ki, masked, **kw)
        # dv_j += p_ijᵀ · do_i ; dk_j += ds_ijᵀ · q_i
        dv_scr[:] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dk_scr[:] += jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    def finish():
        dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)

    _walk(flag, bits, init, fold, finish)


@functools.partial(
    jax.jit, static_argnames=("causal", "block_q", "block_k", "interpret",
                              "window", "q_offset"))
def _flash_bwd_pallas(q, k, v, o, lse, g, causal, block_q=None,
                      block_k=None, interpret=False, g_lse=None,
                      window=0, q_offset=0):
    """Fused backward: (dq, dk, dv) with only O(L·d) HBM traffic.

    ``lse`` is the forward's saved per-row logsumexp, already in the
    padded (B·H, Lq_pad) layout. Δ = Σ_d do∘o is computed here in one
    fused XLA elementwise pass (O(L·d), not worth a kernel).

    ``g_lse`` (B, L, H), when given, is the cotangent of the lse OUTPUT
    (callers like the ring fold differentiate through it): since
    ∂lse_i/∂s_ij = p_ij, its whole contribution is ds += g_lse∘p — the
    same rank-1 row term as Δ with the opposite sign, so it folds into
    the delta operand and the kernels need no change at all."""
    b, l, h, d = q.shape
    hkv = k.shape[2]
    group = h // hkv
    scale = 1.0 / float(d) ** 0.5

    block_q, block_k = _resolve_blocks(l, block_q, block_k, causal,
                                       window, q_offset)
    qb = _pad_seq(_to_bh(q), block_q)
    kb = _pad_seq(_to_bh(k), block_k)
    vb = _pad_seq(_to_bh(v), block_k)
    dob = _pad_seq(_to_bh(g), block_q)
    ob = _pad_seq(_to_bh(o), block_q)
    delta = jnp.sum(dob.astype(jnp.float32) * ob.astype(jnp.float32),
                    axis=-1)                        # (B·H, Lq_pad)
    # kernel dots need matching operand dtypes: the lse path's cotangent
    # arrives f32 (its out is f32); Δ above already banked the f32
    # precision, so the per-tile dp/dv dots run MXU-native in q.dtype
    dob = dob.astype(q.dtype)
    if g_lse is not None:
        gl = jnp.transpose(g_lse, (0, 2, 1)).reshape(b * h, l)
        pad = delta.shape[1] - l
        if pad:
            gl = jnp.pad(gl, ((0, 0), (0, pad)))
        delta = delta - gl.astype(jnp.float32)
    n_q = qb.shape[1] // block_q
    n_kv = kb.shape[1] // block_k
    geom = dict(causal=causal, seq_len=l, block_q=block_q,
                block_k=block_k, window=window, q_offset=q_offset)
    cls = _class_grid(n_q, n_kv, **geom)
    kw = dict(scale=scale, **geom)

    # row operands (lse, Δ) ride lane-replicated — see _LANES
    lse_r = _lane_rep(lse)
    delta_r = _lane_rep(delta)

    def specs(q_at, kv_at):
        """(q-side, kv-side, row-state) block specs of a walk whose
        ``q_at`` / ``kv_at`` map (grid row, the step's outer, inner) to
        the operand's (row, block)."""
        def spec(at, block, lanes):
            return pl.BlockSpec(
                (1, block, lanes),
                lambda row, s, t: (*at(row, *_step(t, s)[:2]), 0),
                memory_space=pltpu.VMEM)
        return (spec(q_at, block_q, d), spec(kv_at, block_k, d),
                spec(q_at, block_q, _LANES))

    params = pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary"))
    operands = (qb, kb, vb, dob, lse_r, delta_r)

    # dq walks the forward's table: a q block's live kv blocks in a run
    table = _tile_table(cls)
    spec_q, spec_kv, spec_row = specs(
        lambda bh, qi, ki: (bh, qi),
        lambda bh, qi, ki: (_kv_row(bh, h, hkv), ki))
    dq = pl.pallas_call(
        functools.partial(_flash_bwd_dq_kernel,
                          bits=_table_bits(table, interpret), **kw),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b * h, table.size),
            in_specs=[spec_q, spec_kv, spec_kv, spec_q, spec_row,
                      spec_row],
            out_specs=spec_q,
            scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)]),
        out_shape=out_struct(qb.shape, q.dtype, *operands[:4]),
        compiler_params=params,
        interpret=interpret,
        name="flash_bwd_pallas_dq",
    )(table, *operands)

    # dkv: one grid row per KV head; a kv block's run walks its live
    # (q-head-in-group × q-block) pairs — the q-side index map recovers
    # the q grid row from (bhkv, inner // n_q)
    table = _tile_table(cls.T, group)
    spec_q, spec_kv, spec_row = specs(
        lambda bhkv, ki, inner: (
            (bhkv // hkv) * h + (bhkv % hkv) * group + inner // n_q,
            inner % n_q),
        lambda bhkv, ki, inner: (bhkv, ki))
    dk, dv = pl.pallas_call(
        functools.partial(_flash_bwd_dkv_kernel, n_q=n_q,
                          bits=_table_bits(table, interpret), **kw),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b * hkv, table.size),
            in_specs=[spec_q, spec_kv, spec_kv, spec_q, spec_row,
                      spec_row],
            out_specs=[spec_kv, spec_kv],
            scratch_shapes=[pltpu.VMEM((block_k, d), jnp.float32),
                            pltpu.VMEM((block_k, d), jnp.float32)]),
        out_shape=[out_struct(kb.shape, k.dtype, *operands[:4]),
                   out_struct(vb.shape, v.dtype, *operands[:4])],
        compiler_params=params,
        interpret=interpret,
        name="flash_bwd_pallas_dkv",
    )(table, *operands)

    def from_bh(x, ln, heads):
        return jnp.transpose(x[:, :ln, :].reshape(b, heads, ln, d),
                             (0, 2, 1, 3))

    return (from_bh(dq, l, h), from_bh(dk, l, hkv),
            from_bh(dv, l, hkv))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _flash_p(q, k, v, cfg):
    causal, block_q, block_k, interpret, window, q_off = cfg
    return _flash_pallas(q, k, v, causal, block_q=block_q,
                         block_k=block_k, interpret=interpret,
                         window=window, q_offset=q_off)


def _flash_fwd(q, k, v, cfg):
    causal, block_q, block_k, interpret, window, q_off = cfg
    o, lse = _flash_pallas(q, k, v, causal, block_q=block_q,
                           block_k=block_k, interpret=interpret,
                           with_lse=True, window=window,
                           q_offset=q_off)
    # primal must match _flash_p's eval dtype (q.dtype) — the with_lse
    # kernel emits f32; keep THAT in the residuals (sharper delta)
    return o.astype(q.dtype), (q, k, v, o, lse)


def _flash_bwd(cfg, res, g):
    causal, block_q, block_k, interpret, window, q_off = cfg
    q, k, v, o, lse = res
    return _flash_bwd_pallas(q, k, v, o, lse, g, causal,
                             block_q=block_q, block_k=block_k,
                             interpret=interpret, window=window,
                             q_offset=q_off)


_flash_p.defvjp(_flash_fwd, _flash_bwd)


def _lse_public(lse, b, l, h):
    """Padded (B·H, Lq_pad) → public (B, L, H) f32."""
    return jnp.transpose(lse[:, :l].reshape(b, h, l), (0, 2, 1))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _flash_p_lse(q, k, v, cfg):
    """(out, lse (B, L, H)) — the two-output form ring folds consume;
    gradients flow through BOTH outputs (see _flash_bwd_pallas g_lse)."""
    causal, block_q, block_k, interpret, window, q_off = cfg
    b, l, h, _ = q.shape
    o, lse = _flash_pallas(q, k, v, causal, block_q=block_q,
                           block_k=block_k, interpret=interpret,
                           with_lse=True, window=window,
                           q_offset=q_off)
    return o, _lse_public(lse, b, l, h)


def _flash_lse_fwd(q, k, v, cfg):
    causal, block_q, block_k, interpret, window, q_off = cfg
    b, l, h, _ = q.shape
    o, lse = _flash_pallas(q, k, v, causal, block_q=block_q,
                           block_k=block_k, interpret=interpret,
                           with_lse=True, window=window,
                           q_offset=q_off)
    return (o, _lse_public(lse, b, l, h)), (q, k, v, o, lse)


def _flash_lse_bwd(cfg, res, g):
    causal, block_q, block_k, interpret, window, q_off = cfg
    g_out, g_lse = g
    q, k, v, o, lse = res
    return _flash_bwd_pallas(q, k, v, o, lse, g_out, causal,
                             block_q=block_q, block_k=block_k,
                             interpret=interpret, g_lse=g_lse,
                             window=window, q_offset=q_off)


_flash_p_lse.defvjp(_flash_lse_fwd, _flash_lse_bwd)


def flash_attention(q, k, v, *, causal: bool = False,
                    backend: str = "auto",
                    block_q: int | None = None,
                    block_k: int | None = None,
                    return_lse: bool = False,
                    window: int = 0, q_offset: int = 0):
    """Exact softmax attention, (B, L, H, D) → (B, L, H, D).

    ``backend="pallas"``/``"pallas_interpret"`` runs the fused VMEM
    kernel; ``"xla"`` is the reference composition (correctness oracle,
    non-TPU platforms).

    Grouped-query attention: k/v may carry FEWER heads than q (H_kv
    dividing H) — q head ``h`` attends kv head ``h // (H/H_kv)``. The
    kernels regroup via index maps (kv tiles re-read per group member;
    the dkv backward walks each kv head's whole q group in its scratch
    accumulation), so GQA costs no extra HBM materialization either.

    ``return_lse=True`` also returns the per-row logsumexp of the
    masked scores, shape (B, L, H) f32 — the mergeable-softmax state
    that lets callers combine partial attentions over disjoint KV sets
    (the ring fold's contract). The out is then f32 too (partials must
    round once at the caller's final cast, not per merge step).
    Differentiable through BOTH outputs.

    Backward-precision note (return_lse path): the out-cotangent
    arrives f32 but the backward's dp/dv dots run in q.dtype — at bf16
    the gradients round there, so training grads are slightly less
    precise than the forward's round-once f32 merge contract. This is
    the standard MXU tradeoff (bf16 dots are what make the kernel
    fast); validate grad error vs the XLA oracle at bf16 if a new
    recipe is sensitive to it."""
    backend = resolve_backend(backend, "flash_attention")
    if window:
        if not causal:
            raise ValueError("sliding window (window > 0) implies "
                             "causal attention")
        if window < 1:
            raise ValueError(f"window must be >= 1, got {window}")
    if q_offset:
        if not window:
            raise ValueError("q_offset only applies to windowed "
                             "attention (the banded-ring case)")
        if q_offset < 0:
            raise ValueError(f"q_offset must be >= 0, got {q_offset}")
    if k.shape != v.shape:
        raise ValueError(f"k/v shapes differ: {k.shape} vs {v.shape}")
    if (q.shape[0], q.shape[1], q.shape[3]) != \
            (k.shape[0], k.shape[1], k.shape[3]):
        raise ValueError(f"q/k shapes incompatible: {q.shape} vs "
                         f"{k.shape} (batch, seq, head_dim must match)")
    if q.shape[2] % k.shape[2]:
        raise ValueError(
            f"GQA needs q heads divisible by kv heads: {q.shape[2]} "
            f"vs {k.shape[2]}")
    # the kernel's dots run in the operand dtype (MXU-native bf16 path),
    # so mixed q/k/v dtypes are promoted HERE — otherwise dot_general
    # fails deep inside the pallas trace with no user-facing cause
    dt = jnp.promote_types(q.dtype, jnp.promote_types(k.dtype, v.dtype))
    q, k, v = q.astype(dt), k.astype(dt), v.astype(dt)
    if backend == "xla":
        scale = 1.0 / float(q.shape[-1]) ** 0.5
        return _attn_reference_xla(q, k, v, causal, scale,
                                   with_lse=return_lse, window=window,
                                   q_offset=q_offset)
    cfg = (causal, block_q, block_k, backend == "pallas_interpret",
           window, q_offset)
    if return_lse:
        return _flash_p_lse(q, k, v, cfg)
    return _flash_p(q, k, v, cfg)
