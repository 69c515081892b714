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

Grid: (batch·heads, q-blocks, kv-blocks); the kv axis is the innermost
(sequential) dimension, accumulating into scratch and writing the
normalized output tile on its last step — the accumulator discipline of
ops/matmul.py. Causal masking compares global row/column indices built
from the program ids; padded tail rows/columns are masked the same way.

Backward: fused too (FlashAttention-2 shape). The forward saves only
(q, k, v, o, per-row logsumexp); the backward re-materializes each
(block_q, block_k) probability tile in VMEM from those — p = exp(s −
lse) — and accumulates dq in one kernel (kv innermost) and dk/dv in a
second (q innermost). No (L, L) matrix ever touches HBM in EITHER
direction, so training through the kernel is O(L·d) memory like
inference — previously the custom VJP re-ran the XLA composition,
paying the O(L²) HBM the forward existed to avoid.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
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


def _tile_live(qi, ki, block_q: int, block_k: int, causal: bool,
               window: int, q_offset: int = 0):
    """Whether tile (qi, ki) contains ANY visible score — the block-skip
    predicate (None = statically always live). Causal prunes tiles
    wholly above the diagonal; a window additionally prunes tiles wholly
    behind it (~L/window of the causal work at long L)."""
    row0 = qi * block_q + q_offset
    conds = []
    if causal:
        conds.append(ki * block_k <= row0 + block_q - 1)
    if window:
        conds.append(row0 - (ki * block_k + block_k - 1) < window)
    if not conds:
        return None
    live = conds[0]
    for c in conds[1:]:
        live = jnp.logical_and(live, c)
    return live


def _kv_clamp(qi, ki, *, block_q, block_k, causal, window, q_offset,
              n_kv):
    """Clamp a kv block index into q-block ``qi``'s LIVE range — the
    dead-tile DMA elision. ``pl.when`` skips the masked COMPUTE, but the
    pipeline still fetches every tile the index map names; re-mapping a
    dead step onto the nearest live block makes consecutive indices
    equal, and Pallas skips the copy when the index does not change.
    Causal halves kv traffic; a sliding window cuts it to O(window/L).
    Exactly _tile_live's algebra: live ⟹ clamp is the identity, so live
    steps always see their own tile (pinned by the interpret-mode parity
    suite across causal/window/offset/GQA)."""
    if not (causal or window):
        return ki
    row0 = qi * block_q + q_offset
    hi = ((row0 + block_q - 1) // block_k) if causal else n_kv - 1
    lo = ((row0 - window + 1) // block_k) if window else 0
    # bounds sanitization: a fully-dead geometry (every tile of this
    # grid row pruned) may cross the bounds or push them out of range;
    # the clamp must still emit an IN-RANGE index (any one — compute is
    # skipped), never a negative or overflowing DMA offset
    lo = jnp.clip(lo, 0, n_kv - 1)
    hi = jnp.clip(hi, lo, n_kv - 1)
    return jnp.clip(ki, lo, hi)


def _q_clamp(qi, ki, *, block_q, block_k, causal, window, q_offset,
             n_q):
    """The dkv-kernel twin of _kv_clamp: clamp a q block index into kv
    block ``ki``'s live range (q innermost there). Same liveness
    algebra transposed: causal gives the LOWER bound (q blocks above
    the diagonal are dead), the window gives the UPPER bound (q rows
    too far past the kv block see nothing)."""
    if not (causal or window):
        return qi
    lo = ((ki * block_k - q_offset) // block_q) if causal else 0
    # strict inequality: row0 < ki·bk + bk - 1 + window - q_offset,
    # so the last live block is (T - 1) // bq
    hi = (((ki * block_k + block_k - 2 + window - q_offset) // block_q)
          if window else n_q - 1)
    # same bounds sanitization as _kv_clamp (hi can go NEGATIVE here
    # when the kv block sits wholly behind the window — the banded
    # ring's far hop): crossed bounds must still yield in-range indices
    lo = jnp.clip(lo, 0, n_q - 1)
    hi = jnp.clip(hi, lo, n_q - 1)
    return jnp.clip(qi, lo, hi)


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


def _flash_kernel_nolse(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr,
                        acc_scr, **kw):
    """Inference variant: no lse output allocated or written at all —
    the plain forward (return_lse=False, outside any vjp) should not
    pay HBM for softmax state nobody reads."""
    _flash_kernel(q_ref, k_ref, v_ref, o_ref, None, m_scr, l_scr,
                  acc_scr, **kw)


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr,
                  acc_scr, *, scale: float, causal: bool, seq_len: int,
                  block_q: int, block_k: int, n_kv: int,
                  window: int = 0, q_offset: int = 0):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _():
        m_scr[:] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    def fold():
        # dots take the INPUT dtype (bf16×bf16→f32 is the MXU's native
        # mode — upcasting operands to f32 first quarters matmul
        # throughput); only the softmax bookkeeping runs in f32
        q = q_ref[0]                                    # (bq, d)
        k = k_ref[0]                                    # (bk, d)
        v = v_ref[0]

        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale

        # global positions: mask padded tail columns always, the upper
        # triangle when causal (padded q rows give garbage, sliced off)
        rows = qi * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        cols = ki * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        valid = _tile_mask(rows, cols, causal, window, seq_len,
                           q_offset)
        s = jnp.where(valid, s, _NEG_INF)

        m_prev = jnp.max(m_scr[:], axis=-1, keepdims=True)  # (bq, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
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

    live = _tile_live(qi, ki, block_q, block_k, causal, window,
                      q_offset)
    if live is None:
        fold()
    else:
        # skip kv blocks with no visible scores (above the causal
        # diagonal / behind the sliding window) — folding them is pure
        # wasted MXU time
        pl.when(live)(fold)

    @pl.when(ki == n_kv - 1)
    def _():
        l_fin = jnp.maximum(jnp.max(l_scr[:], axis=-1, keepdims=True),
                            1e-30)                      # (bq, 1)
        o_ref[0] = (acc_scr[:] / l_fin).astype(o_ref.dtype)
        if lse_ref is not None:
            # per-row logsumexp: the ONLY softmax state the fused
            # backward needs (p re-materializes as exp(s - lse))
            lse = (jnp.max(m_scr[:], axis=-1, keepdims=True)
                   + jnp.log(l_fin))
            lse_ref[0] = jnp.broadcast_to(lse, lse_ref.shape[1:])


# Tuned defaults from the on-chip sweep (benchmarks/flash_tune.py →
# results/flash_tune.json, second-round sweep, v5e 2026-07-31
# 11:32-11:38 UTC): (512, 512) is the decisive winner at every swept
# shape — fwd 0.501 ms at L=2048 (vs 2.077 ms at the original
# (128, 128), 0.778 at (256, 256)) and 1.80× the (256, 256) schedule
# on the L=4096 training path (6.545 vs 11.756 ms fwdbwd). Bigger
# tiles amortize the per-tile online-softmax state updates and halve
# the number of VMEM-refill boundaries; the f32 score tile at 512² is
# 1 MB, q/kv tiles 128 KB each at d=128 — comfortably inside VMEM
# with double buffering. Short sequences clamp down in _clamp_blocks;
# explicit callers (tiny windows, odd geometries) can still override.
_DEFAULT_BLOCK_Q = 512
_DEFAULT_BLOCK_K = 512


def _resolve_blocks(block_q, block_k):
    for nm, v in (("block_q", block_q), ("block_k", block_k)):
        if v is not None and v <= 0:  # match ops/matmul.py's validation
            raise ValueError(f"{nm} must be positive, got {v}")
    return (_DEFAULT_BLOCK_Q if block_q is None else block_q,
            _DEFAULT_BLOCK_K if block_k is None else block_k)


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

    block_q, block_k = _resolve_blocks(block_q, block_k)
    block_q, block_k = _clamp_blocks(l, block_q, block_k)
    qb = _pad_seq(_to_bh(q), block_q)
    kb = _pad_seq(_to_bh(k), block_k)
    vb = _pad_seq(_to_bh(v), block_k)
    n_q = qb.shape[1] // block_q
    n_kv = kb.shape[1] // block_k

    kern = _flash_kernel if with_lse else _flash_kernel_nolse
    clamp = functools.partial(_kv_clamp, block_q=block_q,
                              block_k=block_k, causal=causal,
                              window=window, q_offset=q_offset,
                              n_kv=n_kv)
    spec_o = pl.BlockSpec((1, block_q, d), lambda bh, qi, ki: (bh, qi, 0),
                          memory_space=pltpu.VMEM)
    spec_lse = pl.BlockSpec((1, block_q, _LANES),
                            lambda bh, qi, ki: (bh, qi, 0),
                            memory_space=pltpu.VMEM)
    # the lse path serves partial-merge callers (ring folds): its out
    # stays f32 so P merged partials round ONCE at the caller's final
    # cast, not once per ring step
    shape_o = out_struct(
        qb.shape, jnp.float32 if with_lse else q.dtype, qb, kb, vb)
    shape_lse = out_struct((b * h, qb.shape[1], _LANES), jnp.float32,
                           qb, kb, vb)
    res = pl.pallas_call(
        functools.partial(kern, scale=scale, causal=causal,
                          seq_len=l, block_q=block_q, block_k=block_k,
                          n_kv=n_kv, window=window,
                          q_offset=q_offset),
        grid=(b * h, n_q, n_kv),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda bh, qi, ki: (bh, qi, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_k, d),
                         lambda bh, qi, ki: (_kv_row(bh, h, hkv),
                                             clamp(qi, ki), 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_k, d),
                         lambda bh, qi, ki: (_kv_row(bh, h, hkv),
                                             clamp(qi, ki), 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=[spec_o, spec_lse] if with_lse else [spec_o],
        out_shape=[shape_o, shape_lse] if with_lse else [shape_o],
        scratch_shapes=[
            pltpu.VMEM((block_q, _LANES), jnp.float32),  # running max
            pltpu.VMEM((block_q, _LANES), jnp.float32),  # running denom
            pltpu.VMEM((block_q, d), jnp.float32),       # running output
        ],
        # (bh, qi) carry no cross-iteration state (scratch re-inits at
        # ki == 0); only the kv axis accumulates — telling Mosaic lets
        # it parallelize/pipeline across the first two grid axes
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="flash_pallas",
    )(qb, kb, vb)

    out = res[0]
    out = jnp.transpose(out[:, :l, :].reshape(b, h, l, d), (0, 2, 1, 3))
    if not with_lse:
        return out
    return out, res[1][:, :, 0]        # collapse the replicated lanes


def _bwd_tile(q, k, v, do, lse_ref, delta_ref, qi, ki, *, scale, causal,
              seq_len, block_q, block_k, window=0, q_offset=0):
    """Re-materialize one (block_q, block_k) tile's p and ds in VMEM —
    the shared core of both backward kernels. Returns (p, ds) in f32.

    ds = p ∘ (do·vᵀ − Δ) · scale, with Δ_i = Σ_d do_id·o_id computed
    once outside (the standard FlashAttention-2 identity: the softmax
    jacobian term Σ_j p_ij dp_ij equals Δ_i because o = p·v)."""
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    rows = qi * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)
    cols = ki * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)
    valid = _tile_mask(rows, cols, causal, window, seq_len, q_offset)
    lse = _row_read(lse_ref)                            # (bq, 1)
    p = jnp.where(valid, jnp.exp(s - lse), 0.0)
    dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    delta = _row_read(delta_ref)                        # (bq, 1)
    ds = p * (dp - delta) * scale
    return p, ds


def _flash_bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                         dq_ref, dq_scr, *, scale, causal, seq_len,
                         block_q, block_k, n_kv, window=0, q_offset=0):
    qi, ki = pl.program_id(1), pl.program_id(2)

    @pl.when(ki == 0)
    def _():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    def fold():
        k = k_ref[0]
        _, ds = _bwd_tile(q_ref[0], k, v_ref[0], do_ref[0], lse_ref,
                          delta_ref, qi, ki, scale=scale, causal=causal,
                          seq_len=seq_len, block_q=block_q,
                          block_k=block_k, window=window,
                          q_offset=q_offset)
        # dq_i += ds_ij · k_j  (scale already folded into ds)
        dq_scr[:] += jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    live = _tile_live(qi, ki, block_q, block_k, causal, window,
                      q_offset)
    if live is None:
        fold()
    else:
        pl.when(live)(fold)          # same tile pruning as the forward

    @pl.when(ki == n_kv - 1)
    def _():
        dq_ref[0] = dq_scr[:].astype(dq_ref.dtype)


def _flash_bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                          dk_ref, dv_ref, dk_scr, dv_scr, *, scale,
                          causal, seq_len, block_q, block_k, n_q,
                          n_inner, window=0, q_offset=0):
    """Grid: (b·h_kv, n_kv, n_inner) with n_inner = group·n_q — the
    innermost axis walks every (q-head-in-group, q-block) pair whose
    gradients land in THIS kv head's (dk, dv) tile, so GQA's
    sum-over-group falls out of the same scratch accumulation that
    already summed over q blocks (group = 1 reduces to plain MHA)."""
    ki, inner = pl.program_id(1), pl.program_id(2)
    qi = inner % n_q

    @pl.when(inner == 0)
    def _():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    def fold():
        q = q_ref[0]
        do = do_ref[0]
        p, ds = _bwd_tile(q, k_ref[0], v_ref[0], do, lse_ref, delta_ref,
                          qi, ki, scale=scale, causal=causal,
                          seq_len=seq_len, block_q=block_q,
                          block_k=block_k, window=window,
                          q_offset=q_offset)
        # dv_j += p_ijᵀ · do_i ; dk_j += ds_ijᵀ · q_i
        dv_scr[:] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dk_scr[:] += jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    live = _tile_live(qi, ki, block_q, block_k, causal, window,
                      q_offset)
    if live is None:
        fold()
    else:
        pl.when(live)(fold)

    @pl.when(inner == n_inner - 1)
    def _():
        dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


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

    block_q, block_k = _resolve_blocks(block_q, block_k)
    block_q, block_k = _clamp_blocks(l, block_q, block_k)
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
    kw = dict(scale=scale, causal=causal, seq_len=l,
              block_q=block_q, block_k=block_k, window=window,
              q_offset=q_offset)

    # row operands (lse, Δ) ride lane-replicated — see _LANES
    lse_r = _lane_rep(lse)
    delta_r = _lane_rep(delta)
    # dead-tile DMA elision (see _kv_clamp/_q_clamp): dq walks kv
    # innermost, dkv walks q innermost — each clamps its innermost
    # operand maps onto the live band
    kvc = functools.partial(_kv_clamp, block_q=block_q, block_k=block_k,
                            causal=causal, window=window,
                            q_offset=q_offset, n_kv=n_kv)
    qc = functools.partial(_q_clamp, block_q=block_q, block_k=block_k,
                           causal=causal, window=window,
                           q_offset=q_offset, n_q=n_q)
    spec_q = pl.BlockSpec((1, block_q, d), lambda bh, i, j: (bh, i, 0),
                          memory_space=pltpu.VMEM)
    spec_row = pl.BlockSpec((1, block_q, _LANES),
                            lambda bh, i, j: (bh, i, 0),
                            memory_space=pltpu.VMEM)
    spec_kv = pl.BlockSpec(
        (1, block_k, d),
        lambda bh, i, j: (_kv_row(bh, h, hkv), kvc(i, j), 0),
        memory_space=pltpu.VMEM)

    dq = pl.pallas_call(
        functools.partial(_flash_bwd_dq_kernel, n_kv=n_kv, **kw),
        grid=(b * h, n_q, n_kv),
        in_specs=[spec_q, spec_kv, spec_kv, spec_q, spec_row, spec_row],
        out_specs=spec_q,
        out_shape=out_struct(qb.shape, q.dtype, qb, kb, vb, dob),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="flash_bwd_pallas_dq",
    )(qb, kb, vb, dob, lse_r, delta_r)

    # dkv grid: one row per KV head, kv-block outer, and the innermost
    # axis walks (q-head-in-group × q-block) — the q-side index maps
    # recover the q grid row from (bhkv, inner // n_q)
    def q_row(bhkv, i):
        return (bhkv // hkv) * h + (bhkv % hkv) * group + i // n_q

    spec_q2 = pl.BlockSpec(
        (1, block_q, d),
        lambda bh, j, i: (q_row(bh, i), qc(i % n_q, j), 0),
        memory_space=pltpu.VMEM)
    spec_row2 = pl.BlockSpec(
        (1, block_q, _LANES),
        lambda bh, j, i: (q_row(bh, i), qc(i % n_q, j), 0),
        memory_space=pltpu.VMEM)
    spec_kv2 = pl.BlockSpec((1, block_k, d), lambda bh, j, i: (bh, j, 0),
                            memory_space=pltpu.VMEM)
    dk, dv = pl.pallas_call(
        functools.partial(_flash_bwd_dkv_kernel, n_q=n_q,
                          n_inner=group * n_q, **kw),
        grid=(b * hkv, n_kv, group * n_q),
        in_specs=[spec_q2, spec_kv2, spec_kv2, spec_q2, spec_row2,
                  spec_row2],
        out_specs=[spec_kv2, spec_kv2],
        out_shape=[out_struct(kb.shape, k.dtype, qb, kb, vb, dob),
                   out_struct(vb.shape, v.dtype, qb, kb, vb, dob)],
        scratch_shapes=[pltpu.VMEM((block_k, d), jnp.float32),
                        pltpu.VMEM((block_k, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="flash_bwd_pallas_dkv",
    )(qb, kb, vb, dob, lse_r, delta_r)

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
