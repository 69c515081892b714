"""Sequence/context parallelism: ring attention + Ulysses all-to-all.

The reference has no sequence dimension (SURVEY.md §5 "Long-context …
absent"), but its two shuffle topologies are exactly the two ways long
sequences are parallelized on a TPU mesh, so this framework treats them
as first-class:

- **Ring attention** (:func:`ring_attention`) is the *streaming k-way
  merge* shape (utils.lua:206-271): no device ever materializes the full
  sequence; KV shards rotate around the ring (``ppermute`` over ICI, one
  neighbor hop per step) while each device folds incoming blocks into an
  online-softmax accumulator — compute overlaps the next block's DMA,
  the same overlap the reference gets by merging file streams lazily.
  Each local fold runs the FUSED flash kernel (ops/attention.py) via
  its ``return_lse`` contract and merges by logaddexp weights, so
  per-device memory is O(L/P · d) — scores never materialize even
  device-locally, in forward OR backward — enabling context lengths
  that cannot fit on one chip.

- **Ulysses** (:func:`ulysses_attention`) is the *partitionfn →
  all_to_all* shuffle shape (SURVEY.md §2.6): one collective reshards
  from sequence-sharded to head-sharded, each device runs its heads'
  full attention locally, and the inverse all_to_all reshards back.
  Cheaper per step than a ring when heads ≥ devices and the full
  sequence fits per device head-slice.

Both compute EXACTLY standard softmax attention — tests golden-diff them
against :func:`attention_reference` (the single-device oracle), the same
discipline test.sh applies to the wordcount engine (SURVEY.md §4).

Layout: (batch, seq, heads, head_dim), sequence sharded over the mesh
axis (default ``"sp"``). All einsums are MXU contractions; the online
softmax keeps f32 accumulators regardless of input dtype (bf16-safe).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax, shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

from lua_mapreduce_tpu.ops.attention import flash_attention
from lua_mapreduce_tpu.utils.profiling import scope

_NEG_INF = -1e30      # finite mask fill: -inf breaks the m-subtraction


def attention_reference(q, k, v, *, causal: bool = False,
                        window: int = 0):
    """Single-device softmax attention oracle, (B, L, H, D) layout —
    ONE oracle for the whole framework (delegates to the kernel
    library's XLA reference so the two can never diverge)."""
    return flash_attention(q, k, v, causal=causal, backend="xla",
                           window=window)


def _flash_block(q, kb, vb, causal: bool, window: int = 0,
                 q_offset: int = 0):
    """One device-local attention block through the FUSED kernel
    (``ops.flash_attention``: Pallas on TPU, the XLA composition
    elsewhere), returning (out, lse) — the mergeable-softmax state.
    This makes the flash kernel the hot inner loop of the whole
    sequence-parallel stack: scores live one VMEM tile at a time, so
    per-device memory is O(L_loc·d) instead of the O(L_loc²) tile the
    previous hand-inlined fold materialized per ring step, and its
    fused FlashAttention-2 backward keeps the same bound in training.
    ``window``/``q_offset``: the banded-ring mask (q rows sit
    q_offset positions after the kv block's cols — STATIC, because the
    windowed ring unrolls its hops)."""
    return flash_attention(q, kb, vb, causal=causal, backend="auto",
                           return_lse=True, window=window,
                           q_offset=q_offset)


def _merge_block(o, lse, blk):
    """Merge a block's (out_b, lse_b) into the running normalized
    (o, lse): softmax over disjoint key sets combines by logaddexp
    weights — o stays NORMALIZED at every step (weights sum to 1), so
    no final division. All f32; shapes (B, Lq, H, D) / (B, Lq, H)."""
    ob, lseb = blk
    lse_new = jnp.logaddexp(lse, lseb)
    w_old = jnp.exp(lse - lse_new)[..., None]
    w_new = jnp.exp(lseb - lse_new)[..., None]
    return o * w_old + ob.astype(jnp.float32) * w_new, lse_new


def _causal_switch(src, my, o, lse, full_fn, diag_fn):
    """Three-way fold for an aligned causal block pair: src < my →
    every key precedes every query (full attention); src == my → the
    diagonal block (causal mask); src > my → wholly masked, SKIPPED
    (AD-transparent, ~half the attention FLOPs at large ring sizes —
    the pruning the old masked fold did with lax.cond)."""
    branch = (src >= my).astype(jnp.int32) + (src > my).astype(jnp.int32)
    return lax.switch(branch,
                      [lambda c: _merge_block(*c, full_fn()),
                       lambda c: _merge_block(*c, diag_fn()),
                       lambda c: c],
                      (o, lse))


def _ring_init(q):
    """(o, lse) accumulators derived from q (zeroed) rather than
    jnp.zeros so they inherit q's varying-axes type: fresh constants
    are replicated in shard_map's vma typing and would mismatch the
    scan carry — and deriving from q stays correct however many mesh
    axes the CALLER's shard_map adds around this body (e.g. dp × sp
    in the transformer)."""
    o = q.astype(jnp.float32) * 0.0                     # (B, Lq, H, D)
    lse = jnp.sum(o, axis=-1) + _NEG_INF                # (B, Lq, H)
    return o, lse


def _ring_shard(q, k, v, *, axis: str, n_shards: int, causal: bool,
                window: int = 0):
    """Per-device body (inside shard_map): local q stays put, (k, v)
    rotate the ring; after step i this device holds the KV shard of
    device (my - i) mod P. Every fold runs the fused flash kernel
    (_flash_block) and merges via logaddexp weights (_merge_block).

    ``window`` > 0 (causal only) runs the BANDED ring: the loop unrolls
    with a static hop index i, so each fold's q-vs-kv offset (i·L_loc)
    is a static kernel parameter, and the ring STOPS after
    ceil((window-1)/L_loc) hops — blocks further back are wholly behind
    the window for every device, so neither their compute NOR their
    ppermute traffic happens (the communication win sliding-window
    exists for)."""
    my = lax.axis_index(axis)
    l_loc = q.shape[1]
    o, lse = _ring_init(q)

    if causal and window:
        # hops with ANY visible pair: min(row-col) at hop i is
        # i·L_loc - (L_loc - 1) < window  ⇔  i ≤ (window+L_loc-2)/L_loc
        hops = min(n_shards - 1, (window + l_loc - 2) // l_loc)
        # hop 0 = the local block, unconditionally live on every device
        o, lse = _merge_block(o, lse, _flash_block(q, k, v, True,
                                                   window=window))
        kb, vb = k, v
        for i in range(1, hops + 1):
            perm = [(j, (j + 1) % n_shards) for j in range(n_shards)]
            with scope("lm.ring"):
                kb = lax.ppermute(kb, axis, perm)
                vb = lax.ppermute(vb, axis, perm)
            # wrapped sources (src > my, i.e. my < i) are above the
            # causal diagonal — skipped; the kernel's banded mask
            # handles everything else with the static offset i·L_loc
            def live(c, _i=i, _kb=kb, _vb=vb):
                return _merge_block(*c, _flash_block(
                    q, _kb, _vb, True, window=window,
                    q_offset=_i * l_loc))
            o, lse = lax.cond(my >= i, live, lambda c: c, (o, lse))
        return o.astype(q.dtype)

    def fold(o, lse, kb, vb, src):
        if causal:
            # contiguous shards are position-aligned: the (my, src)
            # block is full / diagonal-causal / skipped — never a
            # partial mask, so the kernel's static causal flag suffices
            return _causal_switch(
                src, my, o, lse,
                lambda: _flash_block(q, kb, vb, False),
                lambda: _flash_block(q, kb, vb, True))
        return _merge_block(o, lse, _flash_block(q, kb, vb, False))

    # step 0 folds the LOCAL block before any communication, so the ring
    # makes exactly n_shards - 1 sends — the final fold needs no rotate
    o, lse = fold(o, lse, k, v, my)

    def step(carry, i):
        o, lse, kb, vb = carry
        # ppermute j→j+1 receives from the anticlockwise neighbor: after
        # i rotations this device holds the KV of shard (my - i) mod P
        perm = [(j, (j + 1) % n_shards) for j in range(n_shards)]
        with scope("lm.ring"):
            kb = lax.ppermute(kb, axis, perm)
            vb = lax.ppermute(vb, axis, perm)
        o, lse = fold(o, lse, kb, vb, (my - i) % n_shards)
        return (o, lse, kb, vb), None

    # scan, not fori_loop: the trip count is static and scan supports
    # reverse-mode AD (training needs d(attention)/d(qkv) through the ring)
    (o, lse, _, _), _ = lax.scan(step, (o, lse, k, v),
                                 jnp.arange(1, n_shards))
    return o.astype(q.dtype)


def _zigzag_perm(seq_len: int, n_shards: int):
    """Global zigzag permutation: shard ``d`` holds stripe ``d`` AND
    stripe ``2P-1-d`` (one from each end of the sequence). Under a
    causal mask this balances the ring: with contiguous sharding the
    last shard attends to everything and the first to almost nothing,
    so every ring step's wall time is one FULL block fold on whichever
    device is busiest; zigzag makes every device's visible fraction
    ~equal at every step (~half a block), a ~2× causal wall-time win."""
    h = seq_len // (2 * n_shards)
    idx = []
    for d in range(n_shards):
        idx.extend(range(d * h, (d + 1) * h))
        idx.extend(range((2 * n_shards - 1 - d) * h,
                         (2 * n_shards - d) * h))
    return np.asarray(idx)


def _zigzag_check(seq_len: int, n_shards: int) -> None:
    """Shared validation for every zigzag entry point (standalone ring,
    transformer 2-D/3-D steps): the permutation needs 2 stripes/shard."""
    if seq_len % (2 * n_shards):
        raise ValueError(f"zigzag needs seq len divisible by 2×sp: "
                         f"{seq_len} vs {2 * n_shards}")


def to_zigzag(x, n_shards: int):
    """Standard → zigzag sequence layout on axis 1 of ``x`` (any array,
    numpy or jax; (B, L, ...)). Apply ONCE — e.g. host-side on a batch
    before device_put — and run zigzag entry points with
    ``layout="zigzag"`` so steady-state training/inference never pays a
    per-call cross-shard resharding (the permutation of an already
    P(dp, sp)-sharded array is an all-to-all)."""
    _zigzag_check(x.shape[1], n_shards)
    return x[:, _zigzag_perm(x.shape[1], n_shards)]


def from_zigzag(x, n_shards: int):
    """Inverse of :func:`to_zigzag` (zigzag → standard order)."""
    _zigzag_check(x.shape[1], n_shards)
    return x[:, _zigzag_perm(x.shape[1], n_shards).argsort()]


def _ring_shard_zigzag(q, k, v, *, axis: str, n_shards: int,
                       causal: bool):
    """Zigzag per-device body: local rows = [low stripe ‖ high stripe]
    (see _zigzag_perm). Each incoming KV block is folded per quadrant
    through the fused flash kernel: (q_low, k_high) is fully masked
    ALWAYS (low queries precede every high key — statically omitted);
    (q_high, k_low) is never masked; the two diagonal-ish quadrants
    are switch-skipped by shard index. Every quadrant is position-
    ALIGNED (stripe s of queries vs stripe s' of keys is full, causal-
    diagonal, or empty), so the kernel's static causal flag covers all
    cases. Per step each device folds exactly 2 of 4 quadrants (3 for
    the local block) — the balance the contiguous schedule lacks."""
    h = q.shape[1] // 2
    my = lax.axis_index(axis)
    q_lo, q_hi = q[:, :h], q[:, h:]

    o, lse = _ring_init(q)

    def fold(o, lse, kb, vb, src):
        if not causal:
            # quadrant splitting only buys anything under a causal
            # mask — full attention is one ordinary block fold
            return _merge_block(o, lse, _flash_block(q, kb, vb, False))

        k_lo, k_hi = kb[:, :h], kb[:, h:]
        v_lo, v_hi = vb[:, :h], vb[:, h:]
        o_lo, o_hi = o[:, :h], o[:, h:]
        lse_lo, lse_hi = lse[:, :h], lse[:, h:]

        # (q_low, k_low): stripe my vs stripe src of the LOW half —
        # diagonal band; full iff src < my, causal iff src == my
        o_lo, lse_lo = _causal_switch(
            src, my, o_lo, lse_lo,
            lambda: _flash_block(q_lo, k_lo, v_lo, False),
            lambda: _flash_block(q_lo, k_lo, v_lo, True))
        # (q_high, k_low): high queries see every low key — always
        o_hi, lse_hi = _merge_block(
            o_hi, lse_hi, _flash_block(q_hi, k_lo, v_lo, False))
        # (q_high, k_high): mirrored diagonal (stripe 2P-1-my vs
        # 2P-1-src): full iff src > my, causal iff src == my
        o_hi, lse_hi = _causal_switch(
            my, src, o_hi, lse_hi,
            lambda: _flash_block(q_hi, k_hi, v_hi, False),
            lambda: _flash_block(q_hi, k_hi, v_hi, True))
        # (q_low, k_high): low queries precede every high key —
        # fully masked for every (src, my) pair, statically omitted
        return (jnp.concatenate([o_lo, o_hi], axis=1),
                jnp.concatenate([lse_lo, lse_hi], axis=1))

    o, lse = fold(o, lse, k, v, my)

    def step(carry, i):
        o, lse, kb, vb = carry
        perm = [(j, (j + 1) % n_shards) for j in range(n_shards)]
        with scope("lm.ring"):
            kb = lax.ppermute(kb, axis, perm)
            vb = lax.ppermute(vb, axis, perm)
        o, lse = fold(o, lse, kb, vb, (my - i) % n_shards)
        return (o, lse, kb, vb), None

    (o, lse, _, _), _ = lax.scan(step, (o, lse, k, v),
                                 jnp.arange(1, n_shards))
    return o.astype(q.dtype)


@functools.lru_cache(maxsize=None)
def _ring_jit(mesh, axis: str, causal: bool, schedule: str = "contiguous",
              window: int = 0):
    """One compiled callable per (mesh, axis, causal, schedule, window)
    — jit caches key on the function object, so building shard_map+jit
    per call would retrace and recompile every invocation."""
    if schedule == "zigzag":
        body = functools.partial(_ring_shard_zigzag, axis=axis,
                                 n_shards=mesh.shape[axis],
                                 causal=causal)
    else:
        body = functools.partial(_ring_shard, axis=axis,
                                 n_shards=mesh.shape[axis],
                                 causal=causal, window=window)
    fn = shard_map(
        body,
        mesh=mesh, in_specs=(P(None, axis), P(None, axis), P(None, axis)),
        out_specs=P(None, axis))
    return jax.jit(fn)


def ring_attention(q, k, v, mesh, *, axis: str = "sp",
                   causal: bool = False, schedule: str = "contiguous",
                   layout: str = "seq", window: int = 0):
    """Exact attention over a sequence sharded on ``axis`` of ``mesh``.

    Inputs (B, L, H, D) are resharded to P(None, axis) if not already;
    L must divide evenly by the axis size. Output has the same sharding.

    ``schedule="zigzag"`` load-balances the CAUSAL ring (~2× wall time
    at large ring sizes, numerically identical): inputs are permuted so
    each shard holds one stripe from each end of the sequence, and the
    output is un-permuted before returning — callers see standard
    sequence order either way. L must then divide by 2×shards.

    ``layout="zigzag"`` (opt-in, zigzag schedule only) declares q/k/v
    ALREADY in zigzag order and returns the output in zigzag order too
    — no per-call permutation (which on sharded arrays is a cross-shard
    all-to-all that would dominate at the context lengths zigzag exists
    for). Convert once with :func:`to_zigzag` / :func:`from_zigzag` and
    keep long-lived tensors (training batches, decode prefill) in that
    layout across calls.
    """
    n_shards = mesh.shape[axis]
    if schedule not in ("contiguous", "zigzag"):
        raise ValueError(f"unknown ring schedule {schedule!r}")
    if layout not in ("seq", "zigzag"):
        raise ValueError(f"unknown layout {layout!r}")
    if window:
        if not causal:
            raise ValueError("windowed ring attention implies causal")
        if window < 1:
            raise ValueError(f"window must be >= 1, got {window}")
        if schedule == "zigzag":
            raise ValueError("the banded ring runs the contiguous "
                             "schedule (zigzag balances full-causal "
                             "work; a window already bounds per-device "
                             "work by construction)")
    if layout == "zigzag" and schedule != "zigzag":
        raise ValueError("layout='zigzag' requires schedule='zigzag'")
    permute = schedule == "zigzag" and layout == "seq"
    if schedule == "zigzag":
        _zigzag_check(q.shape[1], n_shards)
    elif q.shape[1] % n_shards:
        raise ValueError(
            f"seq len {q.shape[1]} not divisible by {axis}={n_shards}")
    if permute:
        perm = _zigzag_perm(q.shape[1], n_shards)
        inv = perm.argsort()
        q, k, v = (x[:, perm] for x in (q, k, v))
    sharding = NamedSharding(mesh, P(None, axis))
    q, k, v = (jax.device_put(x, sharding) for x in (q, k, v))
    out = _ring_jit(mesh, axis, causal, schedule, window)(q, k, v)
    if permute:
        out = out[:, inv]
    return out


def _ulysses_shard(q, k, v, *, axis: str, n_shards: int, causal: bool):
    """Per-device body: all_to_all seq-sharded → head-sharded, local full
    attention, all_to_all back."""
    def seq_to_heads(x):
        # (B, L/P, H, D) → (B, L, H/P, D): split heads, concat sequence
        return lax.all_to_all(x, axis, split_axis=2, concat_axis=1,
                              tiled=True)

    def heads_to_seq(x):
        return lax.all_to_all(x, axis, split_axis=1, concat_axis=2,
                              tiled=True)

    qh, kh, vh = seq_to_heads(q), seq_to_heads(k), seq_to_heads(v)
    # the device-local full-sequence attention is where the fused Pallas
    # kernel applies (backend="auto": flash kernel on TPU, the identical
    # XLA composition elsewhere)
    out = flash_attention(qh, kh, vh, causal=causal, backend="auto")
    return heads_to_seq(out)


@functools.lru_cache(maxsize=None)
def _ulysses_jit(mesh, axis: str, causal: bool):
    fn = shard_map(
        functools.partial(_ulysses_shard, axis=axis,
                          n_shards=mesh.shape[axis], causal=causal),
        mesh=mesh, in_specs=(P(None, axis), P(None, axis), P(None, axis)),
        out_specs=P(None, axis))
    return jax.jit(fn)


def ulysses_attention(q, k, v, mesh, *, axis: str = "sp",
                      causal: bool = False):
    """Exact attention via the all-to-all (Ulysses) reshard. Heads must
    divide evenly by the axis size (each device owns H/P full-sequence
    heads between the two collectives)."""
    n_shards = mesh.shape[axis]
    if q.shape[2] % n_shards:
        raise ValueError(
            f"{q.shape[2]} heads not divisible by {axis}={n_shards}")
    if k.shape[2] % n_shards:
        raise ValueError(
            f"{k.shape[2]} kv heads not divisible by {axis}={n_shards} "
            f"(GQA over ulysses reshards BOTH head sets)")
    if q.shape[1] % n_shards:
        raise ValueError(
            f"seq len {q.shape[1]} not divisible by {axis}={n_shards}")
    sharding = NamedSharding(mesh, P(None, axis))
    q, k, v = (jax.device_put(x, sharding) for x in (q, k, v))
    return _ulysses_jit(mesh, axis, causal)(q, k, v)
