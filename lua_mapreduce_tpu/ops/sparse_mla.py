"""Sparse latent attention: the lightning indexer's score-and-top-k, and
absorbed multi-head latent attention over the cache rows it selects.

A query reads at most ``top_k`` cached rows. The indexer scores every
cached position with a few small heads,

    I[t, s] = sum_j w[t, j] * relu(q_i[t, j] . k_i[s]),   s <= t,

and the ``top_k`` highest are gathered from the latent cache; while
fewer positions than that exist, all of them are. Attention then runs
in the absorbed form: the query is already multiplied into the
latent's basis, so a cached row ``[c_kv | k_rope]`` is key and value
at once and no per-head key or value is ever built.

One composition serves prefill (a block of queries) and decode (one
query). The (queries, keys, heads) indexer scores exist for one block
of queries at a time: the caller bounds the block. XLA builds
everything here; ties in the selection go to the lower position (as
`lax.top_k` breaks them).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

_NEG_INF = -jnp.inf


def index_scores(q_i, w, k_i, pos):
    """Indexer scores of a block of queries against every cached key.

    q_i (B, Q, J, D) indexer queries; w (B, Q, J) head weights, already
    scaled; k_i (B, S, D) cached indexer keys; pos (Q,) the queries'
    positions. Returns float32 (B, Q, S), minus infinity where the key
    lies after the query."""
    s = jnp.einsum("bqjd,bsd->bqjs", q_i, k_i,
                   preferred_element_type=jnp.float32)
    s = jnp.einsum("bqjs,bqj->bqs", jax.nn.relu(s), w.astype(jnp.float32))
    seen = jnp.arange(k_i.shape[1])[None, None, :] <= pos[None, :, None]
    return jnp.where(seen, s, _NEG_INF)


def _sortable(x):
    """float32 -> uint32 that orders as the floats do (the two zeros
    as one)."""
    x = x.astype(jnp.float32)
    u = lax.bitcast_convert_type(jnp.where(x == 0, 0.0, x), jnp.uint32)
    return jnp.where(u >> 31 == 1, ~u, u | jnp.uint32(1 << 31))


def kth_largest_key(keys, k: int):
    """The k-th largest of each row of ``keys`` (..., S) uint32, exactly,
    without sorting: the 32 bits are settled four at a time, each pass
    counting the row against the 15 candidates of the next four bits.
    On the chip a sort of 32k scores costs 2.5 ms a row block where
    these 8 passes cost a tenth (my chip run, PR 28)."""
    steps = jnp.arange(1, 16, dtype=jnp.uint32)

    def settle(i, lo):
        shift = (28 - 4 * i).astype(jnp.uint32)
        candidates = lo[..., None] + (steps << shift)          # (..., 15)
        # a candidate wraps where the high bits are full already: never
        enough = (jnp.sum(keys[..., None, :] >= candidates[..., None],
                          axis=-1) >= k) & (candidates > lo[..., None])
        return lo + (jnp.sum(enough, axis=-1).astype(jnp.uint32) << shift)

    return lax.fori_loop(0, 8, settle,
                         jnp.zeros(keys.shape[:-1], jnp.uint32))


_LANES = 128
# rows up to which `select_top_k` goes without a sort: both sizes the
# session cell has, the 8 rows of a timed decode step and the 64 rows of
# a set-up prefill block. The limit is memory, not speed: a row costs
# this way about 4 MB of temporaries (2 GB at 512 rows), and the sort
# won at no size measured (us a call of 32,832 scores a row, top 2048,
# this way / `lax.top_k`: 8 rows 48 / 2,453, 64 rows 571 / 2,392, 128
# rows 1,610 / 4,785, 512 rows 6,826 / 20,330; my chip runs, PR 33,
# `benchmarks/selection_bench.py`)
_FEW_ROWS = 64


def _ones_below(n: int):
    """(n, n) bfloat16, one where the row's number is at most the
    column's: a product with it is an inclusive running sum."""
    i = jnp.arange(n)
    return (i[:, None] <= i[None, :]).astype(jnp.bfloat16)


def _running_counts(flags):
    """Of each row of ``flags`` (..., S) bool, cut into blocks of 128
    lanes (False behind the row's end): the running count of True inside
    each block, inclusive (R, nb, 128), and the blocks' running totals,
    inclusive (R, nb); float32, whole numbers. Both are products with a
    triangle of ones on the matrix unit, and exact: every factor is a
    whole number of at most 128 and every sum stays under 2**24 (a
    longer row is refused). (The chip's own running sum over 128 lanes
    is a window reduction: 89 us for (8, 257, 128) where this product
    takes under one; my chip runs, PR 33.)"""
    if flags.shape[-1] >= 2 ** 24:
        raise ValueError(f"a row of {flags.shape[-1]} positions: the "
                         f"running counts are exact under 2**24")
    rows = flags.reshape(-1, flags.shape[-1])
    rows = jnp.pad(rows, ((0, 0), (0, -rows.shape[-1] % _LANES)))
    c = rows.reshape(rows.shape[0], -1, _LANES)                # (R, nb, 128)
    inside = jnp.einsum("rbj,jl->rbl", c.astype(jnp.bfloat16),
                        _ones_below(_LANES),
                        preferred_element_type=jnp.float32)
    ends = jnp.einsum("rb,bc->rc", inside[..., -1].astype(jnp.bfloat16),
                      _ones_below(c.shape[1]),
                      preferred_element_type=jnp.float32)
    return inside, ends


def _running_count(flags):
    """The inclusive running count of True along each row of ``flags``
    (..., S), float32: inside a block of 128 plus the blocks before."""
    inside, ends = _running_counts(flags)
    before = ends - inside[..., -1]
    return (inside + before[..., None]).reshape(ends.shape[0], -1)[
        :, :flags.shape[-1]].reshape(flags.shape)


def _positions(chosen, k: int):
    """The positions of the True of each row of ``chosen`` (..., S),
    rising, in ``k`` slots; no sort and no gather. The row is cut into
    blocks of 128 lanes. A slot finds its block by comparing with the
    blocks' running totals, and fetches that block's 128 running counts
    (and, as three bytes, the count before the block: bfloat16 holds a
    byte whole, and a count is under 2**24) by a one-hot product on the
    matrix unit: exact, one term of each sum is not zero and none is
    over 255. The lane of the block's r-th True is the number of its
    running counts at or under r. Slots past the row's last True hold
    some position of the row."""
    *lead, s = chosen.shape
    inside, ends = _running_counts(chosen)
    starts = ends - inside[..., -1]                            # (R, nb)
    table = jnp.concatenate(
        [inside, jnp.stack([starts // 65536, starts // 256 % 256,
                            starts % 256], axis=-1)],
        axis=-1).astype(jnp.bfloat16)
    slot = jnp.arange(k, dtype=jnp.float32)[None, :, None]
    before = ends[:, None, :] <= slot                          # (R, k, nb)
    block = jnp.sum(before, axis=-1, dtype=jnp.int32)
    here = (starts[:, None, :] <= slot) & ~before              # one-hot
    mine = jnp.einsum("rkb,rbl->rkl", here.astype(jnp.bfloat16), table,
                      preferred_element_type=jnp.float32)
    high, mid, low = (mine[..., _LANES + i, None] for i in range(3))
    r = slot - (65536 * high + 256 * mid + low)
    lane = jnp.sum(mine[..., :_LANES] <= r, axis=-1, dtype=jnp.int32)
    idx = jnp.minimum(block * _LANES + lane, s - 1)
    return idx.reshape(*lead, k)


def select_top_k(scores, top_k: int):
    """The ``top_k`` highest-scoring keys of each query (all of the
    cache where it is shorter): (idx (B, Q, K) int32, valid (B, Q, K)).
    An entry is invalid where the query sees fewer than K keys (a score
    of minus infinity is a key not seen, wherever it lies). Equal
    scores go to the lower position. For a few rows (a decode step)
    without a sort and without a gather: the k-th score by
    :func:`kth_largest_key`, then the positions at or over it
    (:func:`_positions`), rising, the first ``n`` of them valid where
    ``n`` scores are finite: 8 rows of 32,832 take the chip 48 us (with
    a table of lanes and two gathers of a scalar a slot 540, sorted
    2,453), 64 rows 571 us (5,099; 2,392) (my chip runs, PR 33). For
    more rows `lax.top_k`. The same set either way."""
    k = min(top_k, scores.shape[-1])
    if scores[..., 0].size > _FEW_ROWS:
        vals, idx = lax.top_k(scores, k)
        return idx.astype(jnp.int32), vals > _NEG_INF
    keys = _sortable(scores)
    kth = kth_largest_key(keys, k)[..., None]
    above, tied = keys > kth, keys == kth
    room = k - jnp.sum(above, axis=-1, keepdims=True)
    chosen = ((above | (tied & (_running_count(tied) <= room)))
              & (scores > _NEG_INF))
    n = jnp.sum(chosen, axis=-1, keepdims=True)
    return _positions(chosen, k), jnp.arange(k) < n


def sparse_latent_attention(q, cache, idx, valid, *, scale: float,
                            v_rank: int):
    """Absorbed attention of each query over its selected cache rows.

    q (B, Q, H, R) queries in the cache's basis (``[q_nope W_k | q_rope]``);
    cache (B, S, R) rows ``[c_kv | k_rope]``; idx, valid (B, Q, K) from
    :func:`select_top_k`. Softmax in float32 over the valid entries.
    Returns (B, Q, H, v_rank): the weighted sum of the rows' first
    ``v_rank`` values (the latent), for the caller to take out of the
    latent's basis."""
    rows = jax.vmap(lambda c, i: c[i])(cache, idx)        # (B, Q, K, R)
    s = jnp.einsum("bqhr,bqkr->bqhk", q, rows,
                   preferred_element_type=jnp.float32) * scale
    s = jnp.where(valid[:, :, None, :], s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bqhk,bqkc->bqhc", p.astype(rows.dtype),
                      rows[..., :v_rank],
                      preferred_element_type=jnp.float32)
