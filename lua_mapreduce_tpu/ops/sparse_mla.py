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
# rows up to which `select_top_k` goes without a sort. Two exact ways stay
# because each wins on one side of a size the code can see, and the
# session cell has both sides: the 8 rows of a timed decode step (0.5 ms
# against the sort's 3.5) and the 64 rows of a set-up prefill block (6.1 ms
# against 3.4: over the cell's 20,480 blocks 55 s more of a 107 s prefill;
# my chip runs, PR 28)
_FEW_ROWS = 16


def _positions(chosen, k: int):
    """The positions of the ``k`` True of each row of ``chosen`` (..., S),
    rising, with one gather of k scalars a row and no sort. The row is
    cut into blocks of 128: inside a block the r-th True is found by
    comparing every lane's running count with r; a slot finds its block
    by comparing with the blocks' running totals. (A binary search a
    slot costs the chip 2 ms a row block, as much as the sort.)"""
    *lead, s = chosen.shape
    rows = chosen.reshape(-1, s)
    rows = jnp.pad(rows, ((0, 0), (0, -s % _LANES)))
    c = rows.reshape(rows.shape[0], -1, _LANES)                # (R, nb, 128)
    lane = jnp.arange(_LANES, dtype=jnp.int32)
    rank = jnp.cumsum(c, axis=-1, dtype=jnp.int32) - 1
    # table[R, b, r]: the lane of block b's r-th True
    table = jnp.sum(jnp.where(c[..., :, None]
                              & (rank[..., :, None] == lane),
                              lane[:, None], 0), axis=-2)
    counts = jnp.sum(c, axis=-1, dtype=jnp.int32)              # (R, nb)
    ends = jnp.cumsum(counts, axis=-1)
    slot = jnp.arange(k, dtype=jnp.int32)
    before = ends[:, None, :] <= slot[None, :, None]           # (R, k, nb)
    block = jnp.sum(before, axis=-1, dtype=jnp.int32)
    start = jnp.sum(jnp.where(before, counts[:, None, :], 0), axis=-1)
    block = jnp.minimum(block, c.shape[1] - 1)
    inside = jnp.take_along_axis(table.reshape(rows.shape[0], -1),
                                 block * _LANES + (slot - start), axis=-1)
    idx = jnp.minimum(block * _LANES + inside, s - 1)
    return idx.reshape(*lead, k).astype(jnp.int32)


def select_top_k(scores, top_k: int):
    """The ``top_k`` highest-scoring keys of each query (all of the
    cache where it is shorter): (idx (B, Q, K) int32, valid (B, Q, K)).
    An entry is invalid where the query sees fewer than K keys. Equal
    scores go to the lower position. For a few rows (a decode step)
    without a sort: the k-th score by :func:`kth_largest_key`, then the
    positions at or over it (:func:`_positions`), rising; for many (a
    block of a prefill) `lax.top_k`, whose sort is then the cheaper: 64
    rows of 32k take the chip 3.4 ms sorted and 6.1 ms this way, 8 rows
    3.5 ms and 0.5 ms (my chip run, PR 28). The same set either way."""
    k = min(top_k, scores.shape[-1])
    if scores[..., 0].size > _FEW_ROWS:
        vals, idx = lax.top_k(scores, k)
        return idx.astype(jnp.int32), vals > _NEG_INF
    keys = _sortable(scores)
    kth = kth_largest_key(keys, k)[..., None]
    above, tied = keys > kth, keys == kth
    room = k - jnp.sum(above, axis=-1, keepdims=True)
    chosen = above | (tied & (jnp.cumsum(tied, axis=-1) <= room))
    idx = _positions(chosen, k)
    picked = jnp.take_along_axis(scores, idx, axis=-1)
    return idx, picked > _NEG_INF


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
