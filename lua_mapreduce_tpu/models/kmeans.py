"""k-means — the iterative-state workload, TPU-native (BASELINE.json
config 5: "Iterative k-means / ALS … persistent_table.lua state across
MapReduce iters on TPU").

The reference expresses iterative algorithms as looping MapReduce with
cross-iteration state in a persistent_table (SURVEY.md §3.5, §5). Lloyd's
algorithm has exactly that shape — map = assign each point shard to its
nearest centroid and fold per-cluster partial sums, reduce = sum partials
across shards, final = recompute centroids and loop. Here the whole loop
is ONE jitted SPMD program: points stay sharded over the ``dp`` axis for
the entire fit, the assign step is a distance matmul on the MXU, the
reduce is a ``psum`` over ICI, and iterations run inside ``lax.scan`` with
zero host round-trips (the hot-path rule of BASELINE.md). The
six-function-engine packaging of the same algorithm lives in
examples/kmeans/ — both paths must agree (golden-diff discipline,
SURVEY.md §4).
"""

from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax, shard_map
from jax.sharding import NamedSharding, PartitionSpec as P


class KMeansResult(NamedTuple):
    centroids: jnp.ndarray      # (k, d)
    inertia: jnp.ndarray        # scalar: sum of squared distances
    history: jnp.ndarray        # (n_iters,) inertia per iteration


def _assign_fold(x, centroids):
    """Per-shard map+combine: nearest-centroid one-hot fold.

    Distances via the expanded form — the x·cᵀ term is one (shard, k)
    matmul on the MXU; ‖x‖² is constant in the argmin and omitted.
    Returns (per-cluster sums (k, d), counts (k,), inertia scalar).
    """
    xc = x @ centroids.T                                    # (n, k) MXU
    d2 = jnp.sum(centroids ** 2, axis=1)[None, :] - 2.0 * xc
    nearest = jnp.argmin(d2, axis=1)                        # (n,)
    one_hot = jax.nn.one_hot(nearest, centroids.shape[0],
                             dtype=x.dtype)                 # (n, k)
    sums = one_hot.T @ x                                    # (k, d) MXU
    counts = jnp.sum(one_hot, axis=0)                       # (k,)
    inertia = (jnp.sum(x ** 2)
               + jnp.sum(one_hot * d2))    # Σ‖x‖² + Σ(‖c‖² − 2x·c)
    return sums, counts, inertia


def _update(centroids, sums, counts):
    """New centroid = cluster mean; empty clusters keep their centroid
    (the reference engine's empty-partition tolerance, SURVEY.md §6)."""
    safe = jnp.maximum(counts, 1.0)[:, None]
    return jnp.where(counts[:, None] > 0, sums / safe, centroids)


def init_centroids(key, x: np.ndarray, k: int,
                   method: str = "kmeans++") -> jnp.ndarray:
    """Seed centroids, deterministic in ``key``. ``"kmeans++"`` (default)
    does D²-weighted sampling — sequential over k, so it runs host-side
    (seeding is a once-per-fit cost, not the hot loop); ``"random"``
    picks k distinct points uniformly."""
    x = np.asarray(x)
    rng = np.random.RandomState(
        int(jax.random.randint(key, (), 0, 2 ** 31 - 1)))
    if method == "random":
        return jnp.asarray(x[rng.choice(len(x), k, replace=False)])
    if method != "kmeans++":
        raise ValueError(f"unknown init method {method!r}")
    # greedy k-means++: each step samples a few D²-weighted candidates
    # and keeps the one that lowers the potential most (the standard
    # robustification against seeding two centroids into one cluster)
    n_trials = 2 + int(np.log(max(k, 2)))
    chosen = x[rng.randint(len(x))][None, :]
    d2 = np.sum((x - chosen[0]) ** 2, axis=-1)
    for _ in range(k - 1):
        cand = rng.choice(len(x), size=n_trials, p=d2 / d2.sum())
        cand_d2 = np.minimum(
            d2[None, :],
            np.sum((x[None, :, :] - x[cand][:, None, :]) ** 2, axis=-1))
        best = int(np.argmin(cand_d2.sum(axis=1)))
        chosen = np.concatenate([chosen, x[cand[best]][None, :]])
        d2 = cand_d2[best]
    return jnp.asarray(chosen)


def kmeans_fit(x, centroids0, *, n_iters: int = 20,
               mesh: Optional[object] = None, axis: str = "dp"
               ) -> KMeansResult:
    """Run ``n_iters`` Lloyd iterations from ``centroids0``.

    With a ``mesh``, ``x`` is sharded on its leading axis over ``axis``
    and the fold is psum'd over ICI; without one it is a single-device
    jit. The iteration count is static (lax.scan) so the whole fit is one
    compiled program. ``history[i]`` is the inertia of the assignment
    computed against the iteration-i centroids — history[-1] lags the
    returned final centroids by one update, matching the classic
    assign-then-update bookkeeping.
    """
    x = jnp.asarray(x)
    centroids0 = jnp.asarray(centroids0)

    def fit(x_in, c0):
        def one_iter(centroids, _):
            sums, counts, inertia = _assign_fold(x_in, centroids)
            if mesh is not None:
                sums = lax.psum(sums, axis)
                counts = lax.psum(counts, axis)
                inertia = lax.psum(inertia, axis)
            return _update(centroids, sums, counts), inertia

        c, hist = lax.scan(one_iter, c0, None, length=n_iters)
        return KMeansResult(c, hist[-1], hist)

    if mesh is None:
        return jax.jit(fit)(x, centroids0)
    shard = shard_map(
        fit, mesh=mesh, in_specs=(P(axis), P()),
        out_specs=KMeansResult(P(), P(), P()))
    x = jax.device_put(x, NamedSharding(mesh, P(axis)))
    centroids0 = jax.device_put(centroids0, NamedSharding(mesh, P()))
    return jax.jit(shard)(x, centroids0)


def assign(x, centroids) -> jnp.ndarray:
    """Nearest-centroid labels for ``x`` (single device)."""
    xc = jnp.asarray(x) @ jnp.asarray(centroids).T
    d2 = jnp.sum(jnp.asarray(centroids) ** 2, axis=1)[None, :] - 2.0 * xc
    return jnp.argmin(d2, axis=1)
