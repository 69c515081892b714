"""ALS matrix factorization — the second iterative-state workload
(BASELINE.json config 5).

Alternating least squares on an observed ratings matrix R ≈ U Vᵀ with an
observation mask. Expressed in the reference's looping-MapReduce shape
(SURVEY.md §3.5): each iteration the "map" solves user factors on a shard
of users given replicated item factors V (embarrassingly parallel — the
map phase), then folds that shard's contribution to every item's normal
equations; the "reduce" sums those (k×k, k) partials across shards — on
TPU a ``psum`` over ICI; the "final" solves all item systems and loops.
The whole fit is one jitted SPMD program with users sharded over ``dp``
for its entire lifetime: the per-row solves are batched ``vmap``s over
MXU-shaped normal equations, iterations ride ``lax.scan``, and the only
cross-device traffic is the psum. The six-function-engine packaging of
the same algorithm lives in examples/als/.
"""

from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import NamedSharding, PartitionSpec as P


class ALSResult(NamedTuple):
    user_factors: jnp.ndarray   # (n_users, k)
    item_factors: jnp.ndarray   # (n_items, k)
    rmse: jnp.ndarray           # scalar: final masked train RMSE
    history: jnp.ndarray        # (n_iters,) RMSE per iteration


def init_item_factors(key, n_items: int, rank: int,
                      scale: float = 0.1) -> jnp.ndarray:
    return scale * jax.random.normal(key, (n_items, rank))


def _solve_users(r, w, v, reg):
    """Per-user ridge solve, batched: for each user u,
    (Vᵀ W_u V + λI) x = Vᵀ W_u r_u. r/w are this shard's (n_u, n_items)."""
    k = v.shape[1]
    eye = reg * jnp.eye(k, dtype=v.dtype)

    def solve_one(r_u, w_u):
        vw = v * w_u[:, None]               # (n_items, k)
        a = vw.T @ v + eye                  # (k, k) MXU
        b = vw.T @ r_u                      # (k,)
        return jnp.linalg.solve(a, b)

    return jax.vmap(solve_one)(r, w)        # (n_u, k)


def _item_partials(r, w, u):
    """This shard's contribution to every item's normal equations:
    A_i += Σ_u w_ui u_u u_uᵀ, b_i += Σ_u w_ui r_ui u_u — the quantity the
    reduce phase sums (it is associative+commutative, the combiner
    contract of SURVEY.md §2.5)."""
    a = jnp.einsum("ui,uk,ul->ikl", w, u, u)        # (n_items, k, k)
    b = jnp.einsum("ui,ui,uk->ik", w, r, u)         # (n_items, k)
    return a, b


def als_fit(ratings, mask, item_factors0, *, n_iters: int = 10,
            reg: float = 0.1, mesh: Optional[object] = None,
            axis: str = "dp") -> ALSResult:
    """Run ``n_iters`` ALS rounds from item factors ``item_factors0``.

    With a ``mesh``, ratings/mask are sharded row-wise (users) over
    ``axis``; item factors stay replicated and the item-step normal
    equations are psum'd. ``history[i]`` is the masked RMSE measured with
    the factors produced by round i.
    """
    ratings = jnp.asarray(ratings, jnp.float32)
    mask = jnp.asarray(mask, jnp.float32)
    item_factors0 = jnp.asarray(item_factors0, jnp.float32)
    k = item_factors0.shape[1]

    def fit(r, w, v0):
        eye = reg * jnp.eye(k, dtype=v0.dtype)

        def one_iter(v, _):
            u = _solve_users(r, w, v, reg)              # map: user shard
            a, b = _item_partials(r, w, u)              # combine
            if mesh is not None:
                a = lax.psum(a, axis)                   # reduce over ICI
                b = lax.psum(b, axis)
            v_new = jax.vmap(
                lambda ai, bi: jnp.linalg.solve(ai + eye, bi))(a, b)
            err = w * (u @ v_new.T - r)
            sq, cnt = jnp.sum(err ** 2), jnp.sum(w)
            if mesh is not None:
                sq = lax.psum(sq, axis)
                cnt = lax.psum(cnt, axis)
            rmse = jnp.sqrt(sq / jnp.maximum(cnt, 1.0))
            return v_new, rmse

        v, hist = lax.scan(one_iter, v0, None, length=n_iters)
        u = _solve_users(r, w, v, reg)
        return u, v, hist

    if mesh is None:
        u, v, hist = jax.jit(fit)(ratings, mask, item_factors0)
        return ALSResult(u, v, hist[-1], hist)

    shard = shard_map(
        fit, mesh=mesh, in_specs=(P(axis), P(axis), P()),
        out_specs=(P(axis), P(), P()))
    ratings = jax.device_put(ratings, NamedSharding(mesh, P(axis)))
    mask = jax.device_put(mask, NamedSharding(mesh, P(axis)))
    item_factors0 = jax.device_put(item_factors0,
                                   NamedSharding(mesh, P()))
    u, v, hist = jax.jit(shard)(ratings, mask, item_factors0)
    return ALSResult(u, v, hist[-1], hist)
