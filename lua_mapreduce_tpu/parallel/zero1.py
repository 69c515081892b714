"""ZeRO-1: optimizer state sharded over the data-parallel axis.

The standard first rung of the FSDP ladder (the scaling-book recipe):
replicated parameters, but gradients REDUCE-SCATTER over dp instead of
all-reducing, each dp rank applies the optimizer to only its 1/n_dp
chunk of every parameter (holding only that chunk of the optimizer
state — Adam's m/v shrink by n_dp), and the updated chunks ALL-GATHER
back into full parameters. Same wire traffic as an all-reduce
(reduce_scatter + all_gather IS the ring all-reduce, split around the
update), optimizer memory ÷ n_dp.

Chunking is per-leaf: each parameter flattens to 1-D, zero-pads to a
multiple of n_dp, and splits evenly. The optimizer therefore sees
flat chunks — correct for every ELEMENTWISE optimizer (sgd, momentum,
adam, adamw, ...); optimizers that read parameter structure
(adafactor's factored second moment) need real FSDP, not ZeRO-1.

All helpers run INSIDE shard_map on the dp axis.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import optax
from jax import lax, shard_map
from jax.sharding import PartitionSpec as P


def _chunk_len(n: int, n_dp: int) -> int:
    return -(-n // n_dp)


def _pad_flat(x, n_dp: int):
    """(flat-padded array, chunk length) — THE chunk layout, shared by
    every helper so gradient and parameter chunks can never
    desynchronize."""
    flat = x.reshape(-1)
    c = _chunk_len(flat.size, n_dp)
    return jnp.pad(flat, (0, c * n_dp - flat.size)), c


def chunk_of_rank(x, axis: str, n_dp: int):
    """This rank's (chunk,) slice of a replicated array (flatten, pad
    to n_dp chunks, take chunk axis_index)."""
    flat, c = _pad_flat(x, n_dp)
    return lax.dynamic_slice_in_dim(flat, lax.axis_index(axis) * c, c)


def scatter_mean_grads(grads, axis: str, n_dp: int):
    """Per-leaf: psum_scatter the flattened grad over dp and divide —
    each rank receives its chunk of the dp-MEAN gradient. (The grads
    must already be identical along every OTHER mesh axis.)"""
    def one(g):
        flat, c = _pad_flat(g, n_dp)
        return lax.psum_scatter(flat.reshape(n_dp, c), axis,
                                scatter_dimension=0, tiled=False) / n_dp
    return jax.tree.map(one, grads)


def update_chunks(optimizer, params, grads, opt_state, axis: str,
                  n_dp: int):
    """The whole ZeRO-1 update dance, shared by every step body
    (transformer make_train_step and the DP trainer): reduce-scatter
    the grads, slice this rank's param chunks, run the optimizer on
    the chunks, gather updated params. Returns (params, opt_state)."""
    g_chunks = scatter_mean_grads(grads, axis, n_dp)
    p_chunks = jax.tree.map(
        lambda p: chunk_of_rank(p, axis, n_dp), params)
    updates, opt_state = optimizer.update(g_chunks, opt_state, p_chunks)
    p_chunks = optax.apply_updates(p_chunks, updates)
    return gather_params(p_chunks, params, axis), opt_state


def gather_params(chunks, templates, axis: str):
    """Inverse of :func:`chunk_of_rank` over a pytree: all_gather each
    leaf's chunks along dp, drop padding, restore the template shape."""
    def one(chunk, t):
        flat = lax.all_gather(chunk, axis, tiled=True)
        return flat[:t.size].reshape(t.shape).astype(t.dtype)
    return jax.tree.map(one, chunks, templates)


def state_specs(state, dp_axis: str):
    """PartitionSpec tree for a chunked optimizer state: array leaves
    (param-chunk moments) shard on dp; scalar leaves (step counts)
    replicate."""
    return jax.tree.map(
        lambda leaf: P(dp_axis) if getattr(leaf, "ndim", 0) >= 1 else P(),
        state)


def init_state(optimizer, params, mesh, *, dp_axis: str = "dp"):
    """Distributed optimizer state: each dp rank initializes on ITS
    param chunks, assembled into global arrays sharded over ``dp_axis``
    (one shard_map call; works for any optax optimizer whose init only
    reads leaf values/shapes)."""
    n_dp = mesh.shape[dp_axis]

    def shard_init(params):
        chunks = jax.tree.map(
            lambda p: chunk_of_rank(p, dp_axis, n_dp), params)
        return optimizer.init(chunks)

    # structure/specs derived from an abstract run of the same init
    tmpl = jax.eval_shape(
        lambda p: optimizer.init(jax.tree.map(
            lambda x: jnp.zeros((_chunk_len(x.size, n_dp),), x.dtype),
            p)), params)
    specs = state_specs(tmpl, dp_axis)

    # check_vma off: chunk slicing by axis_index is rank-varying in a
    # way the static checker rejects for the replicated scalar leaves
    fn = shard_map(shard_init, mesh=mesh, in_specs=(P(),),
                       out_specs=specs, check_vma=False)
    return jax.jit(fn)(params)
