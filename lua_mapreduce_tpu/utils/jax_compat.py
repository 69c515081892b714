"""Helpers for ``jax.shard_map``'s varying-mesh-axes (vma) checker,
which jax 0.9.0 (the one installation there is) keeps on by default."""

from __future__ import annotations

import jax


def spec_axes(spec) -> set:
    """Mesh-axis names a ``PartitionSpec`` shards over (flattening
    tuple entries); empty for ``P()`` — the replicated spec."""
    out = set()
    for entry in tuple(spec):
        if entry is None:
            continue
        if isinstance(entry, (tuple, list)):
            out.update(entry)
        else:
            out.add(entry)
    return out


def stamp_replicated(tree, axes):
    """Make mathematically-replicated shard_map outputs *statically*
    replicated for the vma checker.

    JAX rejects ``out_specs=P()`` for gradients of replicated params at
    trace time: the transpose machinery auto-psums the replicated-input
    cotangents (the values ARE identical across ``axes``), but the
    static checker cannot infer that through ``value_and_grad``.
    ``lax.pmean`` over each axis is a numerical identity on an
    already-replicated value and carries the replication fact the
    checker needs — so the check stays ON (the loud failure mode the
    call sites prefer) instead of being disabled with
    ``check_vma=False``.
    """
    from jax import lax
    axes = tuple(a for a in axes if a)
    if not axes:
        return tree

    def stamp(x):
        for a in axes:
            x = lax.pmean(x, a)
        return x

    return jax.tree.map(stamp, tree)
