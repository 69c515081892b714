"""Device-mesh construction.

The mesh is the TPU build's "worker pool": where the reference's elastic
workers claim jobs one at a time (task.lua:258-343), devices in a mesh each
own a static shard of the computation and exchange data over ICI. Axis
conventions used throughout this framework:

- ``dp``  — data parallel (batch / map-shard axis; the map-phase analog)
- ``mp``  — model parallel (tensor-sharded parameters)

Helper policy: prefer all devices on one axis (pure DP) unless an ``mp``
degree is requested; axes sized 1 are kept so downstream shardings can
always name both axes.
"""

from __future__ import annotations

from typing import Optional, Sequence, Tuple

import numpy as np


def make_mesh(dp: Optional[int] = None, mp: int = 1, devices=None,
              axis_names: Tuple[str, str] = ("dp", "mp")):
    """Build a 2-D ``jax.sharding.Mesh`` of shape (dp, mp).

    ``dp`` defaults to ``len(devices) // mp``. Raises if the device count
    is not divisible.
    """
    import jax
    from jax.sharding import Mesh

    if devices is None:
        devices = jax.devices()
    n = len(devices)
    if dp is None:
        if n % mp:
            raise ValueError(f"{n} devices not divisible by mp={mp}")
        dp = n // mp
    if dp * mp != n:
        raise ValueError(f"mesh {dp}x{mp} != {n} devices")
    arr = np.array(devices).reshape(dp, mp)
    return Mesh(arr, axis_names)


def host_mesh(n: int = 8, dp: Optional[int] = None, mp: int = 1):
    """Mesh over virtual CPU devices — the single-box stand-in for a pod
    slice (the .travis.yml "multi-node on one machine" analog, SURVEY.md
    §4). Requires ``--xla_force_host_platform_device_count=<n>`` (set by
    tests/conftest.py)."""
    import jax

    cpus = jax.devices("cpu")
    if len(cpus) < n:
        raise RuntimeError(
            f"need {n} CPU devices, have {len(cpus)} — set XLA_FLAGS="
            f"--xla_force_host_platform_device_count={n}")
    return make_mesh(dp=dp, mp=mp, devices=cpus[:n])


def mesh_axis_size(mesh, name: str) -> int:
    return mesh.shape[name]


def rows_spec(mesh, shape):
    """Where the default train step keeps a leaf of its weights and of
    its optimizer state, and the gradient that updates it: rows split
    over every device of the mesh (``P(axis_names)`` on dim 0) where
    their count divides evenly, else the leaf whole on every device (a
    scalar, a one-device mesh, a leaf of ragged rows)."""
    from jax.sharding import PartitionSpec as P

    n = mesh.size
    if n > 1 and len(shape) and shape[0] % n == 0:
        return P(tuple(mesh.axis_names))
    return P()


def rows_layout(mesh, tree):
    """:func:`rows_spec` of every leaf of ``tree`` (arrays, or anything
    with a ``shape``) as a ``NamedSharding`` on ``mesh``."""
    import jax
    from jax.sharding import NamedSharding

    return jax.tree.map(
        lambda x: NamedSharding(mesh, rows_spec(mesh, x.shape)), tree)


def opt_state_layout(opt_state) -> Tuple[int, int, int, int]:
    """(bytes a device holds, bytes in all, leaves split, leaves whole) of
    an optimizer state on the devices (arrays, or shapes with a
    sharding): what :func:`rows_layout` saves. A leaf is an array of one
    dimension or more; scalars (step counts) count in the bytes alone."""
    import math

    import jax

    held = total = split = whole = 0
    for x in jax.tree.leaves(opt_state):
        shard = tuple(x.sharding.shard_shape(x.shape))
        held += math.prod(shard) * x.dtype.itemsize
        total += math.prod(x.shape) * x.dtype.itemsize
        if x.ndim:
            split += shard != tuple(x.shape)
            whole += shard == tuple(x.shape)
    return held, total, split, whole
