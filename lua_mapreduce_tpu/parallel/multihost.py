"""Multi-host bootstrap: DCN-aware meshes and global-array helpers.

The reference scales past one machine by pointing every worker at the
same MongoDB (execute_BIG_server.sh:3 names a remote host; workers on any
box join the pool, SURVEY.md §2.6). The TPU-native equivalent is JAX
multi-process SPMD: every host runs THE SAME program, a coordinator
bootstraps the process group (``jax.distributed.initialize``), and the
mesh spans all hosts' devices — collectives ride ICI inside a pod slice
and DCN between slices.

Axis layout policy (the scaling-book recipe): put the *data-parallel*
axis on DCN (gradient all-reduce amortizes over the whole step and
overlaps with backward), keep tensor/sequence axes inside a slice so
their latency-sensitive collectives stay on ICI. That is exactly what
:func:`make_multihost_mesh` builds via ``create_hybrid_device_mesh``.

Single-process (tests, one box, one chip or one four-chip host) everything
degrades gracefully: ``initialize_multihost`` is a no-op, the mesh is the
ordinary single-slice mesh over the local devices.
"""

from __future__ import annotations

import os
from typing import Optional, Sequence, Tuple

import numpy as np


def initialize_multihost(coordinator_address: Optional[str] = None,
                         num_processes: Optional[int] = None,
                         process_id: Optional[int] = None) -> bool:
    """Join the multi-host process group; returns True when distributed.

    Arguments default from the standard env vars
    (``JAX_COORDINATOR_ADDRESS`` / ``JAX_NUM_PROCESSES`` /
    ``JAX_PROCESS_ID``; on GKE/TPU-VM deployments jax can also infer them
    from the metadata server). With no coordinator configured this is a
    no-op returning False — the single-box path used by every test.
    The call must happen BEFORE the first backend query.
    """
    # resolve env defaults FIRST so env-only configurations (e.g. a pod
    # launcher exporting JAX_NUM_PROCESSES and relying on metadata-server
    # coordinator inference) still initialize
    coordinator_address = coordinator_address or os.environ.get(
        "JAX_COORDINATOR_ADDRESS")
    if num_processes is None and os.environ.get("JAX_NUM_PROCESSES"):
        num_processes = int(os.environ["JAX_NUM_PROCESSES"])
    if process_id is None and os.environ.get("JAX_PROCESS_ID"):
        process_id = int(os.environ["JAX_PROCESS_ID"])
    if coordinator_address is None and num_processes is None:
        return False
    import jax

    kw = {}
    if coordinator_address is not None:
        kw["coordinator_address"] = coordinator_address
    if num_processes is not None:
        kw["num_processes"] = int(num_processes)
    if process_id is not None:
        kw["process_id"] = int(process_id)
    jax.distributed.initialize(**kw)
    return True


def make_multihost_mesh(mesh_shape: Sequence[int],
                        axis_names: Sequence[str],
                        dcn_axis: int = 0,
                        devices=None):
    """Mesh over every host's devices, DCN on exactly one axis.

    ``mesh_shape``/``axis_names`` describe the GLOBAL mesh. When the
    platform reports multiple slices (multi-host pods connected by DCN),
    the ``dcn_axis`` axis is factored as (num_slices × per-slice) via
    ``mesh_utils.create_hybrid_device_mesh``, so only that axis's
    collectives cross DCN; every other axis stays inside a slice on ICI.
    Single-slice pods (any process count — one slice is all-ICI) build
    an ordinary ``create_device_mesh``. Platforms with no slice notion
    (multi-controller CPU/GPU) treat the PROCESS boundary as the DCN
    granule instead — single-process degrades to the ordinary mesh, so
    the same program runs on one box and on a pod.
    """
    import jax
    from jax.experimental import mesh_utils
    from jax.sharding import Mesh

    if devices is None:
        devices = jax.devices()
    mesh_shape = list(mesh_shape)
    total = int(np.prod(mesh_shape))
    if total != len(devices):
        raise ValueError(
            f"mesh {tuple(mesh_shape)} needs {total} devices, have "
            f"{len(devices)}")

    # DCN granule = pod slice when the platform reports slices (TPU:
    # a single-slice multi-host pod is ALL ICI — hosts inside a slice
    # are ring-connected, so one slice must stay an ordinary mesh no
    # matter how many processes drive it). Only when the platform has
    # no slice notion at all (multi-controller CPU/GPU, the virtual
    # rig tests run on) does the process boundary stand in for DCN.
    slice_ids = {getattr(d, "slice_index", None) for d in devices}
    by_slice = None not in slice_ids
    granules = (slice_ids if by_slice
                else {d.process_index for d in devices})
    num_slices = len(granules)
    if num_slices > 1:
        if mesh_shape[dcn_axis] % num_slices:
            raise ValueError(
                f"dcn axis {axis_names[dcn_axis]}={mesh_shape[dcn_axis]} "
                f"not divisible by {num_slices} slices")
        dcn_shape = [1] * len(mesh_shape)
        dcn_shape[dcn_axis] = num_slices
        per_slice = list(mesh_shape)
        per_slice[dcn_axis] //= num_slices
        arr = mesh_utils.create_hybrid_device_mesh(
            per_slice, dcn_shape, devices=devices,
            process_is_granule=not by_slice)
    else:
        arr = mesh_utils.create_device_mesh(mesh_shape, devices=devices)
    return Mesh(arr, tuple(axis_names))


def process_local_batch(global_batch: int) -> Tuple[int, int]:
    """(this process's batch rows, row offset) for an even split of a
    global batch over processes — each host feeds only its own rows (the
    ShardedDataset shard-ownership contract, train/sharding.py)."""
    import jax

    n, i = jax.process_count(), jax.process_index()
    if global_batch % n:
        raise ValueError(f"global batch {global_batch} not divisible by "
                         f"{n} processes")
    per = global_batch // n
    return per, i * per


def global_batch_array(mesh, spec, host_local: np.ndarray):
    """Assemble a GLOBAL jax.Array from each host's local rows.

    Single-process: an ordinary ``device_put`` with the sharding (the
    virtual-mesh test path). Multi-process: each host contributes only
    its local block via ``make_array_from_process_local_data`` — no host
    ever materializes the global batch (the reference's equivalent is
    each mapper reading only its own split, WordCountBig/taskfn.lua:5-13).
    """
    import jax
    from jax.sharding import NamedSharding

    sharding = NamedSharding(mesh, spec)
    if jax.process_count() == 1:
        return jax.device_put(host_local, sharding)
    return jax.make_array_from_process_local_data(sharding, host_local)
