"""The SPMD MapReduce executor.

Compiles an :class:`ArrayTaskSpec` to a single jitted program over a mesh
(SURVEY.md §7 step 5). Two shuffle shapes cover the reference's reduce
topologies (SURVEY.md §2.5-2.6):

- **keyed** (:meth:`TpuExecutor.run_keyed`): mapfn's output pytree keys are
  the key space; reduction is an associative collective across the ``dp``
  axis (psum & friends). This is the APRIL-ANN DP-SGD shape: map = shard
  gradient, combine = local batch fold, reduce = all-reduce over ICI.

- **bucketed** (:meth:`TpuExecutor.run_bucketed`): the user partitionfn
  buckets each shard's output into a leading axis of NUM_PARTITIONS;
  ``all_to_all`` redistributes buckets so device p holds every mapper's
  bucket p; a local fold finishes the reduce. This is the general
  partitionfn → per-partition reduce-job shape with the shuffle riding ICI
  instead of intermediate storage files.

Everything under jit is traced once: no data-dependent Python control flow,
static shapes, XLA-fused combiners (the MAX_MAP_RESULT streaming threshold
of the host path, job.lua:92-96, has no device analog — on TPU the combine
is a register/VMEM-level fusion, which is the whole point).

This module is the EXPLICIT array-native surface: users hand it an
:class:`ArrayTaskSpec` already written as a traceable array program.
Since the fusion of the repo's two halves (DESIGN §26), ordinary
six-function tasks (engine/contract.TaskSpec) whose data plane the
static oracle verdicts ``in-graph`` reach this plane AUTOMATICALLY:
engine/ingraph.py lowers them to the same shard_map-over-mesh shapes,
reusing this module's ``_CROSS`` collective table and parallel/mesh.py
rather than reimplementing them — TpuExecutor stays the right tool
when you want to write the array program yourself.
"""

from __future__ import annotations

import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

from lua_mapreduce_tpu.parallel.array_task import ArrayTaskSpec

_CROSS = {
    "sum": lax.psum,
    "mean": lax.pmean,
    "max": lax.pmax,
    "min": lax.pmin,
}

_LOCAL = {
    "sum": jnp.sum,
    "mean": jnp.mean,
    "max": jnp.max,
    "min": jnp.min,
}


class TpuExecutor:
    """Execute a traceable MapReduce over a mesh.

    ``axis`` names the mesh axis that plays the map-shard role (default
    ``dp``). Compiled programs are cached per (mode, scatter) — repeated
    runs (the "loop" protocol) pay zero retrace.
    """

    def __init__(self, spec: ArrayTaskSpec, mesh, axis: str = "dp"):
        self.spec = spec
        self.mesh = mesh
        self.axis = axis
        self.n_shards = mesh.shape[axis]

    # -- input placement ----------------------------------------------------

    def shard_inputs(self, batch):
        """Place a global batch with the leading axis sharded over the map
        axis — the taskfn role: each device's slice is its map job."""
        sharding = NamedSharding(self.mesh, P(self.axis))
        return jax.tree.map(
            lambda x: jax.device_put(x, sharding), batch)

    # -- keyed reduction (psum shape) ---------------------------------------

    @functools.cached_property
    def _keyed_fn(self):
        spec, axis = self.spec, self.axis
        cross = _CROSS[spec.reduce_op]

        def per_shard(batch):
            out = spec.mapfn(batch)
            if spec.combinerfn is not None:
                out = spec.combinerfn(out)
            return jax.tree.map(lambda x: cross(x, axis), out)

        shard_spec = P(self.axis)
        mapped = shard_map(
            per_shard, mesh=self.mesh,
            in_specs=(shard_spec,), out_specs=P())
        return jax.jit(mapped)

    def run_keyed(self, batch) -> Any:
        """map → combine → all-reduce. Returns the replicated reduced
        pytree (every device holds the full result, like every reference
        worker seeing the final reduce output in GridFS)."""
        result = self._keyed_fn(self.shard_inputs(batch))
        if self.spec.finalfn is not None:
            return self.spec.finalfn(result)
        return result

    # -- bucketed shuffle (all_to_all shape) --------------------------------

    @functools.cached_property
    def _bucketed_fn(self):
        spec, axis, n = self.spec, self.axis, self.n_shards
        if spec.partitionfn is None:
            raise ValueError("bucketed mode needs spec.partitionfn")
        num_p = spec.num_partitions
        if num_p % n:
            raise ValueError(
                f"num_partitions={num_p} must be a multiple of the mesh "
                f"axis size {n} (pad partitions; empty ones are cheap)")
        per_dev = num_p // n
        local = _LOCAL[spec.reduce_op]

        def per_shard(batch):
            out = spec.mapfn(batch)
            if spec.combinerfn is not None:
                out = spec.combinerfn(out)
            buckets = spec.partitionfn(out)      # [num_p, ...] per mapper

            def shuffle_reduce(b):
                # [num_p, ...] → [n, per_dev, ...]: outer = destination
                b = b.reshape((n, per_dev) + b.shape[1:])
                # exchange: device p receives every mapper's buckets for
                # its per_dev partitions → [n(mappers), per_dev, ...]
                b = lax.all_to_all(b, axis, split_axis=0, concat_axis=0,
                                   tiled=False)
                # fold over the mapper axis — the k-way merge + reducefn
                return local(b, axis=0)          # [per_dev, ...]

            return jax.tree.map(shuffle_reduce, buckets)

        mapped = shard_map(
            per_shard, mesh=self.mesh,
            in_specs=(P(self.axis),), out_specs=P(self.axis))
        return jax.jit(mapped)

    def run_bucketed(self, batch) -> Any:
        """map → combine → partition → all_to_all shuffle → local reduce.
        Returns the pytree with the partition axis sharded over the mesh
        (device p owns partitions [p*per_dev, (p+1)*per_dev) — one "reduce
        job per partition", server.lua:300-325)."""
        result = self._bucketed_fn(self.shard_inputs(batch))
        if self.spec.finalfn is not None:
            return self.spec.finalfn(result)
        return result

    # -- iterative loop (the "loop" protocol, on device) --------------------

    def run_loop(self, init_state, step_fn, n_steps: int):
        """Run ``state = step_fn(state, executor-reduced-result)`` for
        ``n_steps`` iterations entirely inside one jitted ``lax.scan`` —
        the zero-coordination-round-trips hot loop (BASELINE.md north
        star). ``step_fn(state) -> (state, aux)`` must itself invoke this
        executor's keyed pipeline via closures over mapfn; provided here
        as the generic scan harness used by train/.
        """
        def body(state, _):
            return step_fn(state)

        @jax.jit
        def scan_all(state):
            return lax.scan(body, state, None, length=n_steps)

        return scan_all(init_state)


def differentiable_keyed(mapfn, mesh, axis: str = "dp",
                         reduce_op: str = "mean"):
    """A DIFFERENTIABLE keyed MapReduce primitive (the DrJAX shape:
    arXiv:2403.07128 exposes map/reduce as primitives grads flow
    through).

    ``mapfn(params, shard) -> pytree`` runs per device on its shard of
    the batch; the returned ``f(params, batch) -> reduced`` replicates
    the cross-device reduction's result and is traceable INSIDE user jit
    / grad / vmap. The backward pass is automatic: psum/pmean transpose
    to broadcast (+scale), so ``jax.grad(lambda p: loss(f(p, batch)))``
    differentiates through both the map and the collective — this is
    exactly how the DP trainer's gradient all-reduce arises, exposed as
    a reusable primitive for custom aggregation programs (federated
    means, per-key statistics, distributed EM steps).

    Only ``sum`` and ``mean`` are permitted: pmax/pmin have no JAX
    differentiation rule, which would break this primitive's one
    advertised contract at grad time (use TpuExecutor for forward-only
    max/min reductions).
    """
    if reduce_op not in ("sum", "mean"):
        raise ValueError(
            f"differentiable_keyed needs reduce_op 'sum' or 'mean', got "
            f"{reduce_op!r} — pmax/pmin are not differentiable; use "
            "TpuExecutor.run_keyed for forward-only max/min")
    cross = _CROSS[reduce_op]

    def per_shard(params, batch):
        out = mapfn(params, batch)
        return jax.tree.map(lambda x: cross(x, axis), out)

    return shard_map(per_shard, mesh=mesh,
                         in_specs=(P(), P(axis)), out_specs=P())
