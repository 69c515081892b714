"""lmr-hybrid: stage-granular in-graph lowering (DESIGN §28).

PR 14's engine ladder is all-or-nothing: one store-plane verdict
anywhere in the data plane and the WHOLE task runs interpreted. But the
static oracle (analysis/contracts.py) verdicts per *function*, so this
module compiles the qualifying *legs* of a store-plane task and leaves
the rest interpreted — the third rung between ``ingraph`` and
``store``:

- **compiled map+combine** (:class:`HybridMapEngine`): a batch of map
  jobs traced through ONE jitted program (same two lowering tiers as
  engine/ingraph.py — a shard_map tier stacking jobs over the mesh's
  ``dp`` axis, and a jit-unrolled tier for concrete/heterogeneous job
  keys). The fetched per-job groupings then flow through the SAME
  publish tail as the interpreted plane (engine/job.py
  publish_map_groups), so spills are ordinary JSEG frames and the
  store-plane shuffle, push mode, replication/coding, and speculation
  compose completely unchanged. partitionfn is NOT required to lower:
  it routes host-side on the concrete emitted keys inside that shared
  tail.
- **compiled reduce** (:class:`HybridReduceFold`): the host-side k-way
  merge stays (engine/job.py run_reduce_job), but each multi-value
  group is folded by a jitted sum program instead of the interpreted
  reducefn — gated by the SAME two structural jaxpr proofs as the psum
  tier (``_sum_fold`` ∧ ``_singleton_passthrough``), so only reducers
  provably equal to an elementwise sum compile; everything else falls
  through to the interpreted fold, group by group.

Fallback policy: the hybrid rung NEVER crashes, even when forced
(``engine=hybrid``). An oracle-rejected leg stays interpreted from the
start; a trace-time failure retires that leg permanently and replays
its jobs interpreted. Every degrade leaves evidence — a log line, a
``hybrid.fallback`` span with the stage, and a ``hybrid_fallbacks``
counter folded into IterationStats by BOTH executors (the
stats.COUNTER_FOLD discipline); successes count as
``hybrid_map_legs`` / ``hybrid_reduce_legs``.
"""

from __future__ import annotations

import collections
import sys
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

from lua_mapreduce_tpu.core.serialize import to_plain
from lua_mapreduce_tpu.engine.contract import TaskSpec
from lua_mapreduce_tpu.engine.ingraph import (EngineDecision,
                                              LoweringUnsupported,
                                              _flatten_out,
                                              _group_signature,
                                              _key_scalar, _rebuild,
                                              _run_map,
                                              _singleton_passthrough,
                                              _sum_fold, _unflatten_out,
                                              _value_leaves,
                                              record_hybrid_fallback)
from lua_mapreduce_tpu.trace.span import active_tracer


# --------------------------------------------------------------------------
# compiled map+combine leg
# --------------------------------------------------------------------------

class _MapPlan:
    """The jit tier's output plan: per job, per emitted key (emit
    order), per value — the flat-output slice and value treedef
    captured during the ONE trace."""

    def __init__(self):
        # per job: [(emit_key, [(treedef, start, count), ...]), ...]
        self.jobs: List[list] = []

    def finish(self, per_job: List["collections.OrderedDict"]) -> tuple:
        # reset first: jit may trace more than once per compile
        self.jobs = []
        flat: List = []
        for groups in per_job:
            entries = []
            for k, vs in groups.items():
                vals = []
                for v in vs:
                    leaves, td = _flatten_out(v)
                    vals.append((td, len(flat), len(leaves)))
                    flat.extend(leaves)
                entries.append((k, vals))
            self.jobs.append(entries)
        return tuple(flat)

    def unflatten(self, outputs: tuple, n_jobs: int) -> List[dict]:
        res = []
        for entries in self.jobs[:n_jobs]:
            groups: Dict[Any, list] = {}
            for k, vals in entries:
                groups[k] = [
                    to_plain(_unflatten_out(td, list(outputs[s:s + c])))
                    for td, s, c in vals]
            res.append(groups)
        return res


class _StackedMapPlan:
    """The shard_map tier's plan: every job emits the same keys the
    same number of times (asserted in-trace), and each output leaf
    carries a leading job axis — job j's value is row j."""

    def __init__(self):
        # [(emit_key, [(treedef, start, count), ...])] — shared by jobs
        self.entries: List[tuple] = []

    def unflatten(self, outputs: tuple, n_jobs: int) -> List[dict]:
        res = []
        for j in range(n_jobs):
            groups: Dict[Any, list] = {}
            for k, vals in self.entries:
                groups[k] = [
                    to_plain(_unflatten_out(
                        td, [outputs[s + i][j] for i in range(c)]))
                    for td, s, c in vals]
            res.append(groups)
        return res


class HybridMapEngine:
    """Compile-once batched map+combine for one TaskSpec.

    :meth:`run_batch` takes a lease's ``(map_key, map_value)`` pairs
    and returns each job's ``{emitted_key: [plain values]}`` grouping —
    exactly what make_map_emit accumulates on the interpreted plane,
    with the same combiner rule (folded in-trace for groups longer than
    one). The caller feeds each grouping to
    engine/job.py:publish_map_groups, so validation, partition routing,
    and the spill/push sinks are shared code, not a parallel
    implementation.

    Tiers mirror engine/ingraph.py: **shard_map** stacks uniform
    numeric-keyed jobs over the ``dp`` axis (padded with job-0 replays
    whose rows the host discards — no collectives are needed, the
    shuffle stays on the store plane); **jit** unrolls concrete job
    keys (the tier data-dependent emit keys need — a traced job key
    makes ``_run_map`` refuse them). ``traces`` counts outer compiles
    for the no-retrace contract.
    """

    def __init__(self, spec: TaskSpec, mesh=None, axis: str = "dp"):
        self.spec = spec
        self.axis = axis
        self._mesh = mesh
        self.traces = 0
        self.mode: Optional[str] = None     # "shard_map" | "jit"
        self._program: Optional[Callable] = None
        self._plan = None
        self._sig: Optional[tuple] = None

    def _ensure_mesh(self):
        if self._mesh is None:
            from lua_mapreduce_tpu.parallel.mesh import make_mesh
            self._mesh = make_mesh(mp=1)
        return self._mesh

    # -- public -------------------------------------------------------------

    def run_batch(self, pairs: List[Tuple[Any, Any]]) -> List[dict]:
        """Map+combine every ``(map_key, map_value)`` pair through one
        compiled program; returns per-job plain groupings in input
        order. Raises LoweringUnsupported (caller degrades) when the
        batch is outside the compilable surface."""
        import jax
        keys = [k for k, _ in pairs]
        prepped = []
        for i, (_, v) in enumerate(pairs):
            leaves, struct = _value_leaves(v, f"jobs[{i}].value")
            prepped.append((leaves, struct))
        if self._program is not None \
                and self._mode_sig(keys, prepped, self.mode) == self._sig:
            outputs = self._program(*self._flat_args(keys, prepped))
        else:
            outputs = self._build_and_run(keys, prepped)
        return self._plan.unflatten(jax.device_get(outputs), len(keys))

    def _mode_sig(self, keys, prepped, mode) -> tuple:
        structs = tuple(st for _, st in prepped)
        if mode == "shard_map":
            kind = "f" if any(isinstance(k, float) for k in keys) else "i"
            return ("shard_map", len(keys), kind, structs)
        return ("jit", tuple(keys), structs)

    # -- build --------------------------------------------------------------

    def _build_and_run(self, keys, prepped) -> tuple:
        first_err: Optional[Exception] = None
        uniform = len({st for _, st in prepped}) == 1
        numeric_keys = all(isinstance(k, (int, float))
                           and type(k) is not bool for k in keys)
        if uniform and numeric_keys:
            try:
                return self._finish_build(
                    *self._build_shard_map(keys, prepped),
                    mode="shard_map",
                    sig=self._mode_sig(keys, prepped, "shard_map"))
            except Exception as e:          # noqa: BLE001 — tier fallback
                first_err = e
                self.traces = 0             # aborted trace doesn't count
        try:
            return self._finish_build(
                *self._build_jit(keys, prepped), mode="jit",
                sig=self._mode_sig(keys, prepped, "jit"))
        except LoweringUnsupported:
            raise
        except Exception as e:              # noqa: BLE001
            hint = (f"; batched tier also failed: {first_err}"
                    if first_err is not None else "")
            raise LoweringUnsupported(
                f"hybrid map lowering failed at trace time: "
                f"{type(e).__name__}: {e}{hint}") from e

    def _finish_build(self, program, plan, outputs, *, mode, sig) -> tuple:
        self._program, self._plan, self.mode = program, plan, mode
        self._sig = sig
        return outputs

    def _flat_args(self, keys, prepped) -> list:
        if self.mode == "shard_map":
            return self._stacked_args(keys, prepped)
        return [leaf for leaves, _ in prepped for leaf in leaves]

    def _stacked_args(self, keys, prepped) -> list:
        """[key array] + per-leaf job stacks padded to the mesh axis
        with job-0 replays (rows the host unflatten discards)."""
        import numpy as np
        mesh = self._ensure_mesh()
        n = mesh.shape[self.axis]
        J = len(keys)
        Jp = -(-J // n) * n
        pad = Jp - J
        karr = np.asarray([_key_scalar(k, "jobs") for k in keys])
        karr = np.concatenate([karr, np.repeat(karr[:1], pad)]) \
            if pad else karr
        if karr.dtype.kind == "f":
            karr = karr.astype(np.float32)
        else:
            if karr.size and (karr.min() < -2**31 or karr.max() >= 2**31):
                raise LoweringUnsupported(
                    "job keys outside int32 range — the compiled plane "
                    "would wrap them; run on the store plane")
            karr = karr.astype(np.int32)
        args = [karr]
        n_leaves = len(prepped[0][0])
        for li in range(n_leaves):
            rows = [prepped[j][0][li] for j in range(J)]
            rows += [rows[0]] * pad
            args.append(np.stack(rows))
        return args

    def _build_shard_map(self, keys, prepped):
        import jax
        import jax.numpy as jnp
        from jax import shard_map
        from jax.sharding import PartitionSpec as P

        spec, axis = self.spec, self.axis
        mesh = self._ensure_mesh()
        n = mesh.shape[axis]
        J = len(keys)
        L = -(-J // n)
        struct = prepped[0][1]
        plan = _StackedMapPlan()

        def per_shard(karr, *leaves):
            slot_groups = []
            for i in range(L):
                value = _rebuild(struct, [leaf[i] for leaf in leaves])
                slot_groups.append(_run_map(spec, karr[i], value))
            sig0 = _group_signature(slot_groups[0])
            for g in slot_groups[1:]:
                if _group_signature(g) != sig0:
                    raise LoweringUnsupported(
                        "emission structure diverges across map jobs — "
                        "the batched tier needs every job to emit the "
                        "same keys the same number of times")
            plan.entries = []               # one trace owns the plan
            flat: List = []
            for key, m in sig0:
                vals = []
                for vi in range(m):
                    stacked = jax.tree.map(
                        lambda *xs: jnp.stack(xs),
                        *[slot_groups[i][key][vi] for i in range(L)])
                    leaves_out, td = _flatten_out(stacked)
                    vals.append((td, len(flat), len(leaves_out)))
                    flat.extend(leaves_out)
                plan.entries.append((key, vals))
            return tuple(flat)

        n_leaves = len(prepped[0][0])
        # out_specs=P(axis): each leaf keeps its leading job axis — the
        # global result stacks device blocks in job order, no collective
        mapped = shard_map(per_shard, mesh=mesh,
                           in_specs=(P(axis),) * (1 + n_leaves),
                           out_specs=P(axis), check_vma=False)

        def program(karr, *leaves):
            self.traces += 1
            return mapped(karr, *leaves)

        program = jax.jit(program)
        outputs = program(*self._stacked_args(keys, prepped))
        if not outputs:
            raise LoweringUnsupported(
                "map jobs emitted nothing on the batched tier — "
                "shard_map needs at least one output to shard")
        return program, plan, outputs

    def _build_jit(self, keys, prepped):
        import jax

        spec = self.spec
        plan = _MapPlan()
        structs = [st for _, st in prepped]
        counts = [len(leaves) for leaves, _ in prepped]

        def program(*flat):
            self.traces += 1
            per_job = []
            pos = 0
            for j, key in enumerate(keys):
                leaves = list(flat[pos:pos + counts[j]])
                pos += counts[j]
                per_job.append(
                    _run_map(spec, key, _rebuild(structs[j], leaves)))
            return plan.finish(per_job)

        program = jax.jit(program)
        outputs = program(*[leaf for leaves, _ in prepped
                            for leaf in leaves])
        return program, plan, outputs


# --------------------------------------------------------------------------
# compiled reduce leg
# --------------------------------------------------------------------------

class HybridReduceFold:
    """run_reduce_job's ``reduce_fold`` hook: fold multi-value groups
    with one jitted sum program instead of the interpreted reducefn.

    Gated per (key, arity, value-structure) signature by the SAME two
    structural jaxpr proofs as the in-graph psum tier — the fold must
    be provably the elementwise sum (``_sum_fold``) AND the singleton
    reducefn call provably the identity (``_singleton_passthrough``,
    which then restores the user's own output structure/dtypes with one
    host call). Unproven or non-numeric groups return ``None`` and the
    interpreted reducefn runs — a partial fold can change speed, never
    bytes. Any hard error retires the fold permanently with counted/
    traced evidence; a proof-cache blowup (pathologically many distinct
    signatures) retires it too, because probing would cost more than
    folding saves.
    """

    MAX_PROBES = 64

    def __init__(self, spec: TaskSpec):
        self.spec = spec
        self.retired = False
        self.retire_reason: Optional[str] = None
        self.folded_groups = 0
        self._used = False
        self._proofs: Dict[tuple, bool] = {}
        self._sum_prog: Optional[Callable] = None

    def take_used(self) -> bool:
        """True once per window in which the fold actually folded —
        the executors' per-job ``hybrid_reduce_legs`` bump."""
        u = self._used
        self._used = False
        return u

    def __call__(self, key, values):
        if self.retired or len(values) < 2:
            return None
        try:
            return self._fold(key, values)
        except Exception as e:              # noqa: BLE001 — policy point
            self._retire(f"{type(e).__name__}: {e}")
            return None

    def _retire(self, reason: str) -> None:
        from lua_mapreduce_tpu.faults.retry import COUNTERS
        self.retired = True
        self.retire_reason = reason
        COUNTERS.bump("hybrid_fallbacks")
        record_hybrid_fallback("reduce", reason)
        print(f"[hybrid] compiled reduce retired: {reason}",
              file=sys.stderr)

    def _fold(self, key, values):
        try:
            prepped = [_value_leaves(v, "reduce.value") for v in values]
        except LoweringUnsupported:
            return None                     # group not numeric — interpret
        if len({st for _, st in prepped}) != 1:
            return None
        struct = prepped[0][1]
        token = key if isinstance(key, (int, float, str)) \
            and type(key) is not bool else repr(key)
        sig = (token, len(values), struct)
        proven = self._proofs.get(sig)
        if proven is None:
            if len(self._proofs) >= self.MAX_PROBES:
                self._retire(
                    f"more than {self.MAX_PROBES} distinct (key, arity, "
                    "structure) signatures — per-group proof probing "
                    "would cost more than the compiled fold saves")
                return None
            template = _rebuild(struct, list(prepped[0][0]))
            proven = (_sum_fold(self.spec, key, template, len(values))
                      and _singleton_passthrough(self.spec, key, template))
            self._proofs[sig] = proven
        if not proven:
            return None
        import jax
        import numpy as np
        n_leaves = len(prepped[0][0])
        stacked = [np.stack([prepped[i][0][li] for i in range(len(values))])
                   for li in range(n_leaves)]
        if self._sum_prog is None:
            import jax.numpy as jnp
            self._sum_prog = jax.jit(
                lambda *xs: tuple(jnp.sum(x, axis=0) for x in xs))
        outs = jax.device_get(self._sum_prog(*stacked))
        rebuilt = _rebuild(struct, list(outs))
        # the proven-identity singleton pass restores the user's own
        # output structure (dict insertion order, dtype converts) so
        # serialization matches the interpreted plane exactly
        reduced = to_plain(self.spec.reducefn(key, [rebuilt]))
        self.folded_groups += 1
        self._used = True
        return reduced


# --------------------------------------------------------------------------
# executor-side driver (LocalExecutor; the Worker wires the same parts
# through its lease loop — see engine/worker.py)
# --------------------------------------------------------------------------

class HybridRunner:
    """LocalExecutor's hybrid driver: owns the per-leg engines, the
    ``hybrid.run`` span, the counters, and the degrade policy — the
    exact shape of IngraphRunner so the executors cannot drift."""

    def __init__(self, spec: TaskSpec, decision: EngineDecision,
                 mesh=None, log=None):
        self.spec = spec
        self.decision = decision
        stages = decision.stages or {}
        on = decision.chosen == "hybrid"
        self.map_engine = HybridMapEngine(spec, mesh=mesh) \
            if on and stages.get("map") else None
        self.fold = HybridReduceFold(spec) \
            if on and stages.get("reduce") else None
        self._log = log or (lambda msg: print(f"[hybrid] {msg}",
                                              file=sys.stderr))
        self._evidence_done = False
        if on:
            self._log(f"hybrid plane selected: {decision.reason}")

    @property
    def active(self) -> bool:
        return self.decision.chosen == "hybrid"

    @property
    def map_active(self) -> bool:
        return self.map_engine is not None

    def reduce_fold(self):
        """The run_reduce_job hook, or None once retired/absent."""
        if self.fold is not None and not self.fold.retired:
            return self.fold
        return None

    def ensure_evidence(self) -> None:
        """Forced ``engine=hybrid`` with ZERO qualifying legs runs pure
        store-plane — once per task, leave the counted/traced/logged
        record that the request degraded (the never-crash contract's
        visible half)."""
        if self._evidence_done:
            return
        self._evidence_done = True
        if self.active and self.map_engine is None and self.fold is None:
            from lua_mapreduce_tpu.faults.retry import COUNTERS
            reason = ("no stage qualifies for the hybrid plane: "
                      f"{self.decision.reason}")
            COUNTERS.bump("hybrid_fallbacks")
            record_hybrid_fallback("task", reason)
            self._log(reason)

    def run_map_leg(self, jobs, store, *, segment_format="v1",
                    replication=1, push=False, push_pool=None,
                    spec_lineage=None, iteration: int = 0) -> bool:
        """Compile+run the whole iteration's map jobs as one program
        and publish every job through the shared tail. True = spills
        published (caller skips interpreted map); False = degraded
        (permanently — counted, logged, traced) and the caller runs
        the interpreted map phase."""
        from lua_mapreduce_tpu.faults.retry import COUNTERS
        if self.map_engine is None or not jobs:
            return False
        tracer = active_tracer()
        t0 = time.time()
        try:
            per_job = self.map_engine.run_batch(jobs)
            from lua_mapreduce_tpu.engine.job import publish_map_groups
            for i, groups in enumerate(per_job):
                publish_map_groups(
                    self.spec, store, str(i), groups,
                    segment_format=segment_format,
                    replication=replication, push=push,
                    push_pool=push_pool, spec_lineage=spec_lineage)
        except Exception as exc:            # noqa: BLE001 — policy point
            reason = f"{type(exc).__name__}: {exc}"
            COUNTERS.bump("hybrid_fallbacks")
            record_hybrid_fallback("map", reason)
            self._log(f"iteration {iteration}: compiled map leg failed "
                      f"({reason}); map jobs run interpreted")
            self.map_engine = None
            return False
        COUNTERS.bump("hybrid_map_legs")
        if tracer is not None:
            now = tracer.clock()
            tracer.add("hybrid.run", now - (time.time() - t0), now,
                       ns="hybrid", stage="map", job_id=iteration,
                       jobs=len(jobs), mode=self.map_engine.mode,
                       traces=self.map_engine.traces)
        return True

    def note_reduce_job(self) -> None:
        """Post-reduce-job counter hook: one ``hybrid_reduce_legs``
        bump per reduce job in which the fold actually folded."""
        if self.fold is not None and self.fold.take_used():
            from lua_mapreduce_tpu.faults.retry import COUNTERS
            COUNTERS.bump("hybrid_reduce_legs")


def utest() -> None:
    """Host-only self-test: plan round-trips and fold gating (the
    compiled tiers run under the cpu-pinned pytest conftest,
    tests/test_hybrid.py)."""
    plan = _MapPlan()
    out = plan.finish([collections.OrderedDict([("a", [1, 2]), ("b", [3])]),
                       collections.OrderedDict([("a", [4])])])
    assert out == (1, 2, 3, 4)
    jobs = plan.unflatten(out, 2)
    assert jobs == [{"a": [1, 2], "b": [3]}, {"a": [4]}]
    assert plan.unflatten(out, 1) == [{"a": [1, 2], "b": [3]}]
