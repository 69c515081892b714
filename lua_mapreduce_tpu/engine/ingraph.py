"""In-graph execution engine: MapReduce compiled to JAX collectives.

ROADMAP item 3 (DESIGN §26) — the consumer of the static lowerability
oracle PR 13 shipped (analysis/contracts.py): a six-function task
(engine/contract.py) whose data-plane functions verdict ``in-graph``
is lowered to ONE jitted program instead of the per-record Python loop
of engine/job.py.  This module finally fuses the repo's two halves:
the coordination plane (engine/, coord/) keeps taskfn/finalfn — job
enumeration, the "loop" protocol, result iteration — on the host,
while the data plane (mapfn → partitionfn → reducefn) runs as a
shard_map-over-mesh program in the style of parallel/tpu_engine.py:

- **map**    — per-shard compute over the mesh's ``dp`` axis: the job
  batch is stacked on a leading axis, sharded over devices, and the
  user mapfn is traced once per device slot with the job key/value as
  traced arrays (the vmapped-shard shape of TpuExecutor.run_keyed).
- **shuffle** — emitted keys are CONCRETE at trace time (the oracle's
  in-graph surface guarantees it), so partitionfn routing is resolved
  statically and the device-axis exchange is a collective, not files:
  sum-shaped reducers (verified per key — see ``_sum_fold``) fold as a
  masked local sum + ``psum`` (tpu_engine's keyed ``_CROSS`` table);
  every other in-graph reducer folds over an ``all_gather`` of the job
  axis in exactly the store plane's canonical value order.
- **reduce** — the fold result is fetched once per iteration and
  published as ordinary partition result files — byte-identical lines
  (``dump_record`` through ``to_plain``) in the same canonical key
  order as run_reduce_job, so finalfn, golden diffs, and every
  downstream consumer are engine-invariant.

Engine selection (``resolve_engine``/``select_engine``) is automatic:
``auto`` (the default) runs the static oracle at task-load time and
chooses the store plane for any non-in-graph verdict; ``ingraph``
forces the compiled plane (trace failures raise — the CI hard mode);
``store`` opts out entirely.  A task the oracle accepts but whose
lowering raises at trace time (data-dependent shapes, traced emit
keys) degrades to the store plane under ``auto`` — a logged, traced
(``lowering``/``ingraph.fallback`` spans), counted
(``ingraph_fallbacks``) decision, never a crash.

The ``finalfn → "loop"`` protocol iterates WITHOUT retracing: per-
iteration state is threaded through the taskfn job values as arrays
(same shapes every iteration → one compile per task, counted by
:attr:`InGraphEngine.traces` and asserted in tests/test_ingraph.py).
"""

from __future__ import annotations

import collections
import dataclasses
import sys
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

from lua_mapreduce_tpu.core import tuples
from lua_mapreduce_tpu.core.serialize import (assert_serializable,
                                              dump_record, sorted_keys,
                                              to_plain)
from lua_mapreduce_tpu.engine.contract import TaskSpec
from lua_mapreduce_tpu.trace.span import active_tracer

ENGINES = ("auto", "ingraph", "hybrid", "store")

# the data-plane slots the oracle folds into the task verdict
# (analysis/contracts.py keeps taskfn/finalfn control-plane by
# construction — they run host-side in BOTH engines)
_DATA_PLANE = ("mapfn", "partitionfn", "reducefn", "combinerfn")


class LoweringError(RuntimeError):
    """In-graph lowering/execution failed under ``engine="ingraph"``
    (the forced hard mode raises instead of falling back)."""


class LoweringUnsupported(LoweringError):
    """The task is outside the compilable surface (non-numeric job
    values, data-dependent emit keys, divergent per-job emission
    structure...). Under ``engine="auto"`` this is the graceful
    store-plane fallback trigger, never a crash."""


def resolve_engine(arg: Optional[str]) -> str:
    """The engine knob's shared resolution order: explicit argument,
    else ``LMR_ENGINE`` env, else ``"auto"`` — mirroring
    resolve_push/resolve_replication."""
    if arg is None:
        import os
        arg = os.environ.get("LMR_ENGINE") or "auto"
    arg = str(arg).strip().lower()
    if arg not in ENGINES:
        raise ValueError(f"engine {arg!r} not in {ENGINES}")
    return arg


# --------------------------------------------------------------------------
# engine selection: the oracle consult + the lowering trace span
# --------------------------------------------------------------------------

@dataclasses.dataclass
class EngineDecision:
    """One task's engine-selection outcome (the ``lowering`` span's
    payload): what was requested, what the static oracle said per
    data-plane function, and which plane was chosen."""
    requested: str
    chosen: str                       # "ingraph" | "hybrid" | "store"
    verdict: Optional[str]            # oracle task verdict (None = not run)
    functions: Dict[str, dict]        # fn -> {"verdict", "reasons"}
    reason: str                       # one human-readable line
    oracle_s: float = 0.0
    # stage-granular qualification (DESIGN §28): leg -> compiled?,
    # populated only when the hybrid rung was considered. The legs are
    # "map" (mapfn+combinerfn as one vmapped program) and "reduce"
    # (reducefn as a jitted fold under the host merge).
    stages: Optional[Dict[str, bool]] = None


def oracle_report(spec: TaskSpec) -> Tuple[str, Dict[str, dict]]:
    """Run the static lowerability oracle (analysis/contracts.py) over
    the spec's data-plane modules. Statically — no user code executes
    here; specs that cannot be resolved to importable modules (bare
    callables, dict modules) verdict ``store-plane`` with a reason, so
    ``auto`` degrades instead of guessing."""
    from lua_mapreduce_tpu.analysis import contracts
    try:
        desc = spec.describe()
    except TypeError as e:
        why = f"not statically checkable: {e}"
        return contracts.VERDICT_STORE, {
            f: {"verdict": contracts.VERDICT_STORE, "reasons": [why]}
            for f in _DATA_PLANE if getattr(spec, f, None) is not None}
    reports: Dict[str, Any] = {}      # module name -> TaskReport
    functions: Dict[str, dict] = {}
    for fname in _DATA_PLANE:
        mod = desc["functions"].get(fname)
        if mod is None:
            continue
        rep = reports.get(mod)
        if rep is None:
            rep = reports[mod] = contracts.check_task(mod)
        fr = rep.functions.get(fname)
        if fr is None:
            functions[fname] = {
                "verdict": contracts.VERDICT_STORE,
                "reasons": [f"{fname} not statically resolvable in {mod} "
                            "(decorated / re-exported / dynamically built)"]}
        else:
            functions[fname] = {"verdict": fr.verdict,
                                "reasons": list(fr.reasons)}
    verdict = (contracts.VERDICT_INGRAPH
               if functions and all(f["verdict"] == contracts.VERDICT_INGRAPH
                                    for f in functions.values())
               else contracts.VERDICT_STORE)
    return verdict, functions


def hybrid_stage_legs(spec: TaskSpec,
                      functions: Dict[str, dict]) -> Dict[str, bool]:
    """Which hybrid legs the per-function verdicts qualify (DESIGN §28).

    - ``map``: mapfn verdicts in-graph AND combinerfn (when present)
      does too — the two fuse into one traced program. partitionfn is
      NOT required: routing runs host-side on the concrete emitted keys
      inside the shared publish tail, so a store-plane partitionfn
      composes with a compiled map leg (extsort's exact shape inverted).
    - ``reduce``: reducefn present and in-graph — the host merge feeds
      it as a jitted fold.
    """
    from lua_mapreduce_tpu.analysis import contracts

    def _ok(fname):
        d = functions.get(fname)
        return d is not None and d["verdict"] == contracts.VERDICT_INGRAPH

    map_ok = _ok("mapfn") and (spec.combinerfn is None or _ok("combinerfn"))
    reduce_ok = spec.reducefn is not None and _ok("reducefn")
    return {"map": map_ok, "reduce": reduce_ok}


def select_engine(spec: TaskSpec, engine: Optional[str] = None
                  ) -> EngineDecision:
    """Resolve the engine knob and (for everything but ``store``)
    consult the oracle. Pure decision — no tracing/compiling here.

    The ``auto`` ladder (DESIGN §28): task verdict in-graph → whole-task
    ``ingraph``; else any hybrid leg qualifies → ``hybrid`` with that
    leg set; else ``store``. Forced ``hybrid`` NEVER raises — unlike
    forced ``ingraph`` — because the hybrid rung's contract is
    per-stage best effort: an oracle-rejected leg simply stays
    interpreted (zero qualifying legs = pure store-plane execution,
    with the rejection carried in the decision for trace/log/counter
    evidence).
    """
    from lua_mapreduce_tpu.analysis import contracts
    requested = resolve_engine(engine)
    t0 = time.time()
    verdict: Optional[str] = None
    functions: Dict[str, dict] = {}
    stages: Optional[Dict[str, bool]] = None
    if requested != "store":
        verdict, functions = oracle_report(spec)

    def _offender():
        return next(
            (f"{n}: {d['reasons'][0]}" for n, d in functions.items()
             if d["verdict"] != contracts.VERDICT_INGRAPH and d["reasons"]),
            "data plane not in-graph eligible")

    def _legs_str(legs):
        on = [n for n, ok in legs.items() if ok]
        return "+".join(on) if on else "none"

    if requested == "store":
        chosen, reason = "store", "engine=store requested"
    elif requested == "ingraph":
        chosen = "ingraph"
        reason = ("engine=ingraph forced (oracle verdict "
                  f"{verdict}; trace failures raise)")
    elif requested == "hybrid":
        stages = hybrid_stage_legs(spec, functions)
        chosen = "hybrid"
        reason = (f"engine=hybrid forced (compiled legs: "
                  f"{_legs_str(stages)}; unqualified legs stay "
                  "interpreted, trace failures degrade)")
    elif verdict == contracts.VERDICT_INGRAPH:
        chosen, reason = "ingraph", "oracle verdict in-graph"
    else:
        stages = hybrid_stage_legs(spec, functions)
        if any(stages.values()):
            chosen = "hybrid"
            reason = (f"oracle verdict {verdict} ({_offender()}); "
                      f"stage verdicts qualify legs: {_legs_str(stages)}")
        else:
            chosen = "store"
            stages = None
            reason = f"oracle verdict {verdict} ({_offender()})"
    return EngineDecision(requested=requested, chosen=chosen,
                          verdict=verdict, functions=functions,
                          reason=reason, oracle_s=time.time() - t0,
                          stages=stages)


def record_lowering(decision: EngineDecision) -> None:
    """Emit the ``lowering`` trace span carrying the whole decision —
    verdict, per-function reasons, chosen engine — so a silent
    store-plane fallback is visible in the timeline (DESIGN §26).
    No-op when tracing is off."""
    tracer = active_tracer()
    if tracer is None:
        return
    now = tracer.clock()
    attrs = {"engine": decision.chosen, "requested": decision.requested,
             "verdict": decision.verdict or "(oracle skipped)",
             "reason": decision.reason}
    for fname, d in decision.functions.items():
        why = f" ({d['reasons'][0]})" if d["reasons"] else ""
        attrs[f"fn.{fname}"] = d["verdict"] + why
    tracer.add("lowering", now - decision.oracle_s, now, ns="ingraph",
               **attrs)
    if decision.stages is None:
        return
    # stage-granular decisions (DESIGN §28): one ``lowering.<stage>``
    # span per hybrid leg so TraceCollection.lowering_decisions shows
    # WHICH legs compiled, not just that the hybrid rung was chosen
    _LEG_FNS = {"map": ("mapfn", "combinerfn"), "reduce": ("reducefn",)}
    for stage, compiled in decision.stages.items():
        sattrs = {"stage": stage,
                  "engine": "hybrid" if compiled else "store",
                  "compiled": str(bool(compiled)).lower()}
        for fname in _LEG_FNS[stage]:
            d = decision.functions.get(fname)
            if d is not None:
                why = f" ({d['reasons'][0]})" if d["reasons"] else ""
                sattrs[f"fn.{fname}"] = d["verdict"] + why
        tracer.add(f"lowering.{stage}", now, now, ns="hybrid", **sattrs)


def record_tier(mode: str, collective_error: str) -> None:
    """Emit the ``lowering.tier`` span: the in-graph engine compiled on
    the ``mode`` tier after the collective (shard_map) tier refused the
    task with ``collective_error``. The run is correct either way, but
    the jit tier uses one device."""
    tracer = active_tracer()
    if tracer is None:
        return
    now = tracer.clock()
    tracer.add("lowering.tier", now, now, ns="ingraph", stage="tier",
               engine=mode, compiled="true", reason=collective_error)


def record_fallback(reason: str) -> None:
    """Emit the ``ingraph.fallback`` span marking a RUNTIME degrade to
    the store plane (oracle accepted, lowering raised)."""
    tracer = active_tracer()
    if tracer is None:
        return
    now = tracer.clock()
    tracer.add("ingraph.fallback", now, now, ns="ingraph", reason=reason)


def record_hybrid_fallback(stage: str, reason: str) -> None:
    """Emit the ``hybrid.fallback`` span: one compiled LEG degraded to
    the interpreted plane at runtime (oracle accepted the stage, the
    trace/execution did not). The run continues — only that leg's speed
    is lost, never its results."""
    tracer = active_tracer()
    if tracer is None:
        return
    now = tracer.clock()
    tracer.add("hybrid.fallback", now, now, ns="hybrid", stage=stage,
               reason=reason)


# --------------------------------------------------------------------------
# job-batch preparation (host side)
# --------------------------------------------------------------------------

def _leaf_array(x, path: str):
    """One numeric leaf → a canonical np array (f32 / i32 / bool — the
    same canonicalization jit would apply, made explicit so the retrace
    signature is stable across iterations)."""
    import numpy as np
    try:
        arr = np.asarray(x)
    except Exception as e:
        raise LoweringUnsupported(
            f"job value at {path} is not array-shaped: {e}") from None
    if arr.dtype == object or arr.dtype.kind not in "biuf":
        raise LoweringUnsupported(
            f"job value at {path} has non-numeric dtype {arr.dtype} "
            "(in-graph tasks declare array-shaped records)")
    if arr.dtype.kind == "f":
        arr = arr.astype(np.float32)
    elif arr.dtype.kind in "iu":
        # float narrowing is the documented allclose contract; INT
        # narrowing is not — a value outside int32 would silently WRAP
        # and the planes would diverge bit-for-bit on the workloads
        # promised byte-identical, so refuse (auto degrades to store)
        if arr.size and (arr.min() < -2**31 or arr.max() >= 2**31):
            raise LoweringUnsupported(
                f"job value at {path} holds integers outside int32 "
                "range — the compiled plane would wrap them; run on "
                "the store plane")
        arr = arr.astype(np.int32)
    return arr


def _value_leaves(v, path: str = "value") -> Tuple[list, Any]:
    """Flatten one job value into (numeric leaves, structure token).
    Dicts recurse per sorted key; everything else must coerce to one
    rectangular numeric array. The structure token doubles as the
    retrace-signature component."""
    if isinstance(v, dict):
        leaves: List = []
        struct: List = []
        for k in sorted(v):
            if not isinstance(k, str):
                raise LoweringUnsupported(
                    f"job value at {path} has non-str dict key {k!r}")
            sub, st = _value_leaves(v[k], f"{path}.{k}")
            leaves.extend(sub)
            struct.append((k, st))
        return leaves, ("dict", tuple(struct))
    arr = _leaf_array(v, path)
    return [arr], ("leaf", arr.shape, str(arr.dtype))


def _rebuild(struct, leaves: list):
    """Inverse of :func:`_value_leaves` over a (possibly traced) leaf
    list — consumed left to right."""
    kind = struct[0]
    if kind == "leaf":
        return leaves.pop(0)
    return {k: _rebuild(st, leaves) for k, st in struct[1]}


def _key_scalar(k, path: str):
    """Job keys on the compiled plane ride as traced scalars — numeric
    only (string keys force the unrolled tier, where keys stay
    concrete)."""
    if type(k) is bool or not isinstance(k, (int, float)):
        raise LoweringUnsupported(f"job key {k!r} at {path} is not numeric")
    return k


# --------------------------------------------------------------------------
# trace-time map/shuffle/reduce (shared by both lowering tiers)
# --------------------------------------------------------------------------

def _run_map(spec: TaskSpec, key, value) -> "collections.OrderedDict":
    """Trace one map job: run the user mapfn with a capturing emit and
    return the per-key grouped value lists — the exact grouping
    make_map_emit + run_map_job produce, with the same combiner rule
    (fold only groups longer than one). Emitted keys must be concrete
    (the oracle's in-graph surface computes them from static values);
    a traced key aborts the lowering."""
    import jax
    import jax.numpy as jnp
    groups: "collections.OrderedDict" = collections.OrderedDict()

    def emit(k, v):
        if isinstance(k, jax.core.Tracer):
            raise LoweringUnsupported(
                "mapfn emitted a data-dependent (traced) key — key "
                "spaces must be static to compile (DrJAX's fixed-key "
                "constraint); run on the store plane")
        k = to_plain(k)
        if isinstance(k, list):
            k = tuples.intern(k)
        try:
            v = jax.tree.map(jnp.asarray, v)
        except Exception as e:
            raise LoweringUnsupported(
                f"emitted value for key {k!r} is not traceable: "
                f"{type(e).__name__}: {e}") from None
        groups.setdefault(k, []).append(v)

    spec.mapfn(key, value, emit)
    combiner = spec.combiner_for_map
    if combiner is not None:
        for k in list(groups):
            if len(groups[k]) > 1:
                groups[k] = [combiner(k, groups[k])]
    return groups


def _group_signature(groups) -> Tuple:
    """(key, multiplicity) tuple used to assert per-job emission
    uniformity on the collective tier."""
    return tuple((k, len(vs)) for k, vs in groups.items())


def _flatten_out(v) -> Tuple[list, Any]:
    """Flatten a reduced-value pytree PRESERVING dict insertion order
    (jax.tree sorts dict keys, which would reorder the JSON bytes
    relative to the store plane's serialization of the same dict)."""
    if isinstance(v, dict):
        leaves: List = []
        struct: List = []
        for k in v:
            sub, st = _flatten_out(v[k])
            leaves.extend(sub)
            struct.append((k, st))
        return leaves, ("dict", tuple(struct))
    if isinstance(v, (list, tuple)) and not isinstance(v, tuples.Tuple):
        leaves = []
        struct = []
        for x in v:
            sub, st = _flatten_out(x)
            leaves.extend(sub)
            struct.append(st)
        return leaves, ("list", tuple(struct))
    return [v], ("leaf",)


def _unflatten_out(struct, leaves: list):
    kind = struct[0]
    if kind == "leaf":
        return leaves.pop(0)
    if kind == "dict":
        return {k: _unflatten_out(st, leaves) for k, st in struct[1]}
    return [_unflatten_out(st, leaves) for st in struct[1]]


class _Plan:
    """The static shuffle plan captured during the ONE trace: emitted
    key order, per-key reduced-value structure/offsets in the flat
    program output, partition routing, and which cross-device fold
    each key lowered to (psum vs all_gather — surfaced in the
    ``ingraph.run`` span attrs)."""

    def __init__(self, spec: TaskSpec):
        self.spec = spec
        self.keys: List[Any] = []
        self.treedefs: Dict[Any, Any] = {}
        self.slices: Dict[Any, Tuple[int, int]] = {}
        self.parts: Dict[Any, int] = {}
        self.folds: Dict[Any, str] = {}

    def finish(self, out: "collections.OrderedDict") -> tuple:
        """Record structure + partition routing and return the flat
        traced output tuple. Resets first: jit/shard_map may trace the
        body more than once per compile (abstract eval + lowering),
        and the plan must describe ONE trace, not their concatenation."""
        self.keys, self.treedefs, self.slices, self.parts = [], {}, {}, {}
        flat: List = []
        for key, val in out.items():
            leaves, td = _flatten_out(val)
            self.keys.append(key)
            self.treedefs[key] = td
            self.slices[key] = (len(flat), len(leaves))
            part = int(self.spec.partitionfn(key))
            if part < 0:
                raise ValueError(
                    f"partitionfn({key!r}) returned negative {part}")
            self.parts[key] = part
            flat.extend(leaves)
        return tuple(flat)

    def unflatten(self, outputs: tuple) -> Dict[Any, Any]:
        result = {}
        for key in self.keys:
            start, count = self.slices[key]
            result[key] = _unflatten_out(
                self.treedefs[key], list(outputs[start:start + count]))
        return result


def _sum_fold(spec: TaskSpec, key, value_template, n_values: int) -> bool:
    """Is ``reducefn(key, [v1..vn])`` provably the elementwise SUM of
    its inputs?  Two independent witnesses must agree:

    - the reducer's declared algebra (associative ∧ commutative flags
      — the user's contract promise, job.lua:104-106), and
    - a STRUCTURAL analysis of the fold's jaxpr at the REAL value
      count: only add / element-type-conversion primitives, no
      literal operands (a ``+ bias`` is not a sum), same output
      structure/dtypes as one input value, and — the exactness core —
      every output leaf receives every input value's corresponding
      leaf with multiplicity EXACTLY one (a fold that drops, repeats,
      or weights a value must not psum).

    A sum-shaped fold lowers to masked-local-sum + ``psum`` —
    bit-exact for integer values (int add is associative), within
    reassociation tolerance for floats (the documented allclose
    contract). Everything else takes the all_gather tier, which
    replays the store plane's sequential fold order exactly.

    The analysis is structural (not a concrete numeric probe) because
    it runs INSIDE the shard_map trace, where omnistaging lifts any
    eager evaluation into the surrounding program.
    """
    if not (spec.associative and spec.commutative) or n_values < 2:
        return False
    import jax
    import numpy as np
    from jax.extend.core import Literal
    leaves, td = jax.tree.flatten(value_template)
    shapes = [(tuple(x.shape), x.dtype) for x in leaves]
    probes = [
        jax.tree.unflatten(td, [np.zeros(s, d) for s, d in shapes])
        for _ in range(n_values)]
    traced = _trace_reducer(lambda *vs: spec.reducefn(key, list(vs)),
                            probes)
    if traced is None:
        return False
    core, out_shape = traced
    if jax.tree.structure(out_shape) != td:
        return False
    n_leaves = len(shapes)
    if len(core.invars) != n_values * n_leaves:
        return False
    contrib: Dict[Any, Dict[int, int]] = {
        v: {i: 1} for i, v in enumerate(core.invars)}
    for eqn in core.eqns:
        name = eqn.primitive.name
        if name == "add":
            c: Dict[int, int] = {}
            for x in eqn.invars:
                if isinstance(x, Literal):
                    return False
                for src, mult in contrib.get(x, {}).items():
                    c[src] = c.get(src, 0) + mult
            contrib[eqn.outvars[0]] = c
        elif name == "convert_element_type":
            x = eqn.invars[0]
            if isinstance(x, Literal):
                return False
            contrib[eqn.outvars[0]] = contrib.get(x, {})
        else:
            return False
    if len(core.outvars) != n_leaves:
        return False
    for li, ov in enumerate(core.outvars):
        if isinstance(ov, Literal):
            return False
        if ov.aval.shape != shapes[li][0] \
                or ov.aval.dtype != shapes[li][1]:
            return False
        want = {i * n_leaves + li: 1 for i in range(n_values)}
        if contrib.get(ov, {}) != want:
            return False
    return True


def _trace_reducer(fn, probes):
    """``(jaxpr, out_shape)`` of the user's reducer on ``probes``, or
    None when the reducer will not trace because it needs concrete
    values (``float(v)``, ``if v > 0``: JAX's tracer-conversion errors)
    — such a reducer is not a sum. Nothing else is caught: an
    ``AttributeError``/``ImportError``/plain ``TypeError`` is JAX API
    drift or a bug, and swallowing it once turned every psum fold into
    an all_gather without a word."""
    import jax
    try:
        jaxpr, out_shape = jax.make_jaxpr(fn, return_shape=True)(*probes)
    except jax.errors.JAXTypeError:
        return None
    return jaxpr.jaxpr, out_shape


def _singleton_passthrough(spec: TaskSpec, key, value_template) -> bool:
    """Is ``reducefn(key, [v])`` structurally the identity (modulo
    element-type conversions)?  The psum tier needs it: the collective
    produces the SUM, and the fold result is then threaded through one
    singleton reducefn call so the published value carries the user's
    own output structure (dict insertion order, conversions) — but
    only when that call provably adds nothing else."""
    import jax
    import numpy as np
    from jax.extend.core import Literal
    leaves, td = jax.tree.flatten(value_template)
    shapes = [(tuple(x.shape), x.dtype) for x in leaves]
    probe = jax.tree.unflatten(td, [np.zeros(s, d) for s, d in shapes])
    traced = _trace_reducer(lambda v: spec.reducefn(key, [v]), [probe])
    if traced is None:
        return False
    core, out_shape = traced
    if jax.tree.structure(out_shape) != td:
        return False
    alias = {v: i for i, v in enumerate(core.invars)}
    for eqn in core.eqns:
        if eqn.primitive.name != "convert_element_type":
            return False
        x = eqn.invars[0]
        if isinstance(x, Literal) or x not in alias:
            return False
        alias[eqn.outvars[0]] = alias[x]
    return [alias.get(ov) for ov in core.outvars] \
        == list(range(len(shapes)))


# --------------------------------------------------------------------------
# the engine
# --------------------------------------------------------------------------

class InGraphEngine:
    """Compile-once in-graph executor for one TaskSpec.

    Two lowering tiers, tried in order on the first iteration:

    - **shard_map** (the collective tier): jobs stacked on a leading
      axis sharded over the mesh's ``dp`` axis (parallel/mesh.py;
      padded to the axis size with replayed job-0 values that a
      device-index mask excludes from every fold); mapfn traced per
      device slot with traced key/value; per-key cross-device fold =
      psum for verified sum reducers, all_gather + the user fold
      otherwise. Requires numeric job keys and uniform job-value
      shapes.
    - **jit** (the unrolled tier): every job traced with its concrete
      key inside one jitted program — no mesh, XLA fuses. Handles
      string keys, per-job heterogeneous values, and key-dependent
      mapfns; still one compile, still zero per-record Python.

    ``traces`` counts outer-jit traces — the compile counter the
    no-retrace "loop" contract is asserted against (one per task as
    long as taskfn threads same-shaped state each iteration).
    """

    def __init__(self, spec: TaskSpec, mesh=None, axis: str = "dp"):
        self.spec = spec
        self.axis = axis
        self._mesh = mesh
        self.traces = 0
        self.mode: Optional[str] = None     # "shard_map" | "jit"
        # why the collective tier was refused, when it was tried and
        # the jit tier ran instead ("TypeError: ..."); the runner puts
        # it in the log and in a ``lowering.tier`` span
        self.collective_error: Optional[str] = None
        self._program: Optional[Callable] = None
        self._plan: Optional[_Plan] = None
        self._sig: Optional[tuple] = None

    # -- mesh ---------------------------------------------------------------

    def _ensure_mesh(self):
        if self._mesh is None:
            from lua_mapreduce_tpu.parallel.mesh import make_mesh
            self._mesh = make_mesh(mp=1)
        return self._mesh

    # -- public -------------------------------------------------------------

    def run_iteration(self, result_store) -> int:
        """One full map→shuffle→reduce computed in-graph; partition
        result files are published to ``result_store`` exactly as
        run_reduce_job would. Returns the number of result files.
        The caller owns iteration hygiene (delete_results) and the
        finalfn/"loop" protocol — taskfn runs HERE each iteration so
        threaded state (centroids, factors, weights) enters the
        compiled program as fresh arrays without retracing."""
        from lua_mapreduce_tpu.engine.local import collect_task_jobs
        jobs = collect_task_jobs(self.spec)
        if not jobs:
            return 0
        keys = [k for k, _ in jobs]
        prepped = []
        for i, (_, v) in enumerate(jobs):
            leaves, struct = _value_leaves(v, f"jobs[{i}].value")
            prepped.append((leaves, struct))
        if self._program is not None \
                and self._mode_sig(keys, prepped, self.mode) == self._sig:
            outputs = self._program(*self._flat_args(keys, prepped))
        else:
            outputs = self._build_and_run(keys, prepped)
        return self._publish(outputs, result_store)

    def _mode_sig(self, keys, prepped, mode) -> tuple:
        """The retrace signature, per tier: the jit tier bakes concrete
        key values (and per-key host indexing) into the program, so key
        values are part of its identity; on the collective tier keys
        ride as a TRACED argument — only their count and resolved dtype
        shape the program, and a loop emitting iteration-dependent
        numeric keys must not recompile every iteration."""
        structs = tuple(st for _, st in prepped)
        if mode == "shard_map":
            kind = "f" if any(isinstance(k, float) for k in keys) else "i"
            return ("shard_map", len(keys), kind, structs)
        return ("jit", tuple(keys), structs)

    # -- build --------------------------------------------------------------

    def _build_and_run(self, keys, prepped) -> tuple:
        self.collective_error = None
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
                self.collective_error = f"{type(e).__name__}: {e}"
                self.traces = 0             # aborted trace doesn't count
        try:
            return self._finish_build(
                *self._build_jit(keys, prepped), mode="jit",
                sig=self._mode_sig(keys, prepped, "jit"))
        except LoweringError:
            raise
        except Exception as e:              # noqa: BLE001
            hint = (f"; collective tier also failed: "
                    f"{self.collective_error}"
                    if self.collective_error is not None else "")
            raise LoweringUnsupported(
                f"in-graph lowering failed at trace time: "
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
        """[key array] + per-leaf [Jp, ...] stacks, padded to the mesh
        axis with job-0 replays (masked out of every fold)."""
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
        from jax import lax, shard_map
        from jax.sharding import PartitionSpec as P

        from lua_mapreduce_tpu.parallel.tpu_engine import _CROSS

        spec, axis = self.spec, self.axis
        mesh = self._ensure_mesh()
        n = mesh.shape[axis]
        J = len(keys)
        L = -(-J // n)
        struct = prepped[0][1]
        plan = _Plan(spec)

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
                        "the collective tier needs every job to emit "
                        "the same keys the same number of times")
            # membership mask over this device's slots (padding replays
            # job 0; its emissions must not reach any fold)
            mask = (lax.axis_index(axis) * L + jnp.arange(L)) < J
            out = collections.OrderedDict()
            for key, _m in sig0:
                per_slot = [g[key] for g in slot_groups]
                m = len(per_slot[0])
                stacked = [
                    jax.tree.map(lambda *xs: jnp.stack(xs),
                                 *[per_slot[i][vi] for i in range(L)])
                    for vi in range(m)]
                template = jax.tree.map(lambda x: x[0], stacked[0])
                total = J * m
                if spec.fast_path and total == 1:
                    # the merge fast path: singleton groups skip
                    # reducefn (job.lua:264-275) — J==1, so device 0's
                    # slot 0 holds the one value; broadcast it
                    g0 = jax.tree.map(
                        lambda x: lax.all_gather(x, axis, axis=0,
                                                 tiled=True), stacked[0])
                    out[key] = jax.tree.map(lambda x: x[0], g0)
                    plan.folds[key] = "gather"
                elif _sum_fold(spec, key, template, total) \
                        and _singleton_passthrough(spec, key, template):
                    def local_sum(*xs):
                        acc = None
                        for x in xs:
                            mm = mask.reshape((L,) + (1,) * (x.ndim - 1))
                            s = jnp.sum(
                                jnp.where(mm, x, jnp.zeros_like(x)),
                                axis=0)
                            acc = s if acc is None else acc + s
                        return acc
                    local = jax.tree.map(local_sum, *stacked)
                    summed = jax.tree.map(
                        lambda x: _CROSS["sum"](x, axis), local)
                    # one singleton reducefn pass (verified identity
                    # modulo dtype converts) restores the user's own
                    # output structure — dict insertion order must
                    # serialize exactly as on the store plane
                    out[key] = spec.reducefn(key, [summed])
                    plan.folds[key] = "psum"
                else:
                    gathered = [
                        jax.tree.map(
                            lambda x: lax.all_gather(x, axis, axis=0,
                                                     tiled=True), s)
                        for s in stacked]
                    # canonical store-plane value order: job-major
                    # (zero-padded run names sort numerically), emit
                    # order within a job
                    values = [jax.tree.map(lambda x: x[j], gathered[vi])
                              for j in range(J) for vi in range(m)]
                    if spec.fast_path and len(values) == 1:
                        out[key] = values[0]
                    else:
                        out[key] = spec.reducefn(key, values)
                    plan.folds[key] = "all_gather"
            return plan.finish(out)

        n_leaves = len(prepped[0][0])
        mapped = shard_map(per_shard, mesh=mesh,
                           in_specs=(P(axis),) * (1 + n_leaves),
                           out_specs=P(), check_vma=False)

        def program(karr, *leaves):
            self.traces += 1
            return mapped(karr, *leaves)

        program = jax.jit(program)
        outputs = program(*self._stacked_args(keys, prepped))
        return program, plan, outputs

    def _build_jit(self, keys, prepped):
        import jax

        spec = self.spec
        plan = _Plan(spec)
        structs = [st for _, st in prepped]
        counts = [len(leaves) for leaves, _ in prepped]

        def program(*flat):
            self.traces += 1
            groups: "collections.OrderedDict" = collections.OrderedDict()
            pos = 0
            for j, key in enumerate(keys):
                leaves = list(flat[pos:pos + counts[j]])
                pos += counts[j]
                value = _rebuild(structs[j], leaves)
                for k, vs in _run_map(spec, key, value).items():
                    groups.setdefault(k, []).extend(vs)
            out = collections.OrderedDict()
            for k, vs in groups.items():
                if spec.fast_path and len(vs) == 1:
                    out[k] = vs[0]
                else:
                    out[k] = spec.reducefn(k, vs)
                plan.folds[k] = "fused"
            return plan.finish(out)

        program = jax.jit(program)
        outputs = program(*[leaf for leaves, _ in prepped
                            for leaf in leaves])
        return program, plan, outputs

    # -- publish ------------------------------------------------------------

    def _publish(self, outputs, result_store) -> int:
        """Write per-partition result files from the fetched device
        results — same name, line format (``dump_record(key,
        [reduced])``), and canonical in-file key order as
        run_reduce_job, so the two planes' results are directly
        diffable."""
        import jax
        plan = self._plan
        ns = self.spec.result_ns
        reduced = plan.unflatten(jax.device_get(outputs))
        by_part: Dict[int, List[Any]] = {}
        for key in plan.keys:
            by_part.setdefault(plan.parts[key], []).append(key)
        for part in sorted(by_part):
            builder = result_store.builder()
            try:
                for key in sorted_keys(by_part[part]):
                    plain = to_plain(reduced[key])
                    assert_serializable(plain,
                                        f"reduce value for key {key!r}")
                    builder.write(dump_record(key, [plain]) + "\n")
                builder.build(f"{ns}.P{part}")
            finally:
                builder.close()
        return len(by_part)


# --------------------------------------------------------------------------
# engine-side iteration driver shared by LocalExecutor and Server
# --------------------------------------------------------------------------

class IngraphRunner:
    """The executors' shared in-graph iteration driver: owns the
    engine instance, the ``ingraph.run`` span, the counters, and the
    auto-vs-forced fallback policy — so LocalExecutor and Server
    cannot drift on any of them (the stats.COUNTER_FOLD discipline)."""

    def __init__(self, spec: TaskSpec, decision: EngineDecision,
                 mesh=None, log=None):
        self.decision = decision
        self.engine = InGraphEngine(spec, mesh=mesh) \
            if decision.chosen == "ingraph" else None
        self._log = log or (lambda msg: print(f"[ingraph] {msg}",
                                              file=sys.stderr))
        self._tier_reported = False
        record_lowering(decision)
        if decision.requested != "store" and decision.chosen == "store":
            self._log(f"store plane selected: {decision.reason}")

    @property
    def active(self) -> bool:
        return self.engine is not None

    def run_iteration(self, result_store, iteration: int) -> bool:
        """Try one in-graph iteration. True = results published (the
        caller skips the store-plane phases); False = degraded to the
        store plane (permanently — counted, logged, traced). Raises
        LoweringError under the forced ``engine="ingraph"`` hard
        mode."""
        from lua_mapreduce_tpu.faults.retry import COUNTERS
        if self.engine is None:
            return False
        tracer = active_tracer()
        try:
            if tracer is not None:
                with tracer.span("ingraph.run", ns="ingraph",
                                 job_id=iteration,
                                 mode=self.engine.mode or "build",
                                 traces=self.engine.traces):
                    self.engine.run_iteration(result_store)
            else:
                self.engine.run_iteration(result_store)
        except Exception as exc:            # noqa: BLE001 — policy point
            if self.decision.requested == "ingraph":
                if isinstance(exc, LoweringError):
                    raise
                raise LoweringError(
                    f"engine=ingraph (hard mode): {type(exc).__name__}: "
                    f"{exc}") from exc
            COUNTERS.bump("ingraph_fallbacks")
            reason = f"{type(exc).__name__}: {exc}"
            record_fallback(reason)
            self._log(f"iteration {iteration}: in-graph lowering failed "
                      f"({reason}); falling back to the store plane")
            self.engine = None
            return False
        COUNTERS.bump("ingraph_iterations")
        if self.engine.collective_error is not None \
                and not self._tier_reported:
            # the jit tier ran because the collective tier refused the
            # task: on a multi-device mesh that is the difference
            # between every device working and one, so say it once
            self._tier_reported = True
            record_tier(self.engine.mode, self.engine.collective_error)
            self._log(f"iteration {iteration}: {self.engine.mode} tier "
                      f"(collective tier refused: "
                      f"{self.engine.collective_error})")
        return True


def utest() -> None:
    """Self-test (host-only surface: knob resolution, oracle consult,
    decision logic — the compiled tiers are exercised under the
    cpu-pinned pytest conftest, tests/test_ingraph.py)."""
    import os
    import tempfile

    assert resolve_engine("AUTO") == "auto"
    assert resolve_engine("ingraph") == "ingraph"
    old = os.environ.get("LMR_ENGINE")
    try:
        os.environ["LMR_ENGINE"] = "store"
        assert resolve_engine(None) == "store"
        os.environ.pop("LMR_ENGINE")
        assert resolve_engine(None) == "auto"
    finally:
        if old is not None:
            os.environ["LMR_ENGINE"] = old
    try:
        resolve_engine("gpu")
    except ValueError:
        pass
    else:  # pragma: no cover
        raise AssertionError("bogus engine must be rejected")

    # oracle consult + decision over a real (temp) in-graph module
    good = (
        "def taskfn(emit):\n"
        "    for j in range(4):\n"
        "        emit(j, j)\n"
        "def mapfn(key, value, emit):\n"
        "    emit(0, value * value)\n"
        "def partitionfn(key):\n"
        "    return int(key) % 2\n"
        "def reducefn(key, values):\n"
        "    return sum(values)\n"
    )
    with tempfile.TemporaryDirectory() as d:
        path = os.path.join(d, "ig_utest_task.py")
        with open(path, "w") as f:
            f.write(good)
        import importlib.util
        spec_ = importlib.util.spec_from_file_location("ig_utest_task",
                                                       path)
        mod = importlib.util.module_from_spec(spec_)
        spec_.loader.exec_module(mod)
        sys.modules["ig_utest_task"] = mod
        old_path = list(sys.path)
        sys.path.insert(0, d)
        try:
            tspec = TaskSpec(taskfn="ig_utest_task", mapfn="ig_utest_task",
                             partitionfn="ig_utest_task",
                             reducefn="ig_utest_task")
            dec = select_engine(tspec, "auto")
            assert dec.chosen == "ingraph" and dec.verdict == "in-graph", dec
            assert select_engine(tspec, "store").chosen == "store"
            forced = select_engine(tspec, "ingraph")
            assert forced.chosen == "ingraph" and forced.requested == "ingraph"
        finally:
            sys.path[:] = old_path
            del sys.modules["ig_utest_task"]

    # non-module specs degrade to store under auto, with a reason
    dec = select_engine(TaskSpec(
        taskfn={"taskfn": lambda e: e(0, 1)},
        mapfn={"mapfn": lambda k, v, e: e(k, v)},
        partitionfn={"partitionfn": lambda k: 0},
        reducefn={"reducefn": lambda k, vs: sum(vs)}), "auto")
    assert dec.chosen == "store"
    assert "not statically checkable" in dec.reason or dec.verdict

    # _value_leaves round-trip + rejection
    leaves, st = _value_leaves({"a": [1, 2], "b": 3.5})
    assert len(leaves) == 2
    rebuilt = _rebuild(st, list(leaves))
    assert sorted(rebuilt) == ["a", "b"]
    try:
        _value_leaves({"a": "text"})
    except LoweringUnsupported:
        pass
    else:  # pragma: no cover
        raise AssertionError("string job values must be refused")
