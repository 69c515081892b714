"""lua_mapreduce_tpu — a TPU-native MapReduce framework.

A brand-new framework with the capabilities of pakozm/lua-mapreduce
(reference: /root/reference, see SURVEY.md): a fault-tolerant, iterative
MapReduce engine with six pluggable user functions, an elastic worker pool,
pluggable intermediate storage, and a data-parallel training harness —
re-designed TPU-first:

- map phases compile to pjit-sharded computations over a ``jax.sharding.Mesh``
- combiners/reducers lower to ``psum`` / ``reduce_scatter`` / ``all_to_all``
  collectives over ICI instead of a shuffle through a database
- intermediate data lives in host DRAM with shared-dir / object-store spill
- a single-controller coordinator owns job state, fault tolerance and
  checkpoint/resume, entirely off the jitted hot path

Public API (parity with reference mapreduce/init.lua:25-38):
    server, worker, utils, tuples (interned tuples), persistent_table, utest
"""

from lua_mapreduce_tpu.core import tuples
from lua_mapreduce_tpu.engine.contract import TaskSpec
from lua_mapreduce_tpu.engine.local import LocalExecutor

__version__ = "0.1.0"

_LAZY = {
    "Server": ("lua_mapreduce_tpu.engine.server", "Server"),
    "PhaseFailed": ("lua_mapreduce_tpu.engine.server", "PhaseFailed"),
    "Worker": ("lua_mapreduce_tpu.engine.worker", "Worker"),
    "MemJobStore": ("lua_mapreduce_tpu.coord.jobstore", "MemJobStore"),
    "FileJobStore": ("lua_mapreduce_tpu.coord.filestore", "FileJobStore"),
    "PersistentTable": ("lua_mapreduce_tpu.coord.persistent_table",
                        "PersistentTable"),
    # fault subsystem (DESIGN §19)
    "StoreError": ("lua_mapreduce_tpu.faults.errors", "StoreError"),
    "TransientStoreError": ("lua_mapreduce_tpu.faults.errors",
                            "TransientStoreError"),
    "PermanentStoreError": ("lua_mapreduce_tpu.faults.errors",
                            "PermanentStoreError"),
    "RetryPolicy": ("lua_mapreduce_tpu.faults.retry", "RetryPolicy"),
    "FaultPlan": ("lua_mapreduce_tpu.faults.plan", "FaultPlan"),
    # in-graph engine (DESIGN §26)
    "InGraphEngine": ("lua_mapreduce_tpu.engine.ingraph", "InGraphEngine"),
    "LoweringError": ("lua_mapreduce_tpu.engine.ingraph", "LoweringError"),
    # lmr-trace (DESIGN §22)
    "Tracer": ("lua_mapreduce_tpu.trace.span", "Tracer"),
    "TraceCollection": ("lua_mapreduce_tpu.trace.collect",
                        "TraceCollection"),
    # lmr-sched (DESIGN §23)
    "Tenant": ("lua_mapreduce_tpu.sched.tenancy", "Tenant"),
    "TenantView": ("lua_mapreduce_tpu.sched.tenancy", "TenantView"),
    "FairWorker": ("lua_mapreduce_tpu.sched.tenancy", "FairWorker"),
    "FairScheduler": ("lua_mapreduce_tpu.sched.tenancy", "FairScheduler"),
    "AdmissionError": ("lua_mapreduce_tpu.sched.tenancy",
                       "AdmissionError"),
    "Waiter": ("lua_mapreduce_tpu.sched.waiter", "Waiter"),
    # lmr-ha (DESIGN §31)
    "LeaderLease": ("lua_mapreduce_tpu.sched.lease", "LeaderLease"),
    "FencedJobStore": ("lua_mapreduce_tpu.sched.lease", "FencedJobStore"),
    "StaleLeaderError": ("lua_mapreduce_tpu.faults.errors",
                         "StaleLeaderError"),
}


def __getattr__(name):
    """Lazy exports — the distributed engine pulls in the coordinator; the
    contract/local layers stay importable on their own."""
    try:
        modname, attr = _LAZY[name]
    except KeyError:
        raise AttributeError(name) from None
    import importlib
    return getattr(importlib.import_module(modname), attr)

__all__ = [
    "TaskSpec",
    "LocalExecutor",
    "Server",
    "PhaseFailed",
    "Worker",
    "MemJobStore",
    "FileJobStore",
    "PersistentTable",
    "InGraphEngine",
    "LoweringError",
    "StoreError",
    "TransientStoreError",
    "PermanentStoreError",
    "RetryPolicy",
    "FaultPlan",
    "Tracer",
    "TraceCollection",
    "Tenant",
    "TenantView",
    "FairWorker",
    "FairScheduler",
    "AdmissionError",
    "Waiter",
    "LeaderLease",
    "FencedJobStore",
    "StaleLeaderError",
    "tuples",
    "utest",
]


def utest():
    """Run every module's self-test (reference mapreduce/test.lua:30-39)."""
    from lua_mapreduce_tpu import analysis, faults, sched, trace
    from lua_mapreduce_tpu.core import heap, merge, segment, serialize
    from lua_mapreduce_tpu.coord import jobstore, persistent_table
    from lua_mapreduce_tpu.engine import (contract, ingraph, placement,
                                          premerge, push, server, worker)
    from lua_mapreduce_tpu.store import memfs, router
    from lua_mapreduce_tpu.utils import lockcheck, stats

    # host-path modules ONLY: the sweep runs in the ambient env (test.sh)
    # and must not take an accelerator that another process may hold;
    # jax-computing modules (ops/*) self-test under the cpu-pinned
    # pytest conftest instead (tests/test_q8.py etc.)
    # ingraph's utest is host-only by design (knob resolution + the
    # static oracle consult); its compiled tiers live in
    # tests/test_ingraph.py under the cpu-pinned conftest
    for mod in (tuples, heap, serialize, segment, merge, jobstore, memfs,
                contract, router, persistent_table, stats, placement,
                premerge, push, worker, server, ingraph, analysis, faults,
                trace, sched, lockcheck):
        if hasattr(mod, "utest"):
            mod.utest()
