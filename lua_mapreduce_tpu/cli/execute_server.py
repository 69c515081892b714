"""Server launcher.

Analog of reference execute_server.lua:1-62 with the same positional
contract: coordination spec, then the user-function module names, then
storage. ``/``-paths are normalized to dotted module names
(execute_server.lua:37-39).

    python -m lua_mapreduce_tpu.cli.execute_server \\
        COORD_DIR TASKFN MAPFN PARTITIONFN REDUCEFN \\
        [--combinerfn M] [--finalfn M] [--storage SPEC] \\
        [--result-ns NS] [--init-arg K=V ...]

COORD_DIR is the shared job-store directory (the connection-string analog);
"mem" runs an in-process pool with --inline-workers N.
"""

from __future__ import annotations

import argparse
import sys
import threading


def normalize_module(name: str) -> str:
    """a/b/c.py or a/b/c → a.b.c (execute_server.lua:37-39)."""
    if name.endswith(".py"):
        name = name[:-3]
    return name.strip("/").replace("/", ".")


def parse_init_args(pairs) -> dict:
    out = {}
    for pair in pairs or ():
        k, sep, v = pair.partition("=")
        if not sep:
            raise SystemExit(f"--init-arg needs K=V, got {pair!r}")
        out[k] = v
    return out


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="execute_server",
        description="Run the MapReduce server (orchestrator).")
    p.add_argument("coord", help="shared job-store directory, or 'mem'")
    p.add_argument("taskfn")
    p.add_argument("mapfn")
    p.add_argument("partitionfn")
    p.add_argument("reducefn")
    p.add_argument("--combinerfn")
    p.add_argument("--finalfn")
    p.add_argument("--storage", default=None,
                   help="backend[:path] — mem:TAG | shared:DIR | object:DIR "
                        "(default: mem:cli for an in-process pool, "
                        "shared:<COORD>/spill for a shared-dir pool)")
    p.add_argument("--result-ns", default="result")
    p.add_argument("--init-arg", action="append", metavar="K=V")
    p.add_argument("--inline-workers", type=int, default=0,
                   help="run N worker threads in this process")
    p.add_argument("--idle-poll-ms", type=float, default=None,
                   help="idle-poll CAP in ms for the inline workers "
                        "(lmr-sched, DESIGN §23): bounds the "
                        "lost-notification fallback latency; wakeup "
                        "channels interrupt waits long before it "
                        "(default: LMR_IDLE_POLL_MS, else the worker "
                        "max_sleep; LMR_SCHED_NOTIFY=0 disables "
                        "wakeups fleet-wide)")
    p.add_argument("--poll", type=float, default=0.1)
    p.add_argument("--stale-timeout", type=float, default=600.0,
                   help="requeue RUNNING jobs of silently-dead workers "
                        "after this many seconds (0 disables)")
    p.add_argument("--strict", action="store_true",
                   help="abort with PhaseFailed when any job goes FAILED "
                        "instead of running finalfn on partial results")
    p.add_argument("--pipeline", action="store_true",
                   help="pipelined shuffle: publish eager pre_merge jobs "
                        "while mappers run (byte-identical results, less "
                        "reduce fan-in; see docs/DESIGN.md §15)")
    p.add_argument("--premerge-min-runs", type=int, default=4,
                   help="min committed runs one pre_merge consolidates")
    p.add_argument("--premerge-max-runs", type=int, default=8,
                   help="max runs per pre_merge job")
    p.add_argument("--batch-k", type=int, default=1,
                   help="fleet default claim-lease size, written to the "
                        "task doc: workers claim up to K jobs per "
                        "control-plane round trip and commit them in one "
                        "batch (many-small-jobs amortization; workers "
                        "still shrink long-job leases to 1 adaptively)")
    p.add_argument("--segment-format", choices=("v1", "v2"), default="v1",
                   help="intermediate spill encoding, written to the task "
                        "doc as the fleet default: v1 = JSON text lines, "
                        "v2 = framed binary segments (block-compressed, "
                        "CRC-guarded, ranged reads; docs/DESIGN.md §17). "
                        "Readers sniff per file, final results stay v1")
    p.add_argument("--store-retries", type=int, default=None,
                   help="transient store/coord fault retry budget per op "
                        "(default 3, or LMR_STORE_RETRIES; 0 disables "
                        "the retry layer — DESIGN §19)")
    p.add_argument("--retry-base-ms", type=float, default=None,
                   help="decorrelated-jitter backoff base in ms "
                        "(default 25, or LMR_RETRY_BASE_MS)")
    p.add_argument("--replication", type=int, default=None,
                   help="shuffle replication factor r, written to the "
                        "task doc as the fleet default (default 1, or "
                        "LMR_REPLICATION): each spill publishes r copies "
                        "on distinct placement targets, readers fail over "
                        "to any survivor, and the scavenger reconstructs "
                        "lost copies instead of re-running map jobs — "
                        "docs/DESIGN.md §20. r=1 is byte-identical to "
                        "the unreplicated path")
    p.add_argument("--coding", type=str, default=None, metavar="K+M",
                   help="erasure-coded shuffle spec 'k+m' (e.g. 4+1), "
                        "written to the task doc as the fleet default "
                        "(or LMR_CODING): each spill stripes into k data "
                        "+ m Reed-Solomon parity blocks on distinct "
                        "placement targets, any m losses decode inline, "
                        "at (k+m)/k write amplification instead of "
                        "replication's r — docs/DESIGN.md §27. Mutually "
                        "exclusive with --replication")
    p.add_argument("--speculation-factor", type=float, default=None,
                   help="straggler factor (default 0 = off, or "
                        "LMR_SPECULATION): a RUNNING job older than "
                        "FACTOR x the fleet per-namespace duration EWMA "
                        "gets a speculative duplicate lease; idle "
                        "workers race it and the first commit wins — "
                        "the loser degrades to a zero-repetition no-op "
                        "(docs/DESIGN.md §21)")
    p.add_argument("--speculation-cap", type=int, default=2,
                   help="max live speculative clones per namespace "
                        "(bounds wasted duplicate work)")
    p.add_argument("--push", action="store_true", default=None,
                   help="push-based streaming shuffle (docs/DESIGN.md "
                        "§24), written to the task doc as the fleet "
                        "default: maps push JSEG frames into "
                        "per-partition reducer inboxes as they fill, "
                        "gated by per-map manifests; the reduce side "
                        "merges them incrementally behind the map "
                        "phase. Default off, or LMR_PUSH=1 (the "
                        "subprocess-fleet round-trip); byte-identical "
                        "output either way")
    p.add_argument("--push-budget-mb", type=float, default=None,
                   help="push buffer-pool memory budget in MB for the "
                        "inline workers (default 64, or "
                        "LMR_PUSH_BUDGET_MB): over-budget partitions "
                        "evict to the staged spill path — graceful "
                        "degradation instead of OOM (counted "
                        "push_evictions)")
    p.add_argument("--engine",
                   choices=("auto", "ingraph", "hybrid", "store"),
                   default=None,
                   help="execution engine (docs/DESIGN.md §26/§28; "
                        "default auto, or LMR_ENGINE): 'auto' consults "
                        "the static lowerability oracle at task load "
                        "and compiles in-graph-verdicted tasks to ONE "
                        "jitted shard_map program running on this "
                        "server (no jobs dispatched); tasks with only "
                        "SOME in-graph stages take the hybrid rung — "
                        "qualifying map/reduce legs compile on the "
                        "workers, the rest stays interpreted; pure "
                        "store-plane tasks fall back entirely. Every "
                        "decision is logged and traced ('lowering' + "
                        "per-stage 'lowering.<stage>' spans). 'ingraph' "
                        "forces the whole-task plane and RAISES on any "
                        "lowering failure (the CI hard mode); 'hybrid' "
                        "forces stage-granular lowering and NEVER "
                        "raises (unqualified legs degrade with counted "
                        "evidence); 'store' opts out. Written to the "
                        "task doc (with the per-stage split) and "
                        "sticky on resume")
    p.add_argument("--autotune", action="store_true", default=None,
                   help="self-tuning controller (docs/DESIGN.md §29; "
                        "default off, or LMR_AUTOTUNE=1): the server's "
                        "housekeeping tick reads the live stats/trace "
                        "stream and adapts batch_k, the push buffer "
                        "budget, the speculation factor, the retry "
                        "backoff base, and (with --inline-workers) the "
                        "worker-pool size — every change deployed "
                        "through the task doc with an autotune.<knob> "
                        "evidence span, hysteresis-banded and "
                        "cooldown/flip-lockout gated so knobs never "
                        "oscillate")
    p.add_argument("--autotune-max-workers", type=int, default=None,
                   help="elastic ceiling for the --inline-workers pool "
                        "under --autotune (default: the controller's "
                        "fleet cap, clamped by tenant admission quotas "
                        "when a fair-scheduling config is active)")
    p.add_argument("--ha", action="store_true", default=None,
                   help="highly-available coordination (docs/DESIGN.md "
                        "§31; default off, or LMR_HA=1): contend for "
                        "the epoch-fenced leader lease on the job "
                        "store's persistent table before orchestrating. "
                        "Losers hot-standby on the 'leader' wakeup "
                        "topic and take over MID-PHASE through the "
                        "resume matrix when the lease expires; every "
                        "server-side mutation is epoch-fenced, so a "
                        "paused-and-resumed zombie leader gets "
                        "StaleLeaderError instead of corrupting state. "
                        "Workers need no flag — they are "
                        "leader-agnostic")
    p.add_argument("--lease-ttl-s", type=float, default=None,
                   help="leader lease TTL in seconds (default 10, or "
                        "LMR_LEASE_TTL_S): renewed every TTL/3; a "
                        "standby takes over after the last renewal "
                        "ages past TTL. Lower = faster failover, more "
                        "control-plane CAS traffic")
    p.add_argument("--trace", action="store_true",
                   help="lmr-trace (docs/DESIGN.md §22): record "
                        "claim/body/publish/commit spans and per-op "
                        "latencies, flushed into the task storage as "
                        "_trace.* files; inspect with 'python -m "
                        "lua_mapreduce_tpu.trace STORAGE'. Subprocess "
                        "workers enable theirs via LMR_TRACE=1")
    p.add_argument("--profile", metavar="DIR", default=None,
                   help="wrap the run in utils/profiling.device_trace "
                        "(JAX/XLA profile into DIR, TensorBoard-"
                        "loadable). With --trace, span names are "
                        "bridged into the device profile so host and "
                        "TPU timelines correlate")
    p.add_argument("--quiet", action="store_true")
    return p


def main(argv=None) -> int:
    args = build_parser().parse_args(argv)

    # the platform is JAX's choice (JAX_PLATFORMS); this only says where
    # compiled programs are kept, before anything compiles
    from lua_mapreduce_tpu.utils.jax_env import place_compile_cache
    place_compile_cache()

    from lua_mapreduce_tpu.coord.filestore import FileJobStore
    from lua_mapreduce_tpu.coord.jobstore import MemJobStore
    from lua_mapreduce_tpu.engine.contract import TaskSpec
    from lua_mapreduce_tpu.engine.server import Server
    from lua_mapreduce_tpu.engine.worker import Worker
    from lua_mapreduce_tpu.faults.retry import configure_retry

    if args.store_retries is not None or args.retry_base_ms is not None:
        configure_retry(args.store_retries, args.retry_base_ms)
    if args.trace:
        from lua_mapreduce_tpu.trace.span import Tracer, install_tracer
        install_tracer(Tracer(annotate=bool(args.profile)))

    import os as _os
    storage = args.storage or (
        "mem:cli" if args.coord == "mem"
        else f"shared:{_os.path.join(args.coord, 'spill')}")

    spec = TaskSpec(
        taskfn=normalize_module(args.taskfn),
        mapfn=normalize_module(args.mapfn),
        partitionfn=normalize_module(args.partitionfn),
        reducefn=normalize_module(args.reducefn),
        combinerfn=normalize_module(args.combinerfn) if args.combinerfn else None,
        finalfn=normalize_module(args.finalfn) if args.finalfn else None,
        init_args=parse_init_args(args.init_arg),
        storage=storage,
        result_ns=args.result_ns,
    )

    store = MemJobStore() if args.coord == "mem" else FileJobStore(args.coord)
    server = Server(store, poll_interval=args.poll,
                    stale_timeout_s=args.stale_timeout or None,
                    verbose=not args.quiet,
                    strict=args.strict,
                    pipeline=args.pipeline,
                    premerge_min_runs=args.premerge_min_runs,
                    premerge_max_runs=args.premerge_max_runs,
                    batch_k=args.batch_k,
                    segment_format=args.segment_format,
                    replication=args.replication,
                    coding=args.coding,
                    speculation=args.speculation_factor,
                    speculation_cap=args.speculation_cap,
                    push=args.push,
                    engine=args.engine,
                    autotune=args.autotune,
                    ha=args.ha,
                    lease_ttl_s=args.lease_ttl_s).configure(spec)

    def spawn_worker(_seq: int):
        w = Worker(store).configure(max_iter=10_000)
        if args.idle_poll_ms is not None:
            w.configure(idle_poll_ms=args.idle_poll_ms)
        if args.push_budget_mb is not None:
            w.configure(push_budget_mb=args.push_budget_mb)
        threading.Thread(target=w.execute, daemon=True).start()
        return w

    if args.inline_workers:
        if server.autotune:
            # elastic inline pool (DESIGN §29): the controller's fleet
            # knob resizes through a FleetSupervisor — retire clamps
            # max_jobs to 0, so the member leaves AFTER its current
            # poll settles (no lease is ever abandoned)
            from lua_mapreduce_tpu.sched.controller import FleetSupervisor
            cap = args.autotune_max_workers or max(args.inline_workers, 8)
            sup = FleetSupervisor(
                spawn_worker, retire=lambda w: w.configure(max_jobs=0),
                baseline=args.inline_workers, cap=cap)
            sup.ensure_baseline()
            server.set_fleet(sup.resize, size=args.inline_workers,
                             max_workers=cap)
        else:
            for i in range(args.inline_workers):
                spawn_worker(i)

    def report(phase: str, frac: float) -> None:
        if not args.quiet:
            print(f"\r[{phase}] {100 * frac:5.1f}%", end="", file=sys.stderr)
            if frac >= 1:
                print(file=sys.stderr)

    import contextlib
    profile_ctx = contextlib.nullcontext()
    if args.profile:
        from lua_mapreduce_tpu.utils.profiling import device_trace
        profile_ctx = device_trace(args.profile)
    with profile_ctx:
        stats = server.loop(progress=report)
    last = stats.last
    if not args.quiet and last is not None:
        print(f"cluster_time={last.cluster_time:.2f}s "
              f"wall={stats.wall_time:.2f}s "
              f"map(sum cpu/real)={last.map.sum_cpu_time:.2f}/"
              f"{last.map.sum_real_time:.2f}s "
              f"reduce(sum cpu/real)={last.reduce.sum_cpu_time:.2f}/"
              f"{last.reduce.sum_real_time:.2f}s "
              f"failed={last.map.failed}/{last.reduce.failed}",
              file=sys.stderr)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
