"""Worker launcher.

Analog of reference execute_worker.lua:1-11:

    python -m lua_mapreduce_tpu.cli.execute_worker COORD_DIR \\
        [--max-iter N] [--max-sleep S] [--max-tasks N] [--verbose]

Workers are leader-agnostic: they talk to the job store, never to a
coordinator process, so an HA leader takeover (execute_server --ha,
docs/DESIGN.md §31) is invisible here — no flag, no reconnect, no
restart. In-flight claims survive the takeover and commit normally.
"""

from __future__ import annotations

import argparse


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="execute_worker",
        description="Run one elastic MapReduce worker.")
    p.add_argument("coord", help="shared job-store directory")
    p.add_argument("--max-iter", type=int, default=20)
    p.add_argument("--max-sleep", type=float, default=20.0)
    p.add_argument("--max-tasks", type=int, default=1)
    p.add_argument("--name")
    p.add_argument("--max-jobs", type=int, default=None,
                   help="leave after executing N jobs (bounded lifetime "
                        "for churned elastic pools)")
    p.add_argument("--batch-k", type=int, default=None,
                   help="claim up to K jobs per control-plane round trip "
                        "(batch lease); default follows the task "
                        "document's server-deployed batch_k")
    p.add_argument("--segment-format", choices=("v1", "v2"), default=None,
                   help="spill encoding THIS worker writes (default: "
                        "follow the task document's fleet default); pin "
                        "v1 on hosts that must stay text-only during a "
                        "rollout — readers sniff per file either way")
    p.add_argument("--replication", type=int, default=None,
                   help="shuffle replication factor THIS worker publishes "
                        "and reads with (default: follow the task "
                        "document's fleet default — DESIGN §20)")
    p.add_argument("--coding", type=str, default=None, metavar="K+M",
                   help="erasure-coding spec 'k+m' THIS worker publishes "
                        "and reads with (default: follow the task "
                        "document's deployed value — DESIGN §27)")
    p.add_argument("--idle-poll-ms", type=float, default=None,
                   help="idle-poll CAP in ms (lmr-sched, DESIGN §23): "
                        "the longest an idle worker waits between "
                        "claim-surface scans. Waits are capped jittered "
                        "backoff that the store's wakeup channel "
                        "interrupts, so this bounds only the "
                        "lost-notification fallback latency (default: "
                        "LMR_IDLE_POLL_MS, else --max-sleep; "
                        "LMR_SCHED_NOTIFY=0 disables wakeups entirely)")
    p.add_argument("--push", action="store_true", default=None,
                   help="push-based streaming shuffle for THIS worker "
                        "(docs/DESIGN.md §24; default: follow the task "
                        "document's fleet default — which LMR_PUSH=1 "
                        "round-trips to subprocess fleets): map output "
                        "lands as manifest-gated JSEG inbox frames "
                        "instead of staged run files")
    p.add_argument("--push-budget-mb", type=float, default=None,
                   help="push buffer-pool memory budget in MB (default "
                        "64, or LMR_PUSH_BUDGET_MB): over-budget "
                        "partitions evict to the staged spill path "
                        "instead of OOMing (counted push_evictions)")
    p.add_argument("--engine",
                   choices=("auto", "ingraph", "hybrid", "store"),
                   default=None,
                   help="execution engine (docs/DESIGN.md §26/§28) — "
                        "fleet-launcher parity: in-graph iterations run "
                        "ON THE SERVER (this worker simply sees no jobs "
                        "for them), and the hybrid plane's compiled "
                        "map/reduce legs follow the task document's "
                        "server-negotiated per-stage split regardless "
                        "of this flag; it validates and exports "
                        "LMR_ENGINE (the standalone-worker fallback "
                        "when a doc predates the negotiation, and the "
                        "knob for any LocalExecutor the user task "
                        "spawns in-process), so a launcher can pass "
                        "one uniform --engine to every process")
    p.add_argument("--phases", default="map,reduce",
                   help="comma list of phases this worker claims "
                        "(heterogeneous pools: dedicated mapper hosts "
                        "pass 'map', reducer hosts 'reduce')")
    p.add_argument("--store-retries", type=int, default=None,
                   help="transient store/coord fault retry budget per op "
                        "(default 3, or LMR_STORE_RETRIES; 0 disables "
                        "the retry layer — DESIGN §19)")
    p.add_argument("--retry-base-ms", type=float, default=None,
                   help="decorrelated-jitter backoff base in ms "
                        "(default 25, or LMR_RETRY_BASE_MS)")
    p.add_argument("--autotune-fleet", type=int, default=None, metavar="N",
                   help="elastic pool mode (docs/DESIGN.md §29): run N "
                        "baseline worker threads in this process and "
                        "follow the task document's controller-written "
                        "fleet_target — the pool grows toward the target "
                        "and retires surplus members gracefully (a "
                        "retiring member stops claiming and exits after "
                        "its current lease commits, so no lease is ever "
                        "lost to a scale-down)")
    p.add_argument("--autotune-max-workers", type=int, default=8,
                   help="elastic ceiling for --autotune-fleet (raised to "
                        "the baseline if smaller)")
    p.add_argument("--trace", action="store_true",
                   help="lmr-trace (docs/DESIGN.md §22): record this "
                        "worker's claim/body/publish/commit spans, "
                        "flushed into the task storage as _trace.* "
                        "files (also enabled fleet-wide via LMR_TRACE=1)")
    p.add_argument("--profile", metavar="DIR", default=None,
                   help="wrap execute() in utils/profiling.device_trace "
                        "(JAX/XLA profile into DIR — today only "
                        "train_lm had this). With --trace, span names "
                        "are bridged into the device profile")
    p.add_argument("--verbose", action="store_true")
    return p


def main(argv=None) -> int:
    args = build_parser().parse_args(argv)

    # the platform is JAX's choice (JAX_PLATFORMS); this only says where
    # compiled programs are kept, before anything compiles
    from lua_mapreduce_tpu.utils.jax_env import place_compile_cache
    place_compile_cache()

    from lua_mapreduce_tpu.coord.filestore import FileJobStore
    from lua_mapreduce_tpu.engine.worker import Worker
    from lua_mapreduce_tpu.faults.retry import configure_retry

    if args.store_retries is not None or args.retry_base_ms is not None:
        configure_retry(args.store_retries, args.retry_base_ms)
    if args.trace:
        from lua_mapreduce_tpu.trace.span import Tracer, install_tracer
        install_tracer(Tracer(annotate=bool(args.profile)))
    if args.engine is not None:
        import os
        from lua_mapreduce_tpu.engine.ingraph import resolve_engine
        os.environ["LMR_ENGINE"] = resolve_engine(args.engine)
    phases = tuple(s.strip() for s in args.phases.split(",") if s.strip())
    for ph in phases:
        if ph not in ("map", "reduce"):
            raise SystemExit(f"--phases: unknown phase {ph!r}")
    store = FileJobStore(args.coord)

    def mint(name):
        w = Worker(store, name=name, verbose=args.verbose).configure(
            max_iter=args.max_iter, max_sleep=args.max_sleep,
            max_tasks=args.max_tasks, phases=phases, max_jobs=args.max_jobs)
        if args.batch_k is not None:
            w.configure(batch_k=args.batch_k)
        if args.idle_poll_ms is not None:
            w.configure(idle_poll_ms=args.idle_poll_ms)
        if args.segment_format is not None:
            w.configure(segment_format=args.segment_format)
        if args.replication is not None:
            w.configure(replication=args.replication)
        if args.coding is not None:
            w.configure(coding=args.coding)
        if args.push is not None:
            w.configure(push=args.push)
        if args.push_budget_mb is not None:
            w.configure(push_budget_mb=args.push_budget_mb)
        return w

    import contextlib
    profile_ctx = contextlib.nullcontext()
    if args.profile:
        from lua_mapreduce_tpu.utils.profiling import device_trace
        profile_ctx = device_trace(args.profile)
    if args.autotune_fleet:
        # elastic pool mode (DESIGN §29): thread members share this
        # process's store handle; the supervisor loop follows the task
        # doc's fleet_target (written by the server's controller) and
        # runs until every member's own lifetime bounds retire it
        import threading
        import time
        from lua_mapreduce_tpu.sched.controller import FleetSupervisor

        threads = {}

        def spawn(seq):
            w = mint(f"{args.name or 'elastic'}-{seq}")
            t = threading.Thread(target=w.execute, daemon=True)
            threads[id(w)] = t
            t.start()
            return w

        cap = max(args.autotune_fleet, args.autotune_max_workers)
        sup = FleetSupervisor(
            spawn, retire=lambda w: w.configure(max_jobs=0),
            baseline=args.autotune_fleet, cap=cap)
        with profile_ctx:
            sup.ensure_baseline()
            while any(t.is_alive() for t in threads.values()):
                task = store.get_task() or {}
                if task.get("autotune") and task.get("fleet_target"):
                    sup.resize(int(task["fleet_target"]))
                time.sleep(0.2)
    else:
        worker = mint(args.name)
        with profile_ctx:
            worker.execute()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
