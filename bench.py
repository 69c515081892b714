"""Flagship benchmark. Prints ONE JSON line:
{"metric", "value", "unit", "vs_baseline", ...detail fields}.

Headline on a real chip (VERDICT r4 weak-1): the llama-style LM
training step's MFU (flash attention + RoPE/RMS/SwiGLU/GQA, fused grad
all-reduce, optimizer — the realest workload the framework trains),
with ``vs_baseline`` = measured MFU / the BASELINE.md ≥50% north star.

Also reported every run: the BASELINE.json digits workload (the
reference's APRIL-ANN digits MLP, 256→128 tanh→10 log_softmax, trained
with synchronous data-parallel SGD), as images/sec/chip plus
``digits_native_vs_mapreduce_path`` — the reference publishes no number
for its NN-training example (BASELINE.md: "published is empty"), so the
comparison is architectural: the identical workload through the
six-function MapReduce engine (map = grad shards, shuffle by parameter
name, reduce = grad sum, finalfn = optimizer step —
examples/digits/mr_train.py, the faithful re-expression of
examples/APRIL-ANN/common.lua) vs the TPU-native zero-coordination hot
loop.

Runs on a TPU only: without one it refuses to start, and it exits
non-zero when the LM train step fails. ROADMAP A1 replaces this script
with a table of cells.
"""

from __future__ import annotations

import json
import time

import numpy as np


def bench_tpu_native(steps: int = 100, batch: int = 8192) -> float:
    """Images/sec/chip of the jitted DP train step on real devices."""
    import jax

    from lua_mapreduce_tpu.models.mlp import init_mlp, nll_loss
    from lua_mapreduce_tpu.parallel.mesh import make_mesh
    from lua_mapreduce_tpu.train.data import make_digits
    from lua_mapreduce_tpu.train.harness import DataParallelTrainer, TrainConfig

    devices = jax.devices()
    n_chips = len(devices)
    mesh = make_mesh(dp=n_chips, mp=1, devices=devices)

    x_tr, y_tr, _, _ = make_digits(seed=0, n_train=batch * 2)
    params = init_mlp(jax.random.PRNGKey(0))
    tr = DataParallelTrainer(nll_loss, params, mesh,
                             TrainConfig(batch_size=batch))

    # the hot loop is lax.scan over batches inside ONE jitted call
    # (zero host round-trips per step — the BASELINE.md north star);
    # stepping one batch at a time would measure dispatch latency instead
    rng = np.random.RandomState(0)
    n = batch * steps
    idx = rng.randint(0, len(x_tr), n)
    xs = x_tr[idx].reshape(steps, batch, -1)
    ys = y_tr[idx].reshape(steps, batch)

    xs_d, ys_d = tr._shard_batch(xs, ys, batched=True)
    # h2d of both shards is forced to finish by the warm-up call below,
    # which consumes them before the timed window opens
    # warm up on the SAME shapes as the timed call — the scan length is
    # baked into the trace, so a different-length warmup would leave a
    # full XLA recompile inside the timed window
    p, o, losses = tr._epoch(tr.params, tr.opt_state, xs_d, ys_d)
    np.asarray(losses)
    tr.params, tr.opt_state = p, o
    from lua_mapreduce_tpu.utils.roofline import best_time

    def rep():
        # completion forced by the d2h fetch inside (see roofline.best_time)
        p, o, losses = tr._epoch(tr.params, tr.opt_state, xs_d, ys_d)
        np.asarray(losses)
        tr.params, tr.opt_state = p, o

    return steps * batch / best_time(rep) / n_chips


def bench_mfu_wide(sizes=None, batch: int = None, steps: int = 20):
    """MFU of the framework's training hot loop on an MXU-saturating
    model: a bf16 MLP whose every matmul is 8192-square (the digits MLP's
    256×128 layers are far too small to fill the systolic array — its MFU
    is reported honestly alongside). Returns (mfu, achieved_flops_per_s).

    The model FLOP count is the standard 3×(2·Σ fan_in·fan_out) per
    example (fwd + both backward matmuls); tanh/log_softmax FLOPs are
    uncounted, so the figure understates true utilization.
    """
    import jax
    import jax.numpy as jnp

    from lua_mapreduce_tpu.models.mlp import (flops_per_example, init_mlp,
                                              nll_loss)
    from lua_mapreduce_tpu.parallel.mesh import make_mesh
    from lua_mapreduce_tpu.train.harness import DataParallelTrainer, TrainConfig
    from lua_mapreduce_tpu.utils.roofline import best_time, mfu

    devices = jax.devices()
    if sizes is None:
        sizes = (8192,) * 4         # MXU-saturating
        batch = batch or 8192
    n_chips = len(devices)
    mesh = make_mesh(dp=n_chips, mp=1, devices=devices)
    params = init_mlp(jax.random.PRNGKey(0), sizes, dtype=jnp.bfloat16)
    tr = DataParallelTrainer(nll_loss, params, mesh,
                             TrainConfig(batch_size=batch))
    # batch generated on device (bf16 host arrays don't exist in numpy,
    # and a 128MB h2d isn't part of the hot loop)
    x = jax.random.normal(jax.random.PRNGKey(1),
                          (batch * n_chips, sizes[0]), jnp.bfloat16)
    y = jax.random.randint(jax.random.PRNGKey(2),
                           (batch * n_chips,), 0, sizes[-1])

    np.asarray(tr.run_steps(x, y, steps))    # compile + warm
    best_dt = best_time(lambda: np.asarray(tr.run_steps(x, y, steps)))

    model_flops = steps * batch * n_chips * flops_per_example(sizes)
    config = (f"mlp {'x'.join(str(s) for s in sizes)} bf16 "
              f"batch={batch} {steps}-step fused scan")
    return (mfu(model_flops, best_dt, n_chips),
            model_flops / best_dt / n_chips, config)


def bench_mapreduce_path(iterations: int = 3) -> float:
    """Images/sec of the same workload through the six-function engine
    (the reference-architecture path)."""
    from lua_mapreduce_tpu.engine.contract import TaskSpec
    from lua_mapreduce_tpu.engine.local import LocalExecutor

    n_shards, bunch = 4, 128
    args = {"sizes": (256, 128, 10), "n_shards": n_shards, "bunch": bunch,
            "max_steps": iterations, "patience": 10_000,
            "model_store": "mem:bench-model", "seed": 0}
    spec = TaskSpec(taskfn="examples.digits.mr_train",
                    mapfn="examples.digits.mr_train",
                    partitionfn="examples.digits.mr_train",
                    reducefn="examples.digits.mr_train",
                    finalfn="examples.digits.mr_train",
                    init_args=args, storage="mem:bench-shuffle")
    ex = LocalExecutor(spec, map_parallelism=n_shards,
                       max_iterations=iterations + 1)
    t0 = time.perf_counter()
    ex.run()
    dt = time.perf_counter() - t0
    return iterations * n_shards * bunch / dt


def _shuffle_pipeline_fields() -> dict:
    """Detail fields for the pipelined shuffle (host-side data plane):
    a small live two-leg run of benchmarks/shuffle_bench (multi-process
    pool, pipelining off vs on, byte-compared outputs). Falls back to
    the committed artifact — labeled as such — if the live run cannot
    complete; never sinks the flagship metric."""
    import os
    here = os.path.dirname(os.path.abspath(__file__))
    try:
        from benchmarks.shuffle_bench import run as shuffle_run
        # the artifact shape at roughly half scale, one round
        r = shuffle_run(n_splits=44, n_stragglers=1, straggler_x=32,
                        premerge_min_runs=12, premerge_max_runs=32,
                        corpus_dir="/tmp/bench_shuffle_corpus", rounds=1)
        return {
            "shuffle_pipeline_speedup": r["pipeline_speedup_wall"],
            "shuffle_pipeline_identical_output": r["identical_output"],
            "shuffle_pipeline_overlap_fraction":
                r["pipelined"]["overlap_fraction"],
        }
    except Exception as e:
        out = {"shuffle_pipeline_error": f"{type(e).__name__}: {e}"[:200]}
        try:
            with open(os.path.join(here, "benchmarks", "results",
                                   "shuffle.json")) as f:
                art = json.load(f)
            out["shuffle_pipeline_speedup_committed"] = \
                art["pipeline_speedup_wall"]
        except Exception:
            pass
        return out


def _segment_fields() -> dict:
    """Detail fields for the framed-segment data plane (DESIGN §17):
    a small live paired run of benchmarks/segment_bench (v1 text vs v2
    block-compressed frames over sharedfs, byte-compared outputs), plus
    the committed artifact's full-scale median numbers. Falls back to
    the artifact alone — labeled as such — if the live run cannot
    complete; never sinks the flagship metric."""
    import os
    here = os.path.dirname(os.path.abspath(__file__))
    out = {}
    try:
        from benchmarks.segment_bench import run as segment_run
        r = segment_run(rounds=1, n_jobs=10, vocab=6000)
        out = {
            "segment_speedup_live_1round": r["segment_speedup"],
            "segment_identical_output": (r["identical_output"] and
                                         r["conformance_all_identical"]),
            "compression_ratio_live": r["compression_ratio"],
        }
    except Exception as e:
        out = {"segment_error": f"{type(e).__name__}: {e}"[:200]}
    try:
        with open(os.path.join(here, "benchmarks", "results",
                               "segment.json")) as f:
            art = json.load(f)
        out["segment_speedup"] = art["segment_speedup"]
        out["segment_speedup_cpu"] = art["segment_speedup_cpu"]
        out["shuffle_bytes_written"] = art["shuffle_bytes_written"]
        out["compression_ratio"] = art["compression_ratio"]
    except Exception:
        pass
    return out


def _coord_batch_fields() -> dict:
    """Detail fields for the batch-claim lease protocol (host-side
    control plane): a small live run of benchmarks/coord_bench (many
    tiny jobs over FileJobStore coordination, the seed's single-claim
    protocol vs batched leases, byte-compared outputs). One round only —
    the committed artifact carries the 5-round median; a live single
    round is reported as such. Falls back to the committed artifact if
    the live run cannot complete; never sinks the flagship metric."""
    import os
    here = os.path.dirname(os.path.abspath(__file__))
    out = {}
    try:
        from benchmarks.coord_bench import run as coord_run
        r = coord_run(n_jobs=150, rounds=1)
        out = {
            "coord_batch_speedup_live_1round": r["coord_batch_speedup"],
            "coord_batch_identical_output": r["identical_output"],
        }
    except Exception as e:
        out = {"coord_batch_error": f"{type(e).__name__}: {e}"[:200]}
    try:
        with open(os.path.join(here, "benchmarks", "results",
                               "coord.json")) as f:
            art = json.load(f)
        out["coord_batch_speedup"] = art["coord_batch_speedup"]
        out["coord_batch_speedup_pipelined"] = \
            art["coord_batch_speedup_pipelined"]
    except Exception:
        pass
    return out


def _faults_fields() -> dict:
    """Detail fields for the fault subsystem (DESIGN §19): the retry
    layer's fault-free overhead (a small live paired run of
    benchmarks/faults_bench — median paired wall ratio, ≤1.02 is the
    acceptance bar) and the chaos-smoke gate's wall time. Falls back to
    the committed artifact — labeled as such — if the live run cannot
    complete; never sinks the flagship metric."""
    import os
    here = os.path.dirname(os.path.abspath(__file__))
    out = {}
    try:
        from benchmarks.faults_bench import run as faults_run
        r = faults_run(rounds=1, n_jobs=10, with_chaos=True)
        out = {
            "retry_overhead_ratio_live_1round": r["retry_overhead_ratio"],
            "retry_overhead_identical_output": r["identical_output"],
            "chaos_smoke_wall_s_live": r["chaos_smoke_wall_s"],
        }
    except Exception as e:
        out = {"faults_bench_error": f"{type(e).__name__}: {e}"[:200]}
    try:
        with open(os.path.join(here, "benchmarks", "results",
                               "faults.json")) as f:
            art = json.load(f)
        out["retry_overhead_ratio"] = art["retry_overhead_ratio"]
        out["retry_overhead_ratio_cpu"] = art["retry_overhead_ratio_cpu"]
        out["chaos_smoke_wall_s"] = art["chaos_smoke_wall_s"]
    except Exception:
        pass
    return out


def _replication_fields() -> dict:
    """Detail fields for the replica-aware shuffle (DESIGN §20): a
    small live run of benchmarks/replication_bench (1 paired round,
    overhead only — the recovery legs need the distributed topology
    and stay in the committed artifact), then the committed artifact's
    headline numbers: fault-free overhead of r=2, write amplification
    for r=2 and the erasure-coded 4+1/4+2 stripes (DESIGN §27), the
    failover-vs-map-re-run recovery speedup, and the coded decode
    ratios. Never sinks the flagship metric."""
    import os
    here = os.path.dirname(os.path.abspath(__file__))
    out = {}
    try:
        from benchmarks.replication_bench import run as rep_run
        r = rep_run(rounds=1, n_jobs=6, vocab=2000, with_recovery=False)
        out = {
            "replication_overhead_r2_live_1round":
                r["overhead"]["r2"]["wall_ratio_vs_r1"],
            "replication_identical_output":
                r["overhead"]["r2"]["identical_output_vs_r1"],
            "replication_reconstruct_ms_per_file":
                r["reconstruct"]["reconstruct_ms_per_file"],
        }
    except Exception as e:
        out = {"replication_bench_error": f"{type(e).__name__}: {e}"[:200]}
    try:
        with open(os.path.join(here, "benchmarks", "results",
                               "replication.json")) as f:
            art = json.load(f)
        out["replication_overhead_ratio_r2"] = \
            art["overhead"]["r2"]["wall_ratio_vs_r1"]
        out["replication_write_amplification_r2"] = \
            art["overhead"]["r2"]["write_amplification"]
        out["replication_recovery_speedup"] = \
            art["recovery"]["recovery_speedup"]
        out["replication_failover_recovery_s"] = \
            art["recovery"]["failover"]["recovery_s"]
        out["replication_map_rerun_recovery_s"] = \
            art["recovery"]["map_rerun"]["recovery_s"]
        out["coded_write_amplification_4p1"] = \
            art["coded_overhead"]["c4p1"]["write_amplification"]
        out["coded_write_amplification_4p2"] = \
            art["coded_overhead"]["c4p2"]["write_amplification"]
        out["coded_decode_read_ms_per_file"] = \
            art["decode_micro"]["decode_read_ms_per_file"]
        out["coded_recovery_vs_failover"] = \
            art["recovery"]["coded_recovery_vs_failover"]
        out["coded_recovery_speedup_vs_rerun"] = \
            art["recovery"]["coded_recovery_speedup_vs_rerun"]
    except Exception:
        pass
    return out


def _speculation_fields() -> dict:
    """Detail fields for speculative execution (DESIGN §21): a small
    live paired run of benchmarks/speculation_bench (1 round — the
    straggler leg plus the idle-overhead leg), then the committed
    artifact's headline numbers: the barrier cluster-time speedup with
    one ~10x-slow worker (>1.5x bar), the wasted-work fraction, and
    the speculation-idle overhead (≤1.02 bar). Never sinks the
    flagship metric."""
    import os
    here = os.path.dirname(os.path.abspath(__file__))
    out = {}
    try:
        from benchmarks.speculation_bench import run as spec_run
        r = spec_run(rounds=1, n_jobs=6)
        out = {
            "speculation_speedup_live_1round": r["speculation_speedup"],
            "speculation_identical_output": r["identical_output"],
            "speculation_wins_live": r["spec_wins_total"],
        }
    except Exception as e:
        out = {"speculation_bench_error": f"{type(e).__name__}: {e}"[:200]}
    try:
        with open(os.path.join(here, "benchmarks", "results",
                               "speculation.json")) as f:
            art = json.load(f)
        out["speculation_speedup"] = art["speculation_speedup"]
        out["speculation_p99_job_latency_speedup"] = \
            art["p99_job_latency_speedup"]
        out["speculation_wasted_work_fraction"] = \
            art["wasted_work_fraction"]
        out["speculation_off_overhead_ratio"] = \
            art["speculation_off_overhead_ratio"]
    except Exception:
        pass
    return out


def _autotune_fields() -> dict:
    """Detail fields for lmr-autotune (DESIGN §29): a small live paired
    leg of benchmarks/autotune_bench (the many_tiny_jobs shape, hand-
    tuned vs adaptive — the cheapest shape that exercises the batch_k
    feedback loop end to end), then the committed artifact's headline
    numbers: per-shape adaptive-vs-hand-tuned and adaptive-vs-untuned
    cluster-time ratios and the acceptance verdict. Never sinks the
    flagship metric."""
    import os
    here = os.path.dirname(os.path.abspath(__file__))
    out = {}
    try:
        from benchmarks.autotune_bench import _leg
        h = _leg("many_tiny_jobs", "hand_tuned", "bench-live-hand")
        a = _leg("many_tiny_jobs", "adaptive", "bench-live-adaptive")
        out = {
            "autotune_vs_hand_tuned_live_1round": round(
                h["cluster_s"] / max(a["cluster_s"], 1e-9), 3),
            "autotune_decisions_live": a["decisions"],
            "autotune_identical_output_live": h["result"] == a["result"],
        }
    except Exception as e:
        out = {"autotune_bench_error": f"{type(e).__name__}: {e}"[:200]}
    try:
        with open(os.path.join(here, "benchmarks", "results",
                               "autotune.json")) as f:
            art = json.load(f)
        for shape, d in art["shapes"].items():
            out[f"autotune_{shape}_vs_untuned"] = \
                d["adaptive_speedup_vs_untuned"]
            out[f"autotune_{shape}_vs_hand_tuned"] = \
                d["adaptive_vs_hand_tuned"]
        out["autotune_acceptance_pass"] = art["acceptance"]["pass"]
    except Exception:
        pass
    return out


def _trace_fields() -> dict:
    """Detail fields for lmr-trace (DESIGN §22): a small live paired
    run of benchmarks/trace_bench (1 round, tracing off vs on on the
    distributed coord-shaped wordcount), then the committed artifact's
    numbers — tracing-on wall overhead (≤1.05 bar), the tracing-off
    control ratio (≤1.02 bar; with no tracer the wrapper layer is not
    stacked at all), and spans collected per committed job. Never
    sinks the flagship metric."""
    import os
    here = os.path.dirname(os.path.abspath(__file__))
    out = {}
    try:
        from benchmarks.trace_bench import run as trace_run
        r = trace_run(rounds=1, n_docs=16)
        out = {
            "trace_overhead_live_1round": r["trace_overhead_ratio"],
            "trace_identical_output": r["identical_output"],
        }
    except Exception as e:
        out = {"trace_bench_error": f"{type(e).__name__}: {e}"[:200]}
    try:
        with open(os.path.join(here, "benchmarks", "results",
                               "trace.json")) as f:
            art = json.load(f)
        out["trace_overhead"] = art["trace_overhead_ratio"]
        out["trace_overhead_cpu"] = art["trace_overhead_ratio_cpu"]
        out["trace_off_overhead"] = art["trace_off_ratio"]
        out["trace_spans_per_job"] = art["trace_spans_per_job"]
    except Exception:
        pass
    return out


def _sched_fields() -> dict:
    """Detail fields for lmr-sched (DESIGN §23): a small live run of
    the coord_bench sched legs (poll-vs-notify dispatch latency at a
    dozen concurrent tenant tasks plus the fairness pair), then the
    committed artifact's headline numbers — dispatch p50/p99 speedup
    and jobs/sec at 100 concurrent small tasks vs the polling baseline,
    and the starvation bound (a flooded barrier tenant's p99 as a
    fraction of the FIFO flood drain). Never sinks the flagship
    metric."""
    import os
    here = os.path.dirname(os.path.abspath(__file__))
    out = {}
    try:
        from benchmarks.coord_bench import run_sched
        r = run_sched(n_tenants=12, jobs_per_tenant=2, n_workers=4,
                      rounds=1, submit_window_s=0.4)
        out = {
            "sched_dispatch_p50_speedup_live_1round":
                r["dispatch_p50_speedup"],
            "sched_fairness_gain_live": r["fairness_gain"],
        }
    except Exception as e:
        out = {"sched_bench_error": f"{type(e).__name__}: {e}"[:200]}
    try:
        with open(os.path.join(here, "benchmarks", "results",
                               "sched.json")) as f:
            art = json.load(f)
        out["sched_dispatch_p50_speedup"] = art["dispatch_p50_speedup"]
        out["dispatch_latency_p50_ms"] = art["dispatch_p50_ms_notify"]
        out["dispatch_latency_p99_ms"] = art["dispatch_p99_ms_notify"]
        out["dispatch_latency_p50_ms_poll"] = art["dispatch_p50_ms_poll"]
        out["dispatch_latency_p99_ms_poll"] = art["dispatch_p99_ms_poll"]
        out["sched_jobs_per_s_speedup_100t"] = art["jobs_per_s_speedup"]
        out["sched_chain_jobs_per_s_speedup"] = \
            art["chain_jobs_per_s_speedup"]
        out["sched_fairness_gain"] = art["fairness_gain"]
        out["sched_barrier_p99_vs_flood_drain"] = \
            art["barrier_p99_vs_flood_drain"]
    except Exception:
        pass
    return out


def _analysis_fields() -> dict:
    """Detail fields for the analysis subsystem (DESIGN §18/§25): the
    lint pass's wall time over the whole package (it gates test.sh, so
    its cost is part of the developer loop), the interprocedural deep
    pass's call-graph size (nodes/edges), context-reached function
    count and wall time, and a small exhaustive model-checker run
    (2 workers × 2 jobs, death included) with its state count — the
    protocol-coverage figure. Never sinks the flagship metric."""
    import time as _t
    out = {}
    try:
        from lua_mapreduce_tpu.analysis import run_lint
        t0 = _t.perf_counter()
        findings = run_lint()
        out["analyze_lint_wall_s"] = round(_t.perf_counter() - t0, 3)
        out["analyze_lint_findings"] = len(findings)
    except Exception as e:
        out["analyze_lint_error"] = f"{type(e).__name__}: {e}"[:200]
    try:
        from lua_mapreduce_tpu.analysis import dataflow
        res = dataflow.analyze()
        out["analyze_callgraph_nodes"] = res.graph.node_count()
        out["analyze_callgraph_edges"] = res.graph.edge_count()
        out["analyze_deep_reached"] = res.reached
        out["analyze_deep_findings"] = len(res.findings)
        out["analyze_deep_wall_s"] = round(res.wall_s, 3)
    except Exception as e:
        out["analyze_deep_error"] = f"{type(e).__name__}: {e}"[:200]
    try:
        from lua_mapreduce_tpu.analysis import ModelConfig, check_protocol
        res = check_protocol(ModelConfig(n_workers=2, n_jobs=2))
        out["analyze_protocol_states"] = res.states
        out["analyze_protocol_ok"] = res.ok
        out["analyze_protocol_wall_s"] = round(res.wall_s, 3)
    except Exception as e:
        out["analyze_protocol_error"] = f"{type(e).__name__}: {e}"[:200]
    return out


def _ingraph_fields() -> dict:
    """Detail fields for the in-graph engine (DESIGN §26): a one-round
    live smoke pair (compiled vs interpreted kmeans, allclose-gated),
    then the committed artifact's numbers — the median paired-rounds
    end-to-end speedup on the digits/kmeans loop workloads (≥3.0 bar),
    the steady-state per-iteration asymptote, and the one-time
    compile cost (the no-retrace loop contract makes it one per task).
    Never sinks the flagship metric."""
    import os
    here = os.path.dirname(os.path.abspath(__file__))
    out = {}
    try:
        from benchmarks.ingraph_bench import _kmeans_leg, _workload
        r = _workload("kmeans", _kmeans_leg, 30, 1, warmup=False)
        out = {
            "ingraph_speedup_live_1round": r["speedup"],
            "ingraph_state_allclose": r["state_allclose"],
        }
    except Exception as e:
        out = {"ingraph_bench_error": f"{type(e).__name__}: {e}"[:200]}
    try:
        with open(os.path.join(here, "benchmarks", "results",
                               "ingraph.json")) as f:
            art = json.load(f)
        out["ingraph_speedup"] = art["ingraph_speedup"]
        out["ingraph_compile_s"] = art["ingraph_compile_s"]
        out["ingraph_speedup_digits"] = art["digits"]["speedup"]
        out["ingraph_speedup_kmeans"] = art["kmeans"]["speedup"]
        out["ingraph_steady_state_digits"] = \
            art["digits"]["steady_state_speedup"]
        out["ingraph_steady_state_kmeans"] = \
            art["kmeans"]["steady_state_speedup"]
        out["ingraph_images_per_s"] = art["digits"]["images_per_s_ingraph"]
    except Exception:
        pass
    return out


def _ha_fields() -> dict:
    """Detail fields for the HA coordinator plane (DESIGN §31): a live
    one-round fencing pair + one crash-to-takeover clocking from
    benchmarks/ha_bench (leader lease election, epoch-fenced mutations,
    hot-standby takeover on the threaded-state loop task), then the
    committed artifact's medians — fencing overhead (≤1.02 bar) and
    takeover latency against its 2×TTL budget. Falls back to the
    committed artifact — labeled as such — if the live run cannot
    complete; never sinks the flagship metric."""
    import os
    here = os.path.dirname(os.path.abspath(__file__))
    out = {}
    try:
        from benchmarks.ha_bench import run as ha_run
        r = ha_run(rounds=1, n_iters=6, takeover_rounds=1)
        out = {
            "ha_fencing_overhead_live_1round": r["ha_fencing_overhead"],
            "ha_takeover_ms_live_1round": r["ha_takeover_ms"],
            "ha_identical_output": r["ha_identical_output"],
        }
    except Exception as e:
        out = {"ha_bench_error": f"{type(e).__name__}: {e}"[:200]}
    try:
        with open(os.path.join(here, "benchmarks", "results",
                               "ha.json")) as f:
            art = json.load(f)
        out["ha_fencing_overhead"] = art["ha_fencing_overhead"]
        out["ha_takeover_ms"] = art["ha_takeover_ms"]
        out["ha_takeover_budget_ms"] = art["ha_takeover_budget_ms"]
    except Exception:
        pass
    return out


def main() -> None:
    from lua_mapreduce_tpu.utils.jax_env import (place_compile_cache,
                                                 require_tpu)
    place_compile_cache()
    require_tpu("bench.py")

    import jax

    from lua_mapreduce_tpu.models.mlp import DIGITS_SIZES, flops_per_example
    from lua_mapreduce_tpu.utils.roofline import mfu, peak_flops_per_s

    native_per_chip = bench_tpu_native()
    native_total = native_per_chip * len(jax.devices())
    mr_total = bench_mapreduce_path()
    peak = peak_flops_per_s()
    mfu_digits = mfu(native_per_chip * flops_per_example(DIGITS_SIZES), 1.0)
    mfu_wide, wide_flops, mfu_config = bench_mfu_wide()
    # the REAL-workload number next to the synthetic-MLP one: the
    # llama-style LM train step (flash attention + RoPE/RMS/SwiGLU/GQA,
    # fused grad all-reduce, optimizer). A failure here is recorded so
    # the other fields still print, and then fails the run.
    try:
        from benchmarks.kernel_bench import bench_transformer_step
        r = bench_transformer_step(modern=True)
        lm = {"lm_train_mfu": r["mfu"],
              "lm_train_ms_per_step": r["ms_per_step"],
              "lm_train_tokens_per_sec": r["tokens_per_sec"],
              "lm_train_config": r["config"]}
    except Exception as e:         # noqa: BLE001 — reported, then rc 1
        lm = {"lm_train_error": f"{type(e).__name__}: {e}"[:200]}

    digits_fields = {
        "digits_images_per_sec_per_chip": round(native_per_chip, 1),
        # total/total: same quantity in numerator and denominator, so the
        # ratio is comparable across machine sizes
        "digits_native_vs_mapreduce_path": round(native_total / mr_total, 2),
        # roofline (BASELINE.md ≥50% MFU north star): model FLOPs per
        # second over chip peak bf16 FLOP/s (utils/roofline.py table).
        # The digits MLP (256→128→10) cannot fill a 128×128 systolic
        # array — its honest MFU is tiny; mfu_wide_mlp is the same
        # training hot loop on an MXU-sized model (8192-square bf16).
        "mfu_wide_mlp": round(mfu_wide, 4),
        "mfu_wide_config": mfu_config,
        "mfu_wide_achieved_flops_per_s_per_chip": round(wide_flops, 1),
        "mfu_digits_mlp": round(mfu_digits, 6),
        "peak_bf16_flops_per_s": peak,
        "device_kind": jax.devices()[0].device_kind,
        # host-side data plane: barrier vs pipelined shuffle wall ratio
        # (benchmarks/shuffle_bench.py; >1.0 = pipelining wins)
        **_shuffle_pipeline_fields(),
        # host-side control plane: batched claim leases vs the seed's
        # single-claim protocol (benchmarks/coord_bench.py; >1.0 =
        # batching wins on a many-tiny-jobs FileJobStore workload)
        **_coord_batch_fields(),
        **_sched_fields(),
        # host-side data plane encoding: v2 framed binary segments vs
        # v1 text lines (benchmarks/segment_bench.py; >1.0 = frames win
        # on the IO-bound shuffle leg, byte-identical outputs)
        **_segment_fields(),
        # static analysis: lint wall time over the package + the
        # exhaustive lease-protocol check's state coverage (DESIGN §18)
        **_analysis_fields(),
        # fault subsystem: retry-layer fault-free overhead (≤1.02 bar)
        # + the chaos-smoke gate's wall time (DESIGN §19)
        **_faults_fields(),
        # replica-aware shuffle: r=2 fault-free overhead + write
        # amplification, and the failover-vs-map-re-run recovery
        # speedup (benchmarks/replication_bench.py; DESIGN §20)
        **_replication_fields(),
        # speculative execution: straggler barrier speedup, wasted-work
        # fraction, and the speculation-idle overhead
        # (benchmarks/speculation_bench.py; DESIGN §21)
        **_speculation_fields(),
        # lmr-trace: tracing-on overhead (≤1.05), tracing-off control
        # (≤1.02), spans per job (benchmarks/trace_bench.py; DESIGN §22)
        **_trace_fields(),
        # lmr-autotune: adaptive-vs-hand-tuned / adaptive-vs-untuned
        # cluster-time ratios per workload shape + the acceptance
        # verdict (benchmarks/autotune_bench.py; DESIGN §29)
        **_autotune_fields(),
        # in-graph engine: compiled-vs-interpreted loop-workload
        # speedup + one-time compile cost
        # (benchmarks/ingraph_bench.py; DESIGN §26)
        **_ingraph_fields(),
        # lmr-ha: leader-lease fencing overhead (≤1.02 bar) + hot-
        # standby crash-to-takeover latency vs its 2×TTL budget
        # (benchmarks/ha_bench.py; DESIGN §31)
        **_ha_fields(),
    }
    if "lm_train_mfu" in lm:
        # VERDICT r4 weak-1: the first number a reader (or the driver
        # parser) sees must be the most meaningful one — the llama-style
        # LM training step, scored against the ≥50%-MFU north star.
        out = {
            "metric": "llama_style_lm_train_mfu",
            "value": lm["lm_train_mfu"],
            "unit": "MFU (bf16 model FLOPs / chip peak)",
            "vs_baseline": round(lm["lm_train_mfu"] / 0.50, 3),
            **lm, **digits_fields,
        }
    else:
        out = {
            "metric": "digits_mlp_dp_training_images_per_sec_per_chip",
            "value": round(native_per_chip, 1),
            "unit": "images/sec/chip",
            "vs_baseline": round(native_total / mr_total, 2),
            **lm, **digits_fields,
        }
    print(json.dumps(out))
    if "lm_train_error" in lm:
        raise SystemExit(f"bench.py: {lm['lm_train_error']}")


if __name__ == "__main__":
    main()
