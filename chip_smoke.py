"""The quickest proof that the system still starts on the chip.

    python chip_smoke.py

drives the main path once on a TPU, through the entry points a user
calls and at the full width of the one LM the repo has run on a chip
(d1024, 8 layers, 32k vocabulary; ROADMAP B1 replaces it): the trainer
takes a few steps, the decoder answers a few requests, every kernel the
TPU policy routes to Pallas is compared with its XLA twin, and two
six-function tasks run on the compiled plane. With four chips it also
runs the sharded steps across them. Weights are random, from a seed.

One process, which starts no child that imports JAX: a chip belongs to
one process. There is no CPU mode and no flag that skips the device
check. Any stage that raises or fails an assertion ends the run with a
non-zero exit code and no result line. Each stage prints one JSON line;
the times in them are smoke timings (one cold call, a few warm ones),
not metrics, and ``builds`` are the rows of the process's build log
(``utils/profiling.py``) for the programs the stage built. The line
before the last sums the run up (stages, programs built,
persistent-cache hits, ``"claim": null``), and the last line of standard
output is the result, these keys and no other:

    {"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}

The stages are plain functions of their sizes, so tests/test_chip_smoke.py
runs each of them at a tiny size on the CPU.
"""

from __future__ import annotations

import collections
import functools
import gc
import json
import sys
import time

# a Pallas kernel and its XLA twin both accumulate in float32 and round
# to bfloat16 once, but in different orders: they may differ by a few
# bf16 ulps (2^-8 relative) of the largest value in the reference
BF16_TOLERANCE = 4 * 2.0 ** -8

# the in-graph plane against the store plane on the same module: the
# repo's own allclose bound (benchmarks/ingraph_bench.py, test_ingraph)
ENGINE_TOLERANCE = 1e-4

LM = dict(vocab=32768, d_model=1024, n_heads=16, n_layers=8, d_ff=4096)
TRAIN = dict(batch=8, seq=2048, steps=4, n_kv_heads=4)
MOE_EXPERTS = 8
DECODE = dict(batch=4, prompt_len=3968, n_new=128, max_seq=4096,
              requests=3)
POOL_SHAPE = (256, 64, 64, 32)
ENGINE_ITERATIONS = 5


# --------------------------------------------------------------------------
# what the run counts: programs built, and persistent-cache traffic
# --------------------------------------------------------------------------

def _since(log, snap: dict) -> dict:
    """The process's build log (``utils/profiling.build_log``) since
    ``snap``, a ``log.counts()``: ``programs`` is every executable built
    (compiled, or fetched from the persistent cache), the hits and
    misses are the persistent cache's; ``builds`` are the log's rows
    (trace, lower, build, and what ran after), in the order built."""
    now = log.counts()
    rows = [{k: round(v, 4) if isinstance(v, float) else v
             for k, v in row.items() if k not in ("t0", "t1", "enclosed")}
            for row in log.rows(snap["programs"])]
    return {**{k: now[k] - snap[k] for k in now}, "builds": rows}


def _report(stage: str, log, snap, **fields) -> dict:
    line = {"stage": stage, **fields, **_since(log, snap)}
    print(json.dumps(line), flush=True)
    return line


def check(ok, why) -> None:
    """The smoke's assertion: a plain ``assert`` vanishes under
    ``python -O``, and this script has no way to skip a check."""
    if not ok:
        raise AssertionError(why)


def _timed(fn):
    import jax
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn())
    return out, round(time.perf_counter() - t0, 3)


# --------------------------------------------------------------------------
# a. the trainer
# --------------------------------------------------------------------------

def stage_trainer(log, devices, mesh_shape, *, lm, batch, seq,
                  steps, n_kv_heads, moe_experts=0, expect_backend="pallas",
                  name="trainer") -> dict:
    """``steps`` separately dispatched LM train steps on one repeated
    batch over a ``("dp", "sp")`` mesh of ``devices``: the loss must be
    finite and fall, attention must take the ``expect_backend`` route
    (and say so in the lowered program), and nothing may be built after
    the first step. With ``moe_experts`` the FFNs are expert-parallel
    over ``dp``."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax
    from jax.sharding import Mesh

    from lua_mapreduce_tpu import ops
    from lua_mapreduce_tpu.models import transformer as tfm
    from lua_mapreduce_tpu.parallel.mesh import opt_state_layout

    snap = log.counts()
    dp, sp = mesh_shape
    mesh = Mesh(np.array(devices[:dp * sp]).reshape(dp, sp), ("dp", "sp"))
    kw = dict(lm, n_kv_heads=n_kv_heads, max_seq=seq)
    if moe_experts:
        # expert FFNs are gelu (models/transformer._check_arch); capacity
        # is per routing group, the device's tile: twice its even share
        tile = batch * seq // (dp * sp)
        kw.update(ffn="gelu", moe_experts=moe_experts,
                  moe_capacity=2 * tile // moe_experts)
    cfg = tfm.TransformerConfig.llama_style(**kw)
    params = jax.tree.map(
        lambda x: x.astype(jnp.bfloat16),
        tfm.init_transformer(jax.random.PRNGKey(0), cfg))
    params = tfm.shard_params_moe(params, mesh)     # dense: split by rows
    # Adam: its step is ~lr per weight whatever the gradient's scale, so
    # it survives bfloat16 weights (lr * grad of plain SGD rounds away)
    opt = optax.adam(1e-3)
    opt_state = tfm.init_opt_state(opt, params, mesh)
    step = tfm.make_train_step(cfg, mesh, opt, attn="ring")
    rows = np.random.RandomState(0).randint(0, cfg.vocab, (batch, seq + 1))
    tokens, targets = tfm.shard_batch(
        mesh, jnp.asarray(rows[:, :-1], jnp.int32),
        jnp.asarray(rows[:, 1:], jnp.int32))

    check(ops.default_backend("flash_attention") == expect_backend,
          f"flash_attention routes to "
          f"{ops.default_backend('flash_attention')}, not {expect_backend}")
    lowered = step.lower(params, opt_state, tokens, targets).as_text()
    check(("tpu_custom_call" in lowered) == (expect_backend == "pallas"),
          "the train step's lowered program does not take the "
          f"{expect_backend} attention route")
    del lowered
    _assert_on_every_device(mesh, params=params, batch=(tokens, targets))

    losses, walls, later = [], [], None
    for i in range(steps):
        if i == 1:
            later = log.counts()
        (params, opt_state, loss), wall = _timed(
            lambda: step(params, opt_state, tokens, targets))
        losses.append(float(loss))
        walls.append(wall)
    _assert_on_every_device(mesh, new_params=params, loss=loss)

    check(all(np.isfinite(losses)), losses)
    check(losses[-1] < losses[0],
          f"the loss did not fall over {steps} steps: {losses}")
    built_later = log.programs - later["programs"]
    check(built_later == 0,
          f"{built_later} program(s) built after the first step")

    return _report(
        name + (".moe" if moe_experts else ".dense"), log, snap,
        ran=(f"make_train_step(attn=ring) on a {dp}x{sp} (dp, sp) mesh, "
             f"d{cfg.d_model} L{cfg.n_layers} h{cfg.n_heads}/"
             f"{tfm.kv_heads(cfg)} ff{cfg.d_ff} v{cfg.vocab} bf16, "
             f"batch {batch}x{seq}"
             + (f", {moe_experts} experts cap {cfg.moe_capacity}"
                if moe_experts else "")),
        attention_backend=expect_backend, losses=losses,
        smoke_first_call_s=walls[0], smoke_later_calls_s=walls[1:],
        programs_after_first_step=built_later,
        opt_state=dict(zip(("bytes_a_device", "bytes", "leaves_split",
                            "leaves_whole"), opt_state_layout(opt_state))),
        **_memory_spread(mesh))


def _assert_on_every_device(mesh, **trees) -> None:
    import jax
    devs = set(mesh.devices.flat)
    for what, tree in trees.items():
        for leaf in jax.tree.leaves(tree):
            check(leaf.sharding.device_set == devs,
                  f"{what} lives on {len(leaf.sharding.device_set)} of "
                  f"the mesh's {len(devs)} devices")


def _memory_spread(mesh) -> dict:
    """Bytes in use on each of the mesh's devices. On several devices
    each must hold something and the first must not hold most of it
    (the CPU backend reports no memory statistics)."""
    devs = list(mesh.devices.flat)
    if devs[0].platform != "tpu":
        return {"devices": len(devs)}
    stats = [d.memory_stats() for d in devs]
    in_use = [s["bytes_in_use"] for s in stats]
    if len(devs) > 1:
        check(all(b > 0 for b in in_use), in_use)
        check(in_use[0] < 0.5 * sum(in_use),
              f"memory is concentrated on device 0: {in_use}")
    return {"devices": len(devs), "bytes_in_use": in_use,
            "peak_bytes_in_use": [s["peak_bytes_in_use"] for s in stats]}


# --------------------------------------------------------------------------
# b. the decoder
# --------------------------------------------------------------------------

def stage_decoder(log, *, lm, batch, prompt_len, n_new,
                  max_seq, requests, variant: str) -> dict:
    """``requests`` prompts through ``prefill`` + ``greedy_decode`` in
    one serving variant: ``mha`` (bf16), ``q8`` (int8 weights and int8
    KV cache) or ``gqa`` (four query heads to a kv head). Tokens must be
    in range, the prompt's logits finite, and nothing may be built after
    the first request."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from lua_mapreduce_tpu.models import transformer as tfm

    snap = log.counts()
    cfg = tfm.TransformerConfig.llama_style(
        **lm, max_seq=max_seq,
        n_kv_heads=lm["n_heads"] // 4 if variant == "gqa" else 0)
    params = jax.tree.map(
        lambda x: x.astype(jnp.bfloat16),
        tfm.init_transformer(jax.random.PRNGKey(1), cfg))
    if variant == "q8":
        params = tfm.quantize_lm(params)
    kv_q8 = variant == "q8"
    total = prompt_len + n_new
    prefill = jax.jit(functools.partial(tfm.prefill, cfg=cfg, total=total))
    rng = np.random.RandomState(1)

    def prompt():
        return jnp.asarray(rng.randint(0, cfg.vocab, (batch, prompt_len)),
                           jnp.int32)

    (caches, logits), prefill_wall = _timed(lambda: prefill(params, prompt()))
    hkv, hd = tfm.kv_heads(cfg), cfg.d_model // cfg.n_heads
    check(logits.shape == (batch, cfg.vocab), logits.shape)
    check(bool(jnp.isfinite(logits).all()), "prefill logits not finite")
    check(caches["L0_k"].shape == (batch, total, hkv, hd),
          caches["L0_k"].shape)
    del caches, logits

    walls, later = [], None
    for r in range(requests):
        if r == 1:
            later = log.counts()
        p = prompt()
        out, wall = _timed(lambda: tfm.greedy_decode(
            params, p, n_new, cfg=cfg, use_prefill=True, kv_q8=kv_q8))
        walls.append(wall)
        out = np.asarray(out)
        check(out.shape == (batch, total), out.shape)
        check((out[:, :prompt_len] == np.asarray(p)).all(),
              "the prompt is not the prefix of the output")
        check(out.min() >= 0 and out.max() < cfg.vocab,
              f"tokens out of range: {out.min()}..{out.max()}")
    built_later = log.programs - later["programs"]
    check(built_later == 0,
          f"{built_later} program(s) built after the first request")
    return _report(
        f"decoder.{variant}", log, snap,
        ran=(f"prefill + greedy_decode(use_prefill, kv_q8={kv_q8}), "
             f"d{cfg.d_model} L{cfg.n_layers} h{cfg.n_heads}/{hkv} "
             f"v{cfg.vocab}"
             + (" int8 weights" if variant == "q8" else " bf16")
             + f", batch {batch}, prompt {prompt_len}, {n_new} new, "
             f"{requests} requests"),
        smoke_prefill_first_call_s=prefill_wall,
        smoke_first_call_s=walls[0], smoke_later_calls_s=walls[1:],
        programs_after_first_request=built_later)


# --------------------------------------------------------------------------
# c. every kernel the TPU policy routes to Pallas, against its XLA twin
# --------------------------------------------------------------------------

def _kernel_cases(*, lm, train, decode, pool_shape) -> dict:
    """op name -> [(label, fn(backend) -> arrays)] at the shapes stages
    a and b run. Keyed by the names in ``ops._TPU_AUTO_POLICY``: an op
    routed to Pallas with no case here fails the stage."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from lua_mapreduce_tpu import ops
    from lua_mapreduce_tpu.ops.decode import quantize_kv

    bf = jnp.bfloat16
    h, d = lm["n_heads"], lm["d_model"]
    hd, hkv = d // h, train["n_kv_heads"]
    key = jax.random.PRNGKey(2)

    def normal(i, shape, dtype=bf):
        return jax.random.normal(jax.random.fold_in(key, i), shape, dtype)

    def flash(b, l, heads_kv, grad):
        q = normal(0, (b, l, h, hd))
        k, v = normal(1, (b, l, heads_kv, hd)), normal(2, (b, l, heads_kv, hd))

        def run(backend):
            def f(q, k, v):
                return ops.flash_attention(q, k, v, causal=True,
                                           backend=backend)
            def fwd_bwd(q, k, v):
                out, vjp = jax.vjp(f, q, k, v)
                return (out, *vjp(jnp.ones_like(out)))
            return jax.jit(fwd_bwd if grad else lambda *a: (f(*a),))(
                q, k, v)
        return run

    def decode_attn(heads_kv, q8):
        b, s = decode["batch"], decode["max_seq"]
        q = normal(3, (b, heads_kv, h // heads_kv, hd))
        k, v = normal(4, (b, heads_kv, s, hd)), normal(5, (b, heads_kv, s, hd))
        t = jnp.int32(decode["prompt_len"] + decode["n_new"] // 2)
        scales = {}
        if q8:
            (k, ks), (v, vs) = quantize_kv(k), quantize_kv(v)
            scales = {"k_scale": ks, "v_scale": vs}
        return lambda backend: (ops.decode_attention(
            q, k, v, t, backend=backend, **scales),)

    def latent_decode():
        # a latent cache's row [c_kv | k_rope]: 576 wide, the first 512
        # the values (ops/mla_decode.py), every head over the one row
        b, s = decode["batch"], decode["max_seq"]
        q, rows = normal(9, (b, h, 576)), normal(10, (b, s, 576))
        t = jnp.int32(decode["prompt_len"] + decode["n_new"] // 2)
        return lambda backend: (ops.mla_decode_attention(
            q, rows, t, v_rank=512, scale=576 ** -0.5, backend=backend),)

    def held_experts(count, touched):
        # a decode step's tokens through the touched ones of `count`
        # stacked SwiGLU experts (ops/moe_held.py)
        from lua_mapreduce_tpu.ops.moe_held import moe_held
        b, f = decode["batch"], lm["d_ff"] // 4
        x = normal(11, (b, d))
        wg, wu = (normal(i, (count, d, f)) * d ** -0.5 for i in (12, 13))
        wd = normal(14, (count, f, d)) * f ** -0.5
        chosen = jnp.arange(count) % (count // touched) == 0
        combine = jnp.where(chosen[None, :], jax.random.uniform(
            jax.random.fold_in(key, 15), (b, count), jnp.float32, 0.1, 0.5),
            0.0)
        load = jnp.where(chosen, b, 0).astype(jnp.int32)
        return lambda backend: (moe_held(x, combine, load, wg, wu, wd,
                                         backend=backend),)

    def q8(m, kdim, n):
        x = normal(6, (m, kdim))
        w, s = ops.quantize_q8(
            normal(7, (kdim, n), jnp.float32) / np.sqrt(kdim))
        return lambda backend: (ops.q8_matmul(x, w, s.reshape(-1),
                                              backend=backend),)

    def pool(fn):
        x = normal(8, pool_shape)
        return lambda backend: (fn(x, 2, backend=backend),)

    tb, tl = train["batch"], train["seq"]
    db, dl = decode["batch"], decode["prompt_len"]
    qkv = (h + 2 * h) * hd
    return {
        "flash_attention": [
            (f"train fwd+bwd b{tb} L{tl} h{h}/{hkv}", flash(tb, tl, hkv, True)),
            (f"prefill fwd b{db} L{dl} h{h}/{h}", flash(db, dl, h, False)),
            (f"prefill fwd b{db} L{dl} h{h}/{h // 4}",
             flash(db, dl, h // 4, False)),
        ],
        "decode_attention": [
            (f"mha bf16 cache {decode['max_seq']}", decode_attn(h, False)),
            (f"mha int8 cache {decode['max_seq']}", decode_attn(h, True)),
            (f"gqa4 bf16 cache {decode['max_seq']}",
             decode_attn(h // 4, False)),
        ],
        "mla_decode_attention": [
            (f"h{h} bf16 cache {decode['max_seq']} x 576", latent_decode()),
        ],
        "moe_held": [
            (f"{db} tokens, 4 of 8 experts {d}x{lm['d_ff'] // 4}",
             held_experts(8, 4)),
        ],
        "q8_matmul": [
            (f"decode qkv {db}x{d}x{qkv}", q8(db, d, qkv)),
            (f"decode ffn-down {db}x{lm['d_ff']}x{d}", q8(db, lm["d_ff"], d)),
            (f"decode head {db}x{d}x{lm['vocab']}", q8(db, d, lm["vocab"])),
            (f"prefill ffn-up {db * dl}x{d}x{lm['d_ff']}",
             q8(db * dl, d, lm["d_ff"])),
        ],
        "maxpool2d": [(f"{pool_shape} bf16", pool(ops.maxpool2d))],
        "avgpool2d": [(f"{pool_shape} bf16", pool(ops.avgpool2d))],
    }


def stage_kernels(log, *, lm, train, decode, pool_shape,
                  kernel_backend="pallas") -> dict:
    """For every op ``ops._TPU_AUTO_POLICY`` routes to Pallas: the
    kernel (``kernel_backend``) against ``backend="xla"`` within
    :data:`BF16_TOLERANCE`, at the shapes the other stages run."""
    import numpy as np

    from lua_mapreduce_tpu import ops

    snap = log.counts()
    cases = _kernel_cases(lm=lm, train=train, decode=decode,
                          pool_shape=pool_shape)
    routed = sorted(op for op, to in ops._TPU_AUTO_POLICY.items()
                    if to == "pallas")
    compared = {}
    for op in routed:
        for label, run in cases[op]:
            got, first = _timed(lambda: run(kernel_backend))
            want, _ = _timed(lambda: run("xla"))
            worst = 0.0
            for g, w in zip(got, want):
                g = np.asarray(g, np.float32)
                w = np.asarray(w, np.float32)
                check(g.shape == w.shape and np.isfinite(g).all(),
                      f"{op} [{label}]: bad kernel output")
                err = float(np.abs(g - w).max() / np.abs(w).max())
                check(err <= BF16_TOLERANCE,
                      f"{op} [{label}]: max|{kernel_backend} - xla| is "
                      f"{err:.2e} of max|xla|, over {BF16_TOLERANCE:.2e}")
                worst = max(worst, err)
            compared[f"{op} [{label}]"] = {
                "rel_err": round(worst, 6), "smoke_first_call_s": first}
            del got, want
    return _report(
        "kernels", log, snap,
        ran=f"{kernel_backend} against xla for every op "
            f"_TPU_AUTO_POLICY routes to pallas: {', '.join(routed)}",
        tolerance=BF16_TOLERANCE, compared=compared)


# --------------------------------------------------------------------------
# d. the six-function engine, compiled plane
# --------------------------------------------------------------------------

ENGINE_TASKS = {
    # module -> (init args for n loops, the tier it must compile on, how
    #            its folds must lower, its final state as float arrays)
    "examples.digits.mr_sgd": (
        lambda n: {"max_steps": n}, "shard_map", "psum",
        lambda m: dict(m.read_state()["params"])),
    "examples.kmeans.mr_kmeans": (
        lambda n: {"max_iters": n, "tol": 0.0}, "jit", "fused",
        lambda m: {"centroids": m.read_state("mem")["centroids"]}),
}


def stage_engine(log, mod: str, *, iterations: int,
                 dp: int) -> dict:
    """One looping six-function task on the compiled plane: through the
    server launcher under ``--engine ingraph`` (the hard mode: a
    lowering failure raises), then through ``LocalExecutor`` to read
    which tier compiled, how its folds lowered and that it traced once,
    and against the store plane (on the host CPU, its home) for the
    final state."""
    import importlib

    import jax
    import numpy as np

    from lua_mapreduce_tpu.cli import execute_server
    from lua_mapreduce_tpu.engine.contract import TaskSpec
    from lua_mapreduce_tpu.engine.local import LocalExecutor

    snap = log.counts()
    init_args, want_mode, want_fold, read_state = ENGINE_TASKS[mod]
    args = init_args(iterations)
    short = mod.rsplit(".", 1)[-1]

    cli = ["mem", mod, mod, mod, mod, "--finalfn", mod,
           "--engine", "ingraph", "--quiet"]
    for k, v in args.items():
        cli += ["--init-arg", f"{k}={v}"]
    rc, cli_wall = _timed(lambda: execute_server.main(cli))
    check(rc == 0, f"execute_server.main({cli}) returned {rc}")

    def run(engine, tag):
        spec = TaskSpec(taskfn=mod, mapfn=mod, partitionfn=mod,
                        reducefn=mod, finalfn=mod, init_args=args,
                        storage=f"mem:chip-smoke-{short}-{tag}")
        ex = LocalExecutor(spec, engine=engine,
                           max_iterations=iterations + 5)
        _, wall = _timed(ex.run)
        state = read_state(importlib.import_module(mod))
        return ex, {k: np.array(v) for k, v in state.items()}, wall

    # float32 matmuls on a TPU run in bfloat16 passes unless asked
    # otherwise; the comparison with the host's float32 asks
    with jax.default_matmul_precision("highest"):
        ex, got, wall = run("ingraph", "ingraph")
    eng = ex._ingraph.engine
    its = ex.stats.iterations
    folds = dict(collections.Counter(eng._plan.folds.values()))
    found = {"mode": eng.mode, "traces": eng.traces, "folds": folds,
             "dp": eng._mesh.shape["dp"] if eng._mesh is not None else 1,
             "ingraph_iterations": sum(i.ingraph_iterations for i in its),
             "ingraph_fallbacks": sum(i.ingraph_fallbacks for i in its),
             "hybrid_fallbacks": sum(i.hybrid_fallbacks for i in its),
             "collective_tier_refused": eng.collective_error}
    check(eng.mode == want_mode, found)
    check(eng.traces == 1, found)
    check(set(folds) == {want_fold}, found)
    check(found["ingraph_iterations"] == iterations, found)
    check(found["ingraph_fallbacks"] == 0, found)
    check(found["hybrid_fallbacks"] == 0, found)
    if want_mode == "shard_map":
        check(found["dp"] == dp, found)
    print(f"{short}: {eng.mode}, folds all {want_fold}, traces "
          f"{eng.traces}, fallbacks 0", flush=True)

    with jax.default_device(jax.devices("cpu")[0]):
        _, want, _ = run("store", "store")
    worst = 0.0
    for k in want:
        np.testing.assert_allclose(got[k], want[k], rtol=ENGINE_TOLERANCE,
                                   atol=ENGINE_TOLERANCE, err_msg=k)
        worst = max(worst, float(np.abs(got[k] - want[k]).max()))
    return _report(
        f"engine.{short}", log, snap,
        ran=f"execute_server.main(--engine ingraph) then "
            f"LocalExecutor(engine=ingraph) against engine=store, "
            f"{iterations} iterations",
        **found, max_abs_diff_vs_store=worst, tolerance=ENGINE_TOLERANCE,
        smoke_cli_run_s=cli_wall, smoke_executor_run_s=wall)


# --------------------------------------------------------------------------
# the run
# --------------------------------------------------------------------------

def describe_device() -> dict:
    """The device as JAX reports it, in the result line's own shape."""
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def check_device():
    """The device this run is on and the installed versions — or exit:
    the smoke has no meaning anywhere but on a TPU whose peaks are
    known."""
    import importlib.metadata as md

    from lua_mapreduce_tpu.utils.roofline import PEAK_BF16_FLOPS

    device = describe_device()
    if device["platform"] != "tpu" or device["kind"] not in PEAK_BF16_FLOPS:
        print(f"chip_smoke.py: needs a TPU listed in utils/roofline.py, "
              f"found {device}; refusing to run", file=sys.stderr)
        raise SystemExit(2)
    return device, {p: md.version(p) for p in ("jax", "jaxlib", "libtpu")}


def result_line(device: dict) -> str:
    """The last line of standard output, printed only when every stage
    passed. What reads it accepts exactly these keys, so the run's
    summary is the line before, not part of this one."""
    return json.dumps({"ok": True, "device": device})


def main() -> int:
    from lua_mapreduce_tpu.utils.jax_env import place_compile_cache

    t0 = time.perf_counter()
    cache_dir = place_compile_cache()
    device, versions = check_device()
    print(json.dumps({"device": device, "versions": versions,
                      "compile_cache_dir": cache_dir}), flush=True)

    import jax

    from lua_mapreduce_tpu.utils.profiling import build_log
    log = build_log()
    devs = jax.devices()
    stages = []

    def run(fn, *a, **kw):
        stages.append(fn(log, *a, **kw))
        gc.collect()        # drop the stage's device buffers

    for moe in (0, MOE_EXPERTS):
        run(stage_trainer, devs, (1, 1), lm=LM, moe_experts=moe, **TRAIN)
    for variant in ("mha", "q8", "gqa"):
        run(stage_decoder, lm=LM, variant=variant, **DECODE)
    run(stage_kernels, lm=LM, train=TRAIN, decode=DECODE,
        pool_shape=POOL_SHAPE)
    for mod in ENGINE_TASKS:
        run(stage_engine, mod, iterations=ENGINE_ITERATIONS, dp=len(devs))
    if len(devs) >= 4:
        for moe in (0, MOE_EXPERTS):
            run(stage_trainer, devs, (2, 2), lm=LM, moe_experts=moe,
                name="four_chips.trainer", **TRAIN)

    first = log.rows()[:1]
    first_build_s = (round(first[0]["t0"] - log.process_start, 3)
                     if first and log.process_start is not None else None)
    print(json.dumps({
        "stages": [s["stage"] for s in stages],
        "four_chip_stages_ran": len(devs) >= 4,
        "programs_built": log.programs,
        "persistent_cache_hits": log.hits,
        "persistent_cache_misses": log.misses,
        "process_start_to_first_build_s": first_build_s,
        "compile_cache_dir": cache_dir,
        "smoke_wall_s": round(time.perf_counter() - t0, 1),
        "claim": None}), flush=True)
    print(result_line(device), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
