"""Kernel perf regression bench: Pallas vs XLA on the real chip.

The reference's native-kernel story lives in the external APRIL-ANN
CUDA toolkit (SURVEY.md §2.4); this framework's equivalents are the
Pallas ops (ops/) plus the C++ shuffle merge (core/native/). Their
claimed wins must reproduce from a committed artifact, not commit
messages (VERDICT r1 item 7) — this script times every hot op across
BASELINE.json-relevant shapes and writes
benchmarks/results/kernels.json.

Usage: python benchmarks/kernel_bench.py
"""

from __future__ import annotations

import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

RESULTS = os.path.join(REPO, "benchmarks", "results", "kernels.json")


def best_of(fn, reps: int = 5) -> float:
    """Best wall time of ``fn`` (which must block on completion)."""
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    return best


_overhead_cache: dict = {}


def _call_overhead() -> float:
    """Fixed cost of ONE jitted-call round trip (dispatch + d2h fetch
    of one float), measured on a trivial op. It can exceed a single
    op's time, so it is measured and subtracted, never amortized away
    by a fixed divisor (the first version of this bench divided by
    inner=8 and reported an ~8.7 ms "time" for every op regardless of
    FLOP count: pure overhead)."""
    if "s" not in _overhead_cache:
        import jax
        import jax.numpy as jnp

        x = jnp.zeros((8, 128), jnp.float32)
        f = jax.jit(lambda x: x.sum())
        float(f(x))                                   # compile + warm
        _overhead_cache["s"] = best_of(lambda: float(f(x)), reps=9)
    return _overhead_cache["s"]


def _bench_pair(make, target_s: float = 0.35) -> dict:
    """Time one op both ways; returns {pallas_ms, xla_ms, speedup, ...}.

    Measurement discipline:
    - operands are jit ARGUMENTS, never closed over — a closed-over array
      bakes into the HLO as a multi-MB constant;
    - each measurement runs the op ``inner`` times under ``lax.scan``
      and fetches ONE float, so one dispatch is amortized over many
      executions and the fetch is the wait for the result;
    - re-running the op on identical operands inside scan would let XLA
      hoist it out of the loop, so the smallest operand is perturbed by a
      loop-carried epsilon (``acc * 1e-30``, dynamically zero after the
      cast but unprovable at compile time) — the op re-executes every
      iteration at the cost of one tiny elementwise add;
    - consuming a STATICALLY-indexed output element lets XLA dead-code-
      eliminate the rest of the op (a conv whose only consumer is
      ``r[0,0,0,0]`` compiles to one dot product — an earlier run of this
      bench "measured" 16,461 TF/s for XLA conv that way, 83× over chip
      peak), and even a DYNAMICALLY-indexed element can be pushed through
      dots by the algebraic simplifier (observed: "347 TF/s" XLA flash
      attention, 1.8× peak, vs 4.6 ms when fully consumed). So the body
      consumes the dynamic element PLUS the full ``sum()`` scaled by an
      un-foldable dynamic 1e-30 — every output element feeds the carry,
      nothing can be sliced away (Pallas calls are opaque custom calls
      XLA can't DCE into, so these flaws had inflated only the XLA side);
    - ``inner`` is additionally capped so the call can't claim more than
      ~2× peak-rate compute, and any per-op result implying > 1.1× chip
      peak is flagged ``suspect_elided`` rather than trusted; FLOP-less
      ops (softmax, pool) get the same check against the MEMORY roofline
      instead — finishing faster than reading the inputs once at HBM
      bandwidth is equally impossible;
    - ``inner`` is calibrated per op so net on-device time ≈ ``target_s``
      (two-phase: probe at inner=8, rescale), and the measured fixed
      call overhead is subtracted: per-op = (dt − overhead) / inner.
    """
    import jax
    import jax.numpy as jnp
    from jax import lax

    from lua_mapreduce_tpu.utils.roofline import (peak_flops_per_s,
                                                  peak_hbm_bytes_per_s)

    run_pallas, run_xla, args, flops = make()
    overhead = _call_overhead()
    peak = peak_flops_per_s()
    hbm_bw = peak_hbm_bytes_per_s()
    in_bytes = sum(a.nbytes for a in args)
    i0 = min(range(len(args)), key=lambda i: args[i].nbytes)
    # an op can't legitimately run faster than peak: bound the iteration
    # count so a (mis-compiled-to-nothing) loop can't calibrate to
    # absurd lengths, and anything still implying > 1.1× peak is flagged.
    # FLOP-less ops bound against the memory roofline (inputs read once).
    inner_cap = 16384
    if flops:
        inner_cap = min(inner_cap,
                        max(16, int(2.0 * target_s * peak / flops)))
    elif hbm_bw:
        inner_cap = min(inner_cap,
                        max(16, int(2.0 * target_s * hbm_bw / in_bytes)))
    out = {"call_overhead_ms": round(overhead * 1e3, 2)}
    per_op_s = {}
    for name, run in (("pallas", run_pallas), ("xla", run_xla)):
        per_op, inner = _measure_op(run, args, i0, inner_cap, target_s,
                                    overhead)
        per_op_s[name] = per_op
        out[f"{name}_ms"] = round(per_op * 1e3, 4)
        out[f"{name}_inner_iters"] = inner
        if flops:
            out[f"{name}_tflops"] = round(flops / per_op / 1e12, 2)
            if flops / per_op > 1.1 * peak:
                out[f"{name}_suspect_elided"] = True
        elif hbm_bw and in_bytes / per_op > 1.1 * hbm_bw:
            out[f"{name}_suspect_elided"] = True
    # speedup from the unrounded seconds: an op faster than the 4-decimal
    # ms rounding (~0.05 µs) must not silently drop the key
    out["speedup_pallas_vs_xla"] = round(
        per_op_s["xla"] / per_op_s["pallas"], 3)
    return out


def _measure_op(run, args, i0: int, inner_cap: int, target_s: float,
                overhead: float):
    """(per_op_seconds, inner) for one op — the SINGLE implementation of
    the measurement discipline (matmul_tune.py reuses it; an earlier
    hand-rolled copy there is how elided numbers slipped through once).

    Calibration grows ``inner`` geometrically over a few rounds instead
    of one rescale: a single noise trough at the probe (dt under
    the cached overhead → net ≤ 0) would otherwise floor the estimate
    and explode ``inner`` straight to the cap."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    def make_loop(inner):
        def loop(*a):
            def body(acc, _):
                eps = (acc * 1e-30).astype(a[i0].dtype)
                pert = tuple(x + eps if i == i0 else x
                             for i, x in enumerate(a))
                r = run(*pert).ravel()
                idx = jnp.abs(acc.astype(jnp.int32)) % r.shape[0]
                full = (r.sum().astype(jnp.float32) *
                        (acc * 1e-30 + 1e-30))
                return acc + r[idx].astype(jnp.float32) + full, None
            return lax.scan(body, jnp.float32(0), None, length=inner)[0]
        return jax.jit(loop)

    inner = 8
    for _ in range(4):
        jitted = make_loop(inner)
        float(jitted(*args))                          # compile + warm
        dt = best_of(lambda: float(jitted(*args)))
        net, measured_inner = dt - overhead, inner    # a matched pair —
        # per_op must divide net by the inner it was MEASURED at, never
        # by a post-growth inner the loop prepared but didn't time
        if net >= 0.6 * target_s or inner >= inner_cap:
            break
        # growth factor from the estimate, but never more than 16× per
        # round — a noise-negative net can't overshoot the whole budget
        grow = min(16.0, target_s / max(net, 0.1 * overhead, 1e-4))
        inner = int(min(inner_cap, max(inner + 1, inner * grow)))
    return max(net, 1e-9) / measured_inner, measured_inner


def bench_matmul(m, k, n, dtype):
    import jax
    import jax.numpy as jnp

    from lua_mapreduce_tpu import ops

    def make():
        a = jax.random.normal(jax.random.PRNGKey(0), (m, k), dtype)
        b = jax.random.normal(jax.random.PRNGKey(1), (k, n), dtype)
        return (lambda a, b: ops.matmul(a, b, backend="pallas"),
                lambda a, b: ops.matmul(a, b, backend="xla"),
                (a, b), 2.0 * m * k * n)
    return _bench_pair(make)


def bench_conv2d(n, h, w, cin, cout, kh, stride, dtype):
    import jax
    import jax.numpy as jnp

    from lua_mapreduce_tpu import ops

    def make():
        x = jax.random.normal(jax.random.PRNGKey(0), (n, h, w, cin), dtype)
        wt = jax.random.normal(jax.random.PRNGKey(1), (kh, kh, cin, cout),
                               dtype)
        ho = wo = (h - kh) // stride + 1
        flops = 2.0 * n * ho * wo * kh * kh * cin * cout
        return (lambda x, wt: ops.conv2d(x, wt, stride=stride,
                                         backend="pallas"),
                lambda x, wt: ops.conv2d(x, wt, stride=stride,
                                         backend="xla"),
                (x, wt), flops)
    return _bench_pair(make)


def bench_flash(b, heads, seq, d, causal, dtype):
    import jax

    from lua_mapreduce_tpu import ops

    def make():
        # layout is (B, L, H, D) — flash_attention's contract. An earlier
        # revision built (B, H, L, D), silently benchmarking seq-len-8
        # attention with thousands of heads while counting seq² FLOPs
        # (256× overcount); the near-identical s2048/s4096 timings in the
        # resulting artifact were the tell.
        q = jax.random.normal(jax.random.PRNGKey(0), (b, seq, heads, d),
                              dtype)
        k = jax.random.normal(jax.random.PRNGKey(1), (b, seq, heads, d),
                              dtype)
        v = jax.random.normal(jax.random.PRNGKey(2), (b, seq, heads, d),
                              dtype)
        flops = 4.0 * b * heads * seq * seq * d * (0.5 if causal else 1.0)
        return (lambda q, k, v: ops.flash_attention(q, k, v, causal=causal,
                                                    backend="pallas"),
                lambda q, k, v: ops.flash_attention(q, k, v, causal=causal,
                                                    backend="xla"),
                (q, k, v), flops)
    return _bench_pair(make)


def bench_flash_grad(b, heads, seq, d, causal, dtype):
    """Fwd+bwd through flash attention — the training path. Pallas side
    runs the fused FlashAttention-2 backward (ops/attention.py
    _flash_bwd_pallas); XLA side differentiates the reference
    composition (materializes (L, L) both directions). FLOPs: fwd
    4·L²·d/head + bwd 10·L²·d/head (s recompute, dp, dq, dk, dv) =
    3.5× forward, halved when causal."""
    import jax
    import jax.numpy as jnp

    from lua_mapreduce_tpu import ops

    def make():
        q = jax.random.normal(jax.random.PRNGKey(0), (b, seq, heads, d),
                              dtype)
        k = jax.random.normal(jax.random.PRNGKey(1), (b, seq, heads, d),
                              dtype)
        v = jax.random.normal(jax.random.PRNGKey(2), (b, seq, heads, d),
                              dtype)

        def grad_fn(backend):
            def loss(q, k, v):
                out = ops.flash_attention(q, k, v, causal=causal,
                                          backend=backend)
                return jnp.sum(out.astype(jnp.float32) ** 2)

            def run(q, k, v):
                g = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
                # one consumable array for the measurement harness
                return sum(x.astype(jnp.float32).sum() for x in g
                           ).reshape(1)
            return run

        flops = (14.0 * b * heads * seq * seq * d *
                 (0.5 if causal else 1.0))
        return grad_fn("pallas"), grad_fn("xla"), (q, k, v), flops
    return _bench_pair(make)


def bench_flash_grad_error(b=2, heads=8, seq=2048, d=128):
    """bf16 training-gradient error of the fused backward vs the XLA
    oracle ON CHIP (ADVICE r3: the return_lse backward runs its dp/dv
    dots in q.dtype — the MXU tradeoff the docstring documents; this
    pins its actual size where the MXU does the rounding, not the CPU
    emulation). Error is relative to the f32 oracle grads' scale."""
    import jax
    import jax.numpy as jnp

    from lua_mapreduce_tpu import ops

    ks = jax.random.split(jax.random.PRNGKey(7), 3)
    q, k, v = (jax.random.normal(kk, (b, seq, heads, d), jnp.bfloat16)
               for kk in ks)

    def loss(q, k, v, backend):
        o, lse = ops.flash_attention(q, k, v, causal=True,
                                     return_lse=True, backend=backend)
        return (jnp.sum(o.astype(jnp.float32) ** 2)
                + 0.1 * jnp.sum(lse))

    out = {}
    import functools as ft
    gp = jax.jit(jax.grad(ft.partial(loss, backend="pallas"),
                          argnums=(0, 1, 2)))(q, k, v)
    gx = jax.jit(jax.grad(ft.partial(loss, backend="xla"),
                          argnums=(0, 1, 2)))(q, k, v)
    import numpy as np
    for name, a_, b_ in zip(("dq", "dk", "dv"), gp, gx):
        a_ = np.asarray(a_, np.float64)
        b_ = np.asarray(b_, np.float64)
        scale = max(float(np.abs(b_).max()), 1e-30)
        out[f"{name}_max_rel_err"] = round(
            float(np.abs(a_ - b_).max()) / scale, 6)
        out[f"{name}_mean_rel_err"] = round(
            float(np.abs(a_ - b_).mean()) / scale, 8)
    out["config"] = f"b{b} h{heads} L{seq} d{d} bf16 causal lse"
    return out


def bench_q8_matmul(m, k, n):
    """Weight-only int8 matmul at decode shapes (ops/q8.py): the pallas
    kernel streams int8 weight tiles; the XLA side is the bf16 matmul it
    replaces (the serving baseline), so speedup_pallas_vs_xla IS the
    weight-traffic win at memory-bound shapes (ideal ≈ 2×)."""
    import jax
    import jax.numpy as jnp

    from lua_mapreduce_tpu import ops

    def make():
        kx, kw = jax.random.split(jax.random.PRNGKey(0))
        x = jax.random.normal(kx, (m, k), jnp.bfloat16)
        w = jax.random.normal(kw, (k, n), jnp.float32)
        q, s = ops.quantize_q8(w)
        wb = w.astype(jnp.bfloat16)
        sv = s.reshape(-1)
        flops = 2.0 * m * k * n
        return (lambda x, q, sv, wb: ops.q8_matmul(x, q, sv,
                                                   backend="pallas"),
                lambda x, q, sv, wb: (x @ wb),
                (x, q, sv, wb), flops)

    return _bench_pair(make)


def bench_softmax(rows, cols, dtype, block_rows=256):
    # block_rows * cols * dtype must fit scoped VMEM (16MB on v5e);
    # vocab-wide rows (32k) need a shorter block
    import jax

    from lua_mapreduce_tpu import ops

    def make():
        x = jax.random.normal(jax.random.PRNGKey(0), (rows, cols), dtype)
        return (lambda x: ops.log_softmax(x, backend="pallas",
                                          block_rows=block_rows),
                lambda x: ops.log_softmax(x, backend="xla"),
                (x,), None)
    return _bench_pair(make)


def bench_pool(n, h, w, c, dtype):
    import jax

    from lua_mapreduce_tpu import ops

    def make():
        x = jax.random.normal(jax.random.PRNGKey(0), (n, h, w, c), dtype)
        return (lambda x: ops.maxpool2d(x, 2, backend="pallas"),
                lambda x: ops.maxpool2d(x, 2, backend="xla"),
                (x,), None)
    return _bench_pair(make)


def bench_transformer_step(d_model=1024, n_heads=16, n_layers=8,
                           d_ff=4096, vocab=32768, seq=2048, batch=8,
                           steps=10, modern=False, moe_experts=0) -> dict:
    """Whole-train-step bench for the long-context model family: the
    framework's own LM train step (flash attention on the device-local
    path, fused grad all-reduce, optimizer) scanned ``steps`` times in
    ONE jitted call on a 1-device mesh, bf16 params. Reports ms/step,
    tokens/sec, and MFU from models/transformer.flops_per_token — the
    training-loop counterpart of the per-op numbers above.

    ``modern=True`` runs the llama_style recipe (rope + rms + swiglu +
    4:1 GQA) — the architecture most serving stacks actually train."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax
    from jax import lax
    from jax.sharding import Mesh

    from lua_mapreduce_tpu.models import transformer as tfm
    from lua_mapreduce_tpu.utils.roofline import mfu

    kw = dict(vocab=vocab, d_model=d_model, n_heads=n_heads,
              n_layers=n_layers, d_ff=d_ff, max_seq=seq)
    if moe_experts:
        # switch-routed MoE FFNs; capacity = 2x the even-routing share
        # of the device tile (the whole batch on one chip)
        kw.update(moe_experts=moe_experts,
                  moe_capacity=2 * batch * seq // moe_experts)
    cfg = (tfm.TransformerConfig.llama_style(n_kv_heads=n_heads // 4,
                                             **kw)
           if modern else tfm.TransformerConfig(**kw))
    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1), ("dp", "sp"))
    params = jax.tree.map(lambda x: x.astype(jnp.bfloat16),
                          tfm.init_transformer(jax.random.PRNGKey(0), cfg))
    if moe_experts:
        params = tfm.shard_params_moe(params, mesh)
    opt = optax.sgd(1e-3, momentum=0.9)
    step = tfm.make_train_step(cfg, mesh, opt, attn="ring")
    rng = np.random.RandomState(0)
    seq_arr = rng.randint(0, vocab, (batch, seq + 1))
    tokens = jnp.asarray(seq_arr[:, :-1], jnp.int32)
    targets = jnp.asarray(seq_arr[:, 1:], jnp.int32)

    # params evolve through the scan carry — real data dependency per
    # step, nothing for the compiler to hoist or elide
    def epoch(params, opt_state, tokens, targets):
        def body(c, _):
            p, o = c
            p, o, loss = step(p, o, tokens, targets)
            return (p, o), loss
        (p, o), losses = lax.scan(body, (params, opt_state), None,
                                  length=steps)
        return losses.astype(jnp.float32).sum()

    jitted = jax.jit(epoch)
    opt_state = opt.init(params)
    float(jitted(params, opt_state, tokens, targets))   # compile + warm
    dt = best_of(lambda: float(jitted(params, opt_state, tokens,
                                      targets)))
    per_step = (dt - _call_overhead()) / steps
    tok = batch * seq
    model_flops = tok * tfm.flops_per_token(cfg, seq)
    return {
        "config": (f"d{d_model} h{n_heads} L{n_layers} ff{d_ff} "
                   f"v{vocab} seq{seq} b{batch} bf16 ring+flash"
                   + (" llama-style(rope+rms+swiglu+gqa4:1)"
                      if modern else "")
                   + (f" switch-moe{moe_experts}x(cap2x)"
                      if moe_experts else "")),
        "ms_per_step": round(per_step * 1e3, 2),
        "tokens_per_sec": round(tok / per_step, 1),
        "mfu": round(mfu(model_flops, per_step), 4),
        "tflops_per_s": round(model_flops / per_step / 1e12, 2),
    }


def bench_conv_train(model: str, batch: int, steps: int = 10) -> dict:
    """End-to-end conv TRAINING bench (BASELINE.json configs 3-4,
    VERDICT r2 item 3): the framework's own DP-trainer hot loop
    (``run_steps``: loss/grad/optimizer scanned ``steps`` times inside
    ONE jitted call, batch device-resident) on LeNet-5/CIFAR-10 or
    ResNet-18 (CIFAR and ImageNet stems), bf16 params. Reports ms/step,
    images/sec, and MFU via the model's ``flops_per_example`` — the
    reference publishes per-workload wall-clock tables
    (/root/reference/README.md:43-113); these are the conv rows."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from lua_mapreduce_tpu.parallel.mesh import make_mesh
    from lua_mapreduce_tpu.train.harness import (DataParallelTrainer,
                                                 TrainConfig)
    from lua_mapreduce_tpu.utils.roofline import mfu

    if model == "lenet5_cifar":
        from lua_mapreduce_tpu.models import lenet
        shape = lenet.CIFAR_SHAPE
        params = lenet.init_lenet(jax.random.PRNGKey(0), shape,
                                  dtype=jnp.bfloat16)
        loss_fn = lenet.nll_loss
        per_ex = lenet.flops_per_example(shape)
        n_classes = lenet.N_CLASSES
    elif model.startswith("resnet18_im") or model == "resnet18_cifar":
        from lua_mapreduce_tpu.models import resnet
        if model == "resnet18_cifar":
            cfg = resnet.ResNetConfig.cifar18()
        elif model == "resnet18_imagenet":
            cfg = resnet.ResNetConfig.imagenet18()
        else:
            # ImageNet-shape canaries: the July 2026 windows could not
            # compile the full 224x224 program; these walk the spatial
            # size toward 224
            side = int(model.removeprefix("resnet18_im"))
            cfg = resnet.ResNetConfig(input_shape=(side, side, 3),
                                      n_classes=1000)
        shape = cfg.input_shape
        params = resnet.init_resnet(jax.random.PRNGKey(0), cfg,
                                    dtype=jnp.bfloat16)
        loss_fn = resnet.make_loss(cfg)
        per_ex = resnet.flops_per_example(cfg)
        n_classes = cfg.n_classes
    else:
        raise ValueError(f"unknown conv bench model {model!r}")

    devices = jax.devices()
    n_chips = len(devices)
    mesh = make_mesh(dp=n_chips, mp=1, devices=devices)
    tr = DataParallelTrainer(loss_fn, params, mesh,
                             TrainConfig(batch_size=batch))
    # batch generated on device: bf16 host arrays don't exist in numpy
    # and the h2d is not part of the hot loop
    x = jax.random.normal(jax.random.PRNGKey(1),
                          (batch * n_chips, *shape), jnp.bfloat16)
    y = jax.random.randint(jax.random.PRNGKey(2),
                           (batch * n_chips,), 0, n_classes)

    np.asarray(tr.run_steps(x, y, steps))           # compile + warm
    dt = best_of(lambda: np.asarray(tr.run_steps(x, y, steps)), reps=3)
    per_step = (dt - _call_overhead()) / steps
    images = batch * n_chips
    model_flops = images * per_ex
    return {
        "config": f"{model} b{batch} bf16 {steps}-step fused scan",
        "ms_per_step": round(per_step * 1e3, 2),
        "images_per_sec": round(images / per_step, 1),
        "mfu": round(mfu(model_flops, per_step, n_chips), 4),
        "tflops_per_s_per_chip": round(
            model_flops / per_step / n_chips / 1e12, 2),
    }


def bench_decode(d_model=1024, n_heads=16, n_layers=8, d_ff=4096,
                 vocab=32768, max_seq=4096, prompt_len=3968, n_new=128,
                 batch=4, quantized=False, kv_q8=False,
                 kv_heads=0) -> dict:
    """LM inference bench: long-prompt generation, prefill vs the
    from-scratch position scan. Reports prompt-ingestion speedup and
    decode tokens/sec — the serving-side counterpart of
    bench_transformer_step (training) for the same model family.
    ``quantized=True`` serves through the weight-only int8 copy
    (transformer.quantize_lm → ops/q8.py kernel): same contract, half
    the weight traffic in the matvec-bound decode tail. ``kv_q8``
    additionally stores the KV cache int8 (ops/decode.quantize_kv) —
    together they are the full int8 serving configuration."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from lua_mapreduce_tpu.models import transformer as tfm

    cfg = tfm.TransformerConfig(vocab=vocab, d_model=d_model,
                                n_heads=n_heads, n_layers=n_layers,
                                d_ff=d_ff, max_seq=max_seq,
                                n_kv_heads=kv_heads)
    params = jax.tree.map(
        lambda x: x.astype(jnp.bfloat16),
        tfm.init_transformer(jax.random.PRNGKey(0), cfg))
    if quantized:
        params = tfm.quantize_lm(params)
    rng = np.random.RandomState(0)
    prompt = jnp.asarray(rng.randint(0, vocab, (batch, prompt_len)),
                         jnp.int32)

    def run(use_prefill):
        out = tfm.greedy_decode(params, prompt, n_new, cfg=cfg,
                                use_prefill=use_prefill, kv_q8=kv_q8)
        return np.asarray(out)

    def run_prefill_only():
        c, lg = tfm.prefill(params, prompt, cfg=cfg,
                            total=prompt_len + n_new)
        return np.asarray(lg)

    run(True)                                       # compile + warm
    dt_pre = best_of(lambda: run(True), reps=3) - _call_overhead()
    run(False)
    dt_scan = best_of(lambda: run(False), reps=3) - _call_overhead()
    run_prefill_only()
    dt_ingest = best_of(run_prefill_only, reps=3) - _call_overhead()
    toks = batch * n_new
    # decode rate = generated tokens over the post-ingestion tail; the
    # end-to-end rate includes prompt ingestion and so shifts with
    # prompt_len by construction (labeled accordingly)
    decode_tail = max(dt_pre - dt_ingest, 1e-9)
    return {
        "config": (f"d{d_model} h{n_heads} L{n_layers} v{vocab} "
                   f"prompt{prompt_len} new{n_new} b{batch} bf16"
                   + (f" gqa{n_heads//kv_heads}:1" if kv_heads else "")
                   + (" w-int8" if quantized else "")
                   + (" kv-int8" if kv_q8 else "")),
        "prefill_total_s": round(dt_pre, 3),
        "scan_total_s": round(dt_scan, 3),
        "prompt_ingest_s": round(dt_ingest, 3),
        "speedup_prefill_vs_scan": round(dt_scan / dt_pre, 2),
        "decode_tokens_per_sec": round(toks / decode_tail, 1),
        "end_to_end_tokens_per_sec": round(toks / dt_pre, 1),
    }


def bench_native_merge(n_runs=16, keys_per_run=50_000) -> dict:
    """C++ single-pass shuffle merge vs the Python heap merge (the
    luamongo/mongo-cxx role, SURVEY.md §2.4)."""
    import tempfile

    from lua_mapreduce_tpu.core import native_merge
    from lua_mapreduce_tpu.core.merge import merge_iterator
    from lua_mapreduce_tpu.core.serialize import dump_record
    from lua_mapreduce_tpu.store.sharedfs import SharedStore

    if not native_merge.native_available():
        return {"skipped": "native merge unavailable (no g++?)"}
    d = tempfile.mkdtemp(prefix="kbench-merge")
    store = SharedStore(d)
    names = []
    for r in range(n_runs):
        b = store.builder()
        for i in range(keys_per_run):
            b.write(dump_record(f"w{r:02d}{i:06d}", [1]) + "\n")
        b.build(f"run.{r}")
        names.append(f"run.{r}")

    t0 = time.perf_counter()
    n_py = sum(1 for _ in merge_iterator(store, names))
    py_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    n_nat = sum(1 for _ in native_merge.native_merge_records(store, names))
    nat_s = time.perf_counter() - t0
    assert n_py == n_nat == n_runs * keys_per_run

    # whole-reduce-job comparison for a native_reduce="sum" ACI reducer.
    # THREE rungs, honestly labeled: the fused C++ pass, the engine's
    # actual fallback on this store (C++ merge + Python stream + Python
    # fold), and the pure-Python path (what a non-local store would run).
    out = SharedStore(d + "-out")
    t0 = time.perf_counter()
    ok = native_merge.native_merge_reduce_sum(store, names, out, "res.P0")
    fused_s = time.perf_counter() - t0
    assert ok
    t0 = time.perf_counter()
    b = out.builder()
    for k, vs in native_merge.native_merge_records(store, names):
        b.write(dump_record(k, [sum(vs)]) + "\n")
    b.build("res.fb")
    fallback_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    b = out.builder()
    for k, vs in merge_iterator(store, names):
        b.write(dump_record(k, [sum(vs)]) + "\n")
    b.build("res.py")
    pyred_s = time.perf_counter() - t0
    assert ("".join(out.lines("res.P0")) == "".join(out.lines("res.py"))
            == "".join(out.lines("res.fb")))

    return {"python_s": round(py_s, 3), "native_s": round(nat_s, 3),
            "speedup_native_vs_python": round(py_s / nat_s, 2),
            "reduce_job_pure_python_s": round(pyred_s, 3),
            "reduce_job_engine_fallback_s": round(fallback_s, 3),
            "reduce_job_fused_native_s": round(fused_s, 3),
            "speedup_fused_vs_engine_fallback": round(fallback_s / fused_s,
                                                      2),
            "speedup_fused_vs_pure_python": round(pyred_s / fused_s, 2),
            "records": n_py}


def main() -> None:
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="",
                    help="comma-separated substrings: run only matching "
                         "cases and MERGE into the existing kernels.json "
                         "(for re-running entries after a kernel fix "
                         "without repeating the whole bench)")
    args = ap.parse_args()
    only = [s for s in args.only.split(",") if s]

    from lua_mapreduce_tpu.utils.jax_env import (place_compile_cache,
                                                 require_tpu)
    place_compile_cache()
    require_tpu("kernel_bench.py")

    import jax
    import jax.numpy as jnp

    results = {}
    if only and os.path.exists(RESULTS):
        with open(RESULTS) as f:
            results = json.load(f)
    results["device_kind"] = jax.devices()[0].device_kind
    if not only or any(s in "native_merge_16x50k" for s in only):
        results["native_merge_16x50k"] = bench_native_merge()
    failed = []
    bf16 = jnp.bfloat16
    cases = {
        # MXU-scale matmuls (the APRIL-ANN axpy/matrix role)
        "matmul_1024_bf16": lambda: bench_matmul(1024, 1024, 1024, bf16),
        "matmul_4096_bf16": lambda: bench_matmul(4096, 4096, 4096, bf16),
        "matmul_8192_bf16": lambda: bench_matmul(8192, 8192, 8192, bf16),
        # LeNet-5/CIFAR-10 body conv (BASELINE.json config 3)
        "conv_lenet_c1_b256": lambda: bench_conv2d(256, 32, 32, 3, 32,
                                                   5, 1, bf16),
        # ResNet-18 block conv at 56x56 (BASELINE.json config 4)
        "conv_resnet_56_b64": lambda: bench_conv2d(64, 56, 56, 64, 64,
                                                   3, 1, bf16),
        # transformer attention (long-context path)
        "flash_s2048_h8_d128_causal": lambda: bench_flash(
            4, 8, 2048, 128, True, bf16),
        "flash_s4096_h8_d128_causal": lambda: bench_flash(
            2, 8, 4096, 128, True, bf16),
        # book-length context: XLA's composition holds ~4 GiB of
        # L² temps here (attn_memory.json) — the shape class the
        # kernel exists for
        "flash_s8192_h8_d128_causal": lambda: bench_flash(
            1, 8, 8192, 128, True, bf16),
        # training path: fused Pallas backward vs XLA's O(L²) VJP
        "flash_grad_s2048_h8_d128_causal": lambda: bench_flash_grad(
            4, 8, 2048, 128, True, bf16),
        # numeric, not timing: bf16 grad error of the fused
        # backward vs the f32-dot oracle, measured where the MXU
        # rounds (ADVICE r3 item 3)
        "flash_grad_bf16_error": bench_flash_grad_error,
        # vocab-wide rows need short blocks to fit scoped VMEM
        "log_softmax_8192x32768": lambda: bench_softmax(
            8192, 32768, bf16, block_rows=64),
        # weight-only int8 at decode matvec shapes (ops/q8.py):
        # batch-8 tokens against an LM FFN weight
        "q8_matvec_b8_4096x16384": lambda: bench_q8_matmul(
            8, 4096, 16384),
        "maxpool_b256_64x64x32": lambda: bench_pool(256, 64, 64, 32,
                                                    bf16),
        # whole-train-step: the long-context LM family end to end
        "transformer_step_d1024_L8_s2048": bench_transformer_step,
        "transformer_step_llama_style": lambda: bench_transformer_step(
            modern=True),
        # expert-parallel family on-chip (dp=1: experts all local,
        # the routing/capacity machinery still in the hot loop)
        "transformer_step_moe8": lambda: bench_transformer_step(
            moe_experts=8),
        # double the context, same tokens/step: the attention share
        # of the step doubles — the regime flash's 9.7x-at-L=4096
        # advantage feeds straight into MFU
        "transformer_step_s4096": lambda: bench_transformer_step(
            modern=True, seq=4096, batch=4),
        # inference: long-prompt prefill vs from-scratch scan
        "decode_prompt3968_new128": bench_decode,
        # the int8 serving copy of the same model (q8 kernel in
        # every projection + the tied head): the decode tail is
        # weight-traffic bound, so this is where q8's halved HBM
        # bytes should show up end to end
        # int8 weights AND int8 KV cache — the full int8 serving
        # config (the earlier decode_..._q8 key measured weights
        # only; renamed so results stay comparable across runs)
        "decode_prompt3968_new128_q8wkv": lambda: bench_decode(
            quantized=True, kv_q8=True),
        # GQA serving (DESIGN 13 remedy 1): 4:1 grouping reads a
        # quarter of the cache per step
        "decode_prompt3968_new128_gqa4": lambda: bench_decode(
            kv_heads=4),
        # end-to-end conv training (BASELINE configs 3-4)
        "lenet5_cifar_train_b1024": lambda: bench_conv_train(
            "lenet5_cifar", 1024),
        "resnet18_cifar_train_b256": lambda: bench_conv_train(
            "resnet18_cifar", 256),
        "resnet18_imagenet_train_b32": lambda: bench_conv_train(
            "resnet18_imagenet", 32, steps=5),
        # spatial-size canaries toward 224 (VERDICT r4 next-3): the
        # largest compiling one stands in for the ImageNet number
        "resnet18_im112_train_b32": lambda: bench_conv_train(
            "resnet18_im112", 32, steps=5),
        "resnet18_im160_train_b32": lambda: bench_conv_train(
            "resnet18_im160", 32, steps=5),
        "resnet18_im176_train_b32": lambda: bench_conv_train(
            "resnet18_im176", 32, steps=5),
        "resnet18_im192_train_b32": lambda: bench_conv_train(
            "resnet18_im192", 32, steps=5),
        # 224 with the smallest program we can emit (b=8, single
        # un-scanned step), next to the b32/steps=5 entry
        "resnet18_imagenet_train_b8_s1": lambda: bench_conv_train(
            "resnet18_imagenet", 8, steps=1),
    }
    for name, fn in cases.items():
        if only and not any(s in name for s in only):
            continue
        try:
            results[name] = fn()
        except Exception as e:   # noqa: BLE001 — record, bench the
            #                      rest, then fail the run below
            results[name] = {"error": f"{type(e).__name__}: {e}"[:300]}
            failed.append(name)
        print(f"{name}: {results[name]}", file=sys.stderr)
    print(json.dumps(results, indent=1))
    os.makedirs(os.path.dirname(RESULTS), exist_ok=True)
    with open(RESULTS, "w") as f:
        json.dump(results, f, indent=1)
        f.write("\n")
    if failed:
        raise SystemExit(f"kernel_bench.py: {len(failed)} case(s) failed: "
                         f"{', '.join(failed)}")


if __name__ == "__main__":
    main()
