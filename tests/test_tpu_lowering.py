"""TPU-target lowering regression for every Pallas kernel.

Round 4's hardware window exposed that the flash kernels had NEVER
lowered on TPU: Mosaic rejects (1, block_q) row-state blocks whenever
B·H > 1, and CPU interpret mode — all the suite ran between hardware
windows — never enforces block legality. The fix is ops/attention.py's
lane-replicated row state; THIS file is the structural fix for the test
gap: ``jax.export`` runs the full TPU lowering pipeline (including
Mosaic's legality checks, verified to reproduce the exact round-3
failure) on a CPU-only host, so a kernel that cannot lower on the chip
now fails the suite on every box, between windows included.

Export stops at lowering — nothing executes, so these are fast and
numerics-free; interpret-mode parity tests elsewhere own correctness.
"""

import jax
import jax.numpy as jnp
import pytest

from lua_mapreduce_tpu import ops
from lua_mapreduce_tpu.ops.attention import _flash_pallas


def export_tpu(f, *shapes):
    """Lower ``f`` for the TPU target from the CPU host; raises on any
    Mosaic legality violation."""
    return jax.export.export(jax.jit(f), platforms=["tpu"])(*shapes)


def _q(b, l, h, d, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct((b, l, h, d), dtype)


class TestFlashLowering:
    def test_forward_causal(self):
        q = _q(2, 1024, 8, 128)
        export_tpu(lambda q, k, v: _flash_pallas(q, k, v, True), q, q, q)

    def test_forward_full(self):
        q = _q(2, 512, 4, 128)
        export_tpu(lambda q, k, v: _flash_pallas(q, k, v, False), q, q, q)

    def test_forward_gqa(self):
        q = _q(2, 512, 8, 128)
        kv = _q(2, 512, 2, 128)
        export_tpu(lambda q, k, v: _flash_pallas(q, k, v, True),
                   q, kv, kv)

    def test_forward_head_dim_64(self):
        q = _q(2, 512, 4, 64)
        export_tpu(lambda q, k, v: _flash_pallas(q, k, v, True), q, q, q)

    def test_forward_ragged_seq_padding(self):
        # odd L exercises _pad_seq + _clamp_blocks geometry on-chip
        q = _q(1, 300, 2, 128)
        export_tpu(lambda q, k, v: _flash_pallas(q, k, v, True), q, q, q)

    def test_forward_windowed_offset(self):
        q = _q(2, 512, 4, 128)
        export_tpu(lambda q, k, v: _flash_pallas(
            q, k, v, True, window=128, q_offset=64), q, q, q)

    def test_forward_with_lse(self):
        q = _q(2, 512, 4, 128)
        export_tpu(lambda q, k, v: _flash_pallas(q, k, v, True,
                                                 with_lse=True), q, q, q)

    def test_grad_both_outputs(self):
        """The training path: fused backward kernels (dq and dkv),
        lse-cotangent fold included — the exact program ring training
        runs per shard."""
        q = _q(2, 512, 8, 128)
        kv = _q(2, 512, 2, 128)

        def loss(q_, k_, v_):
            o, lse = ops.flash_attention(q_, k_, v_, causal=True,
                                         return_lse=True,
                                         backend="pallas")
            return o.sum() + lse.sum()

        export_tpu(jax.grad(loss, argnums=(0, 1, 2)), q, kv, kv)

    def test_grad_windowed(self):
        q = _q(1, 512, 4, 128)

        def loss(q_, k_, v_):
            return ops.flash_attention(q_, k_, v_, causal=True,
                                       window=128,
                                       backend="pallas").sum()

        export_tpu(jax.grad(loss, argnums=(0, 1, 2)), q, q, q)


class TestOtherKernelsLowering:
    def test_matmul_default_blocks(self):
        a = jax.ShapeDtypeStruct((1024, 1024), jnp.bfloat16)
        export_tpu(lambda a, b: ops.matmul(a, b, backend="pallas"), a, a)

    def test_matmul_wide_blocks(self):
        # the 512²-tile auto schedule (DESIGN §8) must stay legal
        a = jax.ShapeDtypeStruct((2048, 2048), jnp.bfloat16)
        export_tpu(lambda a, b: ops.matmul(a, b, backend="pallas"), a, a)

    def test_conv2d(self):
        x = jax.ShapeDtypeStruct((8, 32, 32, 16), jnp.bfloat16)
        w = jax.ShapeDtypeStruct((3, 3, 16, 32), jnp.bfloat16)
        export_tpu(lambda x, w: ops.conv2d(x, w, backend="pallas"), x, w)

    def test_maxpool(self):
        x = jax.ShapeDtypeStruct((8, 32, 32, 32), jnp.bfloat16)
        export_tpu(lambda x: ops.maxpool2d(x, backend="pallas"), x)

    def test_avgpool(self):
        x = jax.ShapeDtypeStruct((8, 32, 32, 32), jnp.bfloat16)
        export_tpu(lambda x: ops.avgpool2d(x, backend="pallas"), x)

    def test_log_softmax(self):
        x = jax.ShapeDtypeStruct((256, 1024), jnp.bfloat16)
        export_tpu(lambda x: ops.log_softmax(x, backend="pallas"), x)


def test_export_actually_enforces_block_legality():
    """Guard the guard: a deliberately illegal (1, block) row-state
    block spec must be REJECTED by the export path — if a jax upgrade
    ever stops running Mosaic legality checks under export, this test
    fails and the whole file stops meaning anything."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    def kern(x_ref, r_ref):
        r_ref[...] = x_ref[0].sum(axis=-1).reshape(1, 128)

    def f(x):
        return pl.pallas_call(
            kern,
            grid=(4, 2),
            in_specs=[pl.BlockSpec((1, 128, 128), lambda b, i: (b, i, 0),
                                   memory_space=pltpu.VMEM)],
            out_specs=pl.BlockSpec((1, 128), lambda b, i: (b, i),
                                   memory_space=pltpu.VMEM),
            out_shape=jax.ShapeDtypeStruct((4, 256), jnp.float32),
        )(x)

    x = jax.ShapeDtypeStruct((4, 256, 128), jnp.float32)
    with pytest.raises(ValueError, match="divisible by 8 and 128"):
        export_tpu(f, x)


@pytest.mark.heavy
def test_resnet18_imagenet_grad_lowers_with_tpu_policy():
    """The whole ImageNet ResNet-18 training program — Pallas maxpool
    stem included, exactly what the TPU auto policy routes — lowers
    for the TPU target from this host. Pinned because the July 2026
    hardware windows never compiled it on a chip (their remote-compile
    helper failed at any batch size): this export is the evidence that
    the program itself lowers."""
    import jax.numpy as jnp

    import lua_mapreduce_tpu.ops as ops_pkg
    from lua_mapreduce_tpu.models import resnet

    orig = ops_pkg.default_backend
    ops_pkg.default_backend = (
        lambda op=None: ops_pkg._TPU_AUTO_POLICY.get(op, "pallas"))
    try:
        cfg = resnet.ResNetConfig.imagenet18()
        params = resnet.init_resnet(jax.random.PRNGKey(0), cfg,
                                    dtype=jnp.bfloat16)
        loss_fn = resnet.make_loss(cfg)

        def step(params, x, y):
            return jax.grad(lambda p: loss_fn(p, x, y))(params)

        x = jax.ShapeDtypeStruct((8, *cfg.input_shape), jnp.bfloat16)
        y = jax.ShapeDtypeStruct((8,), jnp.int32)
        p_abs = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), params)
        export_tpu(step, p_abs, x, y)
    finally:
        ops_pkg.default_backend = orig


class TestDecodeLowering:
    """ops/decode.py's flash-decode kernel — the scalar-prefetch grid
    (dynamic dead-chunk elision) must stay Mosaic-legal at the decode
    bench shapes, MHA (g=1 q rows) and GQA alike."""

    @pytest.mark.parametrize("shape", [(4, 16, 1, 64, 4096),
                                       (4, 4, 4, 128, 4096),
                                       (2, 2, 8, 64, 300),
                                       # the two decode cells, exactly
                                       (32, 8, 4, 128, 512),
                                       (8, 8, 4, 128, 4096)])
    def test_decode_kernel(self, shape):
        from lua_mapreduce_tpu.ops.decode import _decode_pallas

        b, hkv, g, d, s_len = shape
        q = jax.ShapeDtypeStruct((b, hkv, g, d), jnp.bfloat16)
        kv = jax.ShapeDtypeStruct((b, hkv, s_len, d), jnp.bfloat16)
        t = jax.ShapeDtypeStruct((), jnp.int32)
        export_tpu(lambda q_, k_, v_, t_: _decode_pallas(q_, k_, v_, t_),
                   q, kv, kv, t)

    @pytest.mark.parametrize("shape", [(4, 16, 1, 64, 4096),
                                       (8, 8, 4, 128, 4096)])
    def test_decode_kernel_q8(self, shape):
        from lua_mapreduce_tpu.ops.decode import _decode_pallas

        b, hkv, g, d, s_len = shape
        q = jax.ShapeDtypeStruct((b, hkv, g, d), jnp.bfloat16)
        kv = jax.ShapeDtypeStruct((b, hkv, s_len, d), jnp.int8)
        sc = jax.ShapeDtypeStruct((b, hkv, s_len), jnp.float32)
        t = jax.ShapeDtypeStruct((), jnp.int32)
        export_tpu(lambda q_, k_, v_, ks_, vs_, t_: _decode_pallas(
            q_, k_, v_, t_, k_scale=ks_, v_scale=vs_),
            q, kv, kv, sc, sc, t)

    def test_decode_kernel_rolling(self):
        from lua_mapreduce_tpu.ops.decode import _decode_pallas

        q = jax.ShapeDtypeStruct((2, 4, 1, 64), jnp.bfloat16)
        kv = jax.ShapeDtypeStruct((2, 4, 512, 64), jnp.bfloat16)
        t = jax.ShapeDtypeStruct((), jnp.int32)
        export_tpu(lambda q_, k_, v_, t_: _decode_pallas(
            q_, k_, v_, t_, roll=True), q, kv, kv, t)


class TestQ8Lowering:
    def test_q8_matmul_decode_shapes(self):
        x = jax.ShapeDtypeStruct((8, 4096), jnp.bfloat16)
        q = jax.ShapeDtypeStruct((4096, 16384), jnp.int8)
        s = jax.ShapeDtypeStruct((16384,), jnp.float32)
        export_tpu(lambda x, q, s: ops.q8_matmul(x, q, s,
                                                 backend="pallas"),
                   x, q, s)

    def test_q8_matmul_ragged(self):
        x = jax.ShapeDtypeStruct((1, 300), jnp.float32)
        q = jax.ShapeDtypeStruct((300, 500), jnp.int8)
        s = jax.ShapeDtypeStruct((500,), jnp.float32)
        export_tpu(lambda x, q, s: ops.q8_matmul(x, q, s,
                                                 backend="pallas"),
                   x, q, s)


def _flash_candidates():
    from benchmarks.flash_tune import CANDIDATES
    return [(None, None)] + CANDIDATES


@pytest.mark.parametrize("blocks", _flash_candidates(), ids=str)
def test_every_flash_candidate_lowers(blocks):
    """The block-size sweep (benchmarks/flash_tune.py) runs on rare,
    short hardware windows — a Mosaic-illegal candidate would burn the
    window on compile errors. Export every candidate the tuner
    enumerates (one module-level list, so the tuner and this guard
    cannot drift) and the shape-chosen default, at the tuner's own
    geometry (GQA, window, the ring's offset hop through the lse path),
    the forward AND the gradient: all three kernels walk a
    scalar-prefetched tile table whose index maps read it."""
    from benchmarks import flash_tune as T
    bq, bk = blocks
    for b, l, q_offset, lse in T.SHAPES.values():
        q = _q(b, l, T.HEADS, T.HEAD_DIM)
        kv = _q(b, l, T.KV_HEADS, T.HEAD_DIM)

        def attend(q_, k_, v_):
            out = ops.flash_attention(
                q_, k_, v_, causal=True, backend="pallas", block_q=bq,
                block_k=bk, window=T.WINDOW, q_offset=q_offset,
                return_lse=lse)
            return out if lse else (out,)

        def loss(q_, k_, v_):
            return sum(x.astype(jnp.float32).sum()
                       for x in attend(q_, k_, v_))

        export_tpu(attend, q, kv, kv)
        export_tpu(jax.grad(loss, argnums=(0, 1, 2)), q, kv, kv)


def test_every_matmul_candidate_lowers():
    """The same guard for benchmarks/matmul_tune.py's candidates."""
    from benchmarks.matmul_tune import candidates as matmul_cands
    from lua_mapreduce_tpu.ops.matmul import _matmul_pallas

    a = jax.ShapeDtypeStruct((4096, 4096), jnp.bfloat16)
    for bm, bn, bkk in matmul_cands():
        export_tpu(lambda x, y, bm=bm, bn=bn, bkk=bkk: _matmul_pallas(
            x, y, block_m=bm, block_n=bn, block_k=bkk), a, a)


class TestSortedMoeLowering:
    """The sorted MoE routing (argsort + bincount + row gathers, the
    default impl since DESIGN §14) is pure XLA, but sort/scatter
    lowering on TPU is exactly the kind of thing a green CPU suite
    can't attest — export the fwd AND grad paths for the TPU pipeline
    the same way the Pallas kernels are."""

    def test_moe_sorted_fwd_and_grad(self):
        from lua_mapreduce_tpu.parallel import moe

        d, ff, e, cap, t = 64, 128, 8, 32, 128
        params = moe.init_moe(jax.random.PRNGKey(0), d, ff, e,
                              jnp.bfloat16)
        x = jax.ShapeDtypeStruct((t, d), jnp.bfloat16)

        def loss(params, x):
            out, aux = moe.moe_ffn_reference(params, x, capacity=cap,
                                             top_k=2, impl="sorted")
            return jnp.sum(out.astype(jnp.float32) ** 2) + aux

        export_tpu(loss, params, x)
        export_tpu(jax.grad(loss), params, x)
