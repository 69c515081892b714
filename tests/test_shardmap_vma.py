"""Every Pallas kernel must be callable from INSIDE ``jax.shard_map``.

Round 4's second hardware window exposed the gap: JAX 0.9 types values
inside shard_map with varying-mesh-axes (vma) sets and rejects any
``pallas_call`` whose out_shape is a plain ``ShapeDtypeStruct`` —
exactly how every sharded train step (the DP/TP/SP paths of
models/transformer.py and train/harness.py) invokes the kernels on TPU,
where the auto policy routes attention/pool/q8 to Pallas. The CPU suite
never saw it because off-TPU the policy resolves everything to "xla".
The fix is ``ops.out_struct`` propagating operand vma into the kernel's
output type.

Two kinds of regression here:

- **Lowering**: ``jax.export`` for the TPU platform over an
  ``AbstractMesh`` runs trace + Mosaic lowering of the kernel inside
  shard_map from a CPU-only host — the exact program shape that failed
  on the chip (vma check fires at trace time).
- **Numerics**: the flash kernels also EXECUTE inside a CPU-mesh
  shard_map in interpret mode, golden-diffed against the XLA oracle
  (SURVEY.md §4's golden-diff discipline at the kernel layer). The
  other kernels cannot: JAX 0.9's pallas HLO interpreter is itself not
  vma-aware when a kernel mixes varying operands with replicated or
  index values (its internal dynamic_slice trips the same check — an
  upstream limitation, not a kernel bug), so their in-shard_map
  coverage is lowering-only; interpret-mode parity OUTSIDE shard_map
  owns their numerics (tests/test_ops.py).
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import AbstractMesh, Mesh, PartitionSpec as P

from lua_mapreduce_tpu import ops



def _abstract_mesh():
    """AbstractMesh across the signature change: newer JAX takes
    (axis_sizes, axis_names); older JAX takes one shape_tuple of
    (name, size) pairs."""
    try:
        return AbstractMesh((4,), ("dp",))
    except TypeError:
        return AbstractMesh((("dp", 4),))


AMESH = _abstract_mesh()


def export_shardmap_tpu(f, in_specs, out_specs, *shapes):
    """Lower ``f`` inside shard_map for the TPU target from the CPU
    host; raises on any vma-typing or Mosaic legality violation."""
    g = shard_map(f, mesh=AMESH, in_specs=in_specs,
                  out_specs=out_specs)
    return jax.export.export(jax.jit(g), platforms=["tpu"])(*shapes)


def _close(a, b, tol=2e-2):
    np.testing.assert_allclose(np.asarray(a, np.float32),
                               np.asarray(b, np.float32),
                               rtol=tol, atol=tol)


class TestShardMapLowering:
    """Trace + Mosaic-lower each Pallas kernel inside shard_map."""

    def test_flash_attention_fwd(self):
        q = jax.ShapeDtypeStruct((8, 1024, 8, 128), jnp.bfloat16)
        export_shardmap_tpu(
            lambda q_, k_, v_: ops.flash_attention(
                q_, k_, v_, causal=True, backend="pallas"),
            (P("dp"), P("dp"), P("dp")), P("dp"), q, q, q)

    def test_flash_attention_grad(self):
        q = jax.ShapeDtypeStruct((8, 1024, 8, 128), jnp.bfloat16)

        def loss(q_, k_, v_):
            return ops.flash_attention(q_, k_, v_, causal=True,
                                       backend="pallas").sum()

        export_shardmap_tpu(
            jax.grad(loss, argnums=(0, 1, 2)),
            (P("dp"), P("dp"), P("dp")),
            (P("dp"), P("dp"), P("dp")), q, q, q)

    def test_matmul_replicated_rhs(self):
        """The DP-trainer shape: activations vary over dp, weights are
        replicated — pallas_call must accept mixed-vma operands."""
        a = jax.ShapeDtypeStruct((8, 256, 512), jnp.bfloat16)
        b = jax.ShapeDtypeStruct((512, 256), jnp.bfloat16)
        export_shardmap_tpu(
            lambda a_, b_: jax.vmap(lambda s: ops.matmul(
                s, b_, backend="pallas"))(a_),
            (P("dp"), P()), P("dp"), a, b)

    def test_log_softmax(self):
        x = jax.ShapeDtypeStruct((512, 1024), jnp.bfloat16)
        export_shardmap_tpu(
            lambda x_: ops.log_softmax(x_, backend="pallas"),
            (P("dp"),), P("dp"), x)

    @pytest.mark.parametrize("op", ["maxpool2d", "avgpool2d"])
    def test_pool(self, op):
        x = jax.ShapeDtypeStruct((8, 32, 32, 32), jnp.bfloat16)
        export_shardmap_tpu(
            lambda x_: getattr(ops, op)(x_, backend="pallas"),
            (P("dp"),), P("dp"), x)

    def test_q8_matmul_replicated_weights(self):
        """The quantized-decode shape: per-rank activations against
        replicated int8 weights + scales."""
        x = jax.ShapeDtypeStruct((8, 4096), jnp.bfloat16)
        q = jax.ShapeDtypeStruct((4096, 8192), jnp.int8)
        s = jax.ShapeDtypeStruct((8192,), jnp.float32)
        export_shardmap_tpu(
            lambda x_, q_, s_: ops.q8_matmul(x_, q_, s_,
                                             backend="pallas"),
            (P("dp"), P(), P()), P("dp"), x, q, s)

    def test_conv2d(self):
        x = jax.ShapeDtypeStruct((8, 32, 32, 16), jnp.bfloat16)
        w = jax.ShapeDtypeStruct((3, 3, 16, 32), jnp.bfloat16)
        export_shardmap_tpu(
            lambda x_, w_: ops.conv2d(x_, w_, backend="pallas"),
            (P("dp"), P()), P("dp"), x, w)


class TestShardMapNumerics:
    """Flash executes (interpret mode) inside a real CPU-device mesh."""

    def _mesh(self, n=4):
        return Mesh(np.array(jax.devices()[:n]), ("dp",))

    def test_flash_attention(self):
        mesh = self._mesh()
        k0 = jax.random.PRNGKey(0)
        q, k, v = (jax.random.normal(kk, (4, 256, 2, 64), jnp.float32)
                   for kk in jax.random.split(k0, 3))
        fn = jax.jit(shard_map(
            lambda q_, k_, v_: ops.flash_attention(
                q_, k_, v_, causal=True, backend="pallas_interpret"),
            mesh=mesh, in_specs=(P("dp"), P("dp"), P("dp")),
            out_specs=P("dp")))
        ref = ops.flash_attention(q, k, v, causal=True, backend="xla")
        _close(fn(q, k, v), ref)

    def test_flash_attention_grad_with_lse(self):
        """The ring-attention training path: fused backward + lse
        cotangent, per shard; batch-sharded inputs under a sum loss
        make the concatenated shard grads equal the global grads."""
        mesh = self._mesh()
        k0 = jax.random.PRNGKey(1)
        q, k, v = (jax.random.normal(kk, (4, 256, 2, 64), jnp.float32)
                   for kk in jax.random.split(k0, 3))

        def loss(q_, k_, v_, backend):
            o, lse = ops.flash_attention(q_, k_, v_, causal=True,
                                         return_lse=True,
                                         backend=backend)
            return o.sum() + 0.1 * lse.sum()

        fn = jax.jit(shard_map(
            jax.grad(functools.partial(loss,
                                       backend="pallas_interpret"),
                     argnums=(0, 1, 2)),
            mesh=mesh, in_specs=(P("dp"), P("dp"), P("dp")),
            out_specs=(P("dp"), P("dp"), P("dp"))))
        got = fn(q, k, v)
        want = jax.grad(functools.partial(loss, backend="xla"),
                        argnums=(0, 1, 2))(q, k, v)
        for g, w in zip(got, want):
            _close(g, w)


def test_out_struct_plain_context():
    """Outside shard_map the helper degrades to an ordinary struct —
    vma is empty and plain-jit callers are unaffected."""
    s = ops.out_struct((4, 8), jnp.float32)
    assert s.shape == (4, 8) and s.dtype == jnp.float32
