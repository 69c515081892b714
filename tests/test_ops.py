"""Kernel correctness: Pallas (interpret mode on CPU) ≡ XLA reference.

The reference has no kernel tests of its own (kernels live in the
external APRIL-ANN toolkit, SURVEY.md §2.4); the framework's kernels get
the golden-diff treatment instead: every Pallas op must match its XLA
reference implementation bit-for-tolerance on the same inputs.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from lua_mapreduce_tpu import ops

RTOL = 1e-4   # K-blocked accumulation reorders float sums vs XLA
ATOL = 1e-4


def rand(*shape, seed=0, dtype=np.float32):
    return jnp.asarray(
        np.random.RandomState(seed).randn(*shape).astype(dtype))


# ---------------------------------------------------------------- matmul

@pytest.mark.parametrize("m,k,n", [
    (8, 128, 128),          # single tile
    (256, 256, 256),        # exact multi-tile
    (100, 70, 50),          # ragged → padding path
    (1, 256, 10),           # vector-ish
])
def test_matmul_matches_xla(m, k, n):
    a, b = rand(m, k, seed=1), rand(k, n, seed=2)
    want = ops.matmul(a, b, backend="xla")
    got = ops.matmul(a, b, backend="pallas_interpret", block_m=128,
                     block_n=128, block_k=128)
    np.testing.assert_allclose(got, want, rtol=RTOL, atol=ATOL)


def test_matmul_bf16_inputs_f32_accumulate():
    a = rand(64, 256, seed=3).astype(jnp.bfloat16)
    b = rand(256, 64, seed=4).astype(jnp.bfloat16)
    got = ops.matmul(a, b, backend="pallas_interpret", out_dtype=jnp.float32)
    want = jnp.dot(a, b, preferred_element_type=jnp.float32)
    np.testing.assert_allclose(got, want, rtol=1e-2, atol=1e-2)


def test_matmul_shape_mismatch():
    with pytest.raises(ValueError):
        ops.matmul(rand(4, 5), rand(6, 7), backend="pallas_interpret")


# --------------------------------------------------------------- softmax

@pytest.mark.parametrize("shape", [(4, 10), (33, 257), (2, 3, 100)])
def test_log_softmax_matches_xla(shape):
    x = rand(*shape, seed=5) * 10.0
    got = ops.log_softmax(x, backend="pallas_interpret")
    want = jax.nn.log_softmax(x, axis=-1)
    np.testing.assert_allclose(got, want, rtol=RTOL, atol=ATOL)


def test_softmax_rows_sum_to_one():
    x = rand(16, 40, seed=6) * 5.0
    got = ops.softmax(x, backend="pallas_interpret")
    want = jax.nn.softmax(x, axis=-1)
    np.testing.assert_allclose(got, want, rtol=RTOL, atol=ATOL)
    np.testing.assert_allclose(jnp.sum(got, axis=-1), 1.0, rtol=1e-5)


def test_log_softmax_extreme_values_stable():
    x = jnp.array([[1e4, -1e4, 0.0, 5.0]], jnp.float32)
    got = ops.log_softmax(x, backend="pallas_interpret")
    assert bool(jnp.all(jnp.isfinite(got)))


# ------------------------------------------------------------------ conv

@pytest.mark.parametrize("cfg", [
    dict(n=2, h=16, w=16, cin=3, cout=6, k=5, stride=1, padding="VALID"),
    dict(n=1, h=14, w=14, cin=6, cout=16, k=5, stride=1, padding="VALID"),
    dict(n=2, h=8, w=8, cin=4, cout=8, k=3, stride=2, padding="SAME"),
    dict(n=1, h=7, w=9, cin=2, cout=4, k=3, stride=1, padding=1),
    dict(n=1, h=8, w=8, cin=3, cout=4, k=2, stride=1, padding="SAME"),
    dict(n=1, h=9, w=9, cin=2, cout=4, k=4, stride=2, padding="SAME"),
])
def test_conv2d_matches_xla(cfg):
    x = rand(cfg["n"], cfg["h"], cfg["w"], cfg["cin"], seed=7)
    w = rand(cfg["k"], cfg["k"], cfg["cin"], cfg["cout"], seed=8) * 0.1
    b = rand(cfg["cout"], seed=9)
    want = ops.conv2d(x, w, b, stride=cfg["stride"],
                      padding=cfg["padding"], backend="xla")
    got = ops.conv2d(x, w, b, stride=cfg["stride"],
                     padding=cfg["padding"], backend="pallas_interpret")
    assert got.shape == want.shape
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


@pytest.mark.heavy
def test_conv2d_same_preserves_shape_even_kernel():
    """TF-style SAME: output spatial dims == input dims at stride 1, even
    for even kernel sizes (needs asymmetric padding)."""
    x = rand(1, 8, 8, 3, seed=20)
    for k in (2, 3, 4, 5):
        w = rand(k, k, 3, 4, seed=21) * 0.1
        for backend in ("xla", "pallas_interpret"):
            out = ops.conv2d(x, w, padding="SAME", backend=backend)
            assert out.shape == (1, 8, 8, 4), (k, backend, out.shape)


def test_conv2d_grad_flows():
    """The im2col+matmul path must be differentiable (training uses it)."""
    x = rand(2, 8, 8, 3, seed=10)
    w = rand(3, 3, 3, 4, seed=11) * 0.1

    def loss(w):
        return jnp.sum(ops.conv2d(x, w, backend="pallas_interpret") ** 2)

    g = jax.grad(loss)(w)
    g_ref = jax.grad(
        lambda w: jnp.sum(ops.conv2d(x, w, backend="xla") ** 2))(w)
    np.testing.assert_allclose(g, g_ref, rtol=1e-4, atol=1e-4)


# ------------------------------------------------------------------ pool

@pytest.mark.parametrize("window,stride", [(2, None), (2, 2), (3, 2)])
def test_maxpool_matches_xla(window, stride):
    x = rand(2, 12, 12, 5, seed=12)
    want = ops.maxpool2d(x, window, stride, backend="xla")
    got = ops.maxpool2d(x, window, stride, backend="pallas_interpret")
    assert got.shape == want.shape
    np.testing.assert_allclose(got, want, rtol=RTOL, atol=ATOL)


def test_avgpool_matches_xla():
    x = rand(3, 8, 8, 4, seed=13)
    want = ops.avgpool2d(x, 2, backend="xla")
    got = ops.avgpool2d(x, 2, backend="pallas_interpret")
    np.testing.assert_allclose(got, want, rtol=RTOL, atol=ATOL)


def test_default_backend_mapping():
    on_tpu = jax.default_backend() == "tpu"
    # off-TPU everything is XLA (Pallas-TPU kernels don't lower); on TPU
    # "auto" resolves per op through ops/__init__._TPU_AUTO_POLICY
    assert ops.default_backend() == ("pallas" if on_tpu else "xla")
    for op, tpu_winner in ops._TPU_AUTO_POLICY.items():
        want = tpu_winner if on_tpu else "xla"
        assert ops.default_backend(op) == want
        assert ops.resolve_backend("auto", op) == want
    # explicit backends are never overridden by the policy
    assert ops.resolve_backend("pallas", "conv2d") == "pallas"
    with pytest.raises(ValueError):
        ops.resolve_backend("cuda")


# ------------------------------------------------- grads (training path)

def test_log_softmax_grad_matches_xla():
    x = rand(8, 33, seed=30) * 4.0

    def loss(x, backend):
        return jnp.sum(ops.log_softmax(x, backend=backend) ** 2)

    g = jax.grad(loss)(x, "pallas_interpret")
    g_ref = jax.grad(loss)(x, "xla")
    np.testing.assert_allclose(g, g_ref, rtol=1e-4, atol=1e-5)


def test_softmax_grad_matches_xla():
    x = rand(6, 20, seed=31) * 3.0

    def loss(x, backend):
        return jnp.sum(ops.softmax(x, backend=backend) ** 3)

    g = jax.grad(loss)(x, "pallas_interpret")
    g_ref = jax.grad(loss)(x, "xla")
    np.testing.assert_allclose(g, g_ref, rtol=1e-4, atol=1e-6)


@pytest.mark.parametrize("pool,window,stride", [
    (ops.maxpool2d, 2, None), (ops.maxpool2d, 3, 2),
    (ops.avgpool2d, 2, None),
])
def test_pool_grad_matches_xla(pool, window, stride):
    x = rand(2, 8, 8, 4, seed=32)

    def loss(x, backend):
        return jnp.sum(pool(x, window, stride, backend=backend) ** 2)

    g = jax.grad(loss)(x, "pallas_interpret")
    g_ref = jax.grad(loss)(x, "xla")
    np.testing.assert_allclose(g, g_ref, rtol=1e-5, atol=1e-6)
