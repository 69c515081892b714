"""Weight-only int8 matmul — the serving-side quantization kernel.

Decode is weight-bandwidth-bound: every generated token streams the
full parameter set from HBM while the MXU idles. Weight-only int8
halves that traffic — the
kernel reads int8 weight tiles from HBM, converts to bf16 in VMEM for
the MXU dot, and applies the per-output-channel scale ONCE on the f32
accumulator (out[:, j] = (x @ q)[:, j] · s[j], exact because the scale
is constant along the contraction), so nothing wider than int8 ever
crosses HBM for the weights. Activations stay bf16/f32: TPU MXUs take
same-typed operands, and weight-only (not activation) quantization is
the serving standard because activations are small and dynamic.

Quantization is symmetric per-output-channel: q = round(w / s),
s = max|w_col| / 127 — zero-point-free so the dot needs no correction
term. The XLA path (`backend="xla"`, non-TPU platforms, and the
correctness oracle) dequantizes then matmuls; under jit the dequantized
copy may be hoisted/materialized, which is exactly why the kernel
exists.

Reference role: the APRIL-ANN toolkit's kernel library (SURVEY.md §2.4)
— this extends the library the same way the reference would grow a new
CUDA kernel.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from lua_mapreduce_tpu.ops import out_struct, resolve_backend


def quantize_q8(w, axis: int = 0) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Symmetric per-channel int8: returns (q int8, s f32) with
    w ≈ q · s broadcast along ``axis`` (the contraction axis — scales
    live per OUTPUT channel). For a (K, N) weight use axis=0."""
    amax = jnp.max(jnp.abs(w.astype(jnp.float32)), axis=axis,
                   keepdims=True)
    s = jnp.maximum(amax, 1e-12) / 127.0
    q = jnp.clip(jnp.round(w.astype(jnp.float32) / s), -127, 127)
    return q.astype(jnp.int8), s.astype(jnp.float32)


def _dequant_matmul_xla(x, q, s):
    """Oracle / non-TPU path: dequantize then dot (f32 accumulate)."""
    w = q.astype(jnp.float32) * s
    return jax.lax.dot_general(
        x.astype(jnp.float32), w, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32).astype(x.dtype)


def _q8_kernel(x_ref, w_ref, s_ref, o_ref, acc_scr, *, n_k: int):
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _():
        acc_scr[:] = jnp.zeros_like(acc_scr)

    # int8 tile → bf16 in VMEM; HBM only ever moved the int8 bytes
    wt = w_ref[...].astype(jnp.bfloat16)
    acc_scr[:] += jax.lax.dot_general(
        x_ref[...], wt, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(ki == n_k - 1)
    def _():
        # per-output-channel scale, applied once on the accumulator
        o_ref[...] = (acc_scr[:] * s_ref[...]).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("block_m", "block_n", "block_k",
                                    "interpret"))
def _q8_matmul_pallas(x, q, s, block_m=256, block_n=512, block_k=512,
                      interpret=False):
    from lua_mapreduce_tpu.ops.matmul import _pad_to

    m, k = x.shape
    _, n = q.shape
    # clamp blocks to the (padded-to-tile) problem — same discipline as
    # ops/matmul.py: no streaming 512-wide weight tiles for an n=128
    # head projection, no whole-M VMEM block for a prefill-sized call
    block_m = min(block_m, max(8, -(-m // 8) * 8))
    block_n = min(block_n, max(128, -(-n // 128) * 128))
    block_k = min(block_k, max(128, -(-k // 128) * 128))
    xb = _pad_to(x.astype(jnp.bfloat16), block_m, block_k)
    qb = _pad_to(q, block_k, block_n)
    sb = _pad_to(s.reshape(1, n), 1, block_n)
    gm, gk = xb.shape[0] // block_m, xb.shape[1] // block_k
    gn = qb.shape[1] // block_n

    out = pl.pallas_call(
        functools.partial(_q8_kernel, n_k=gk),
        grid=(gm, gn, gk),
        in_specs=[
            pl.BlockSpec((block_m, block_k),
                         lambda mi, ni, ki: (mi, ki),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((block_k, block_n),
                         lambda mi, ni, ki: (ki, ni),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_n), lambda mi, ni, ki: (0, ni),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((block_m, block_n),
                               lambda mi, ni, ki: (mi, ni),
                               memory_space=pltpu.VMEM),
        out_shape=out_struct((xb.shape[0], qb.shape[1]), x.dtype,
                             xb, qb, sb),
        scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="q8_matmul_pallas",
    )(xb, qb, sb)
    return out[:m, :n]


def q8_matmul(x, q, s, *, backend: str = "auto", block_n: int = 512,
              block_k: int = 512):
    """x (M, K) @ dequant(q (K, N), s (N,)) → (M, K)·(K, N) = (M, N).

    ``backend="pallas"`` streams int8 weight tiles (the decode path);
    ``"xla"`` dequantizes then dots (oracle, non-TPU)."""
    if x.ndim != 2 or q.ndim != 2:
        raise ValueError(f"x and q must be rank-2; got {x.shape}, "
                         f"{q.shape}")
    if x.shape[1] != q.shape[0]:
        raise ValueError(f"contraction mismatch: x {x.shape} vs q "
                         f"{q.shape}")
    if q.dtype != jnp.int8:
        raise ValueError(f"q must be int8, got {q.dtype}")
    s = jnp.asarray(s)
    if s.size != q.shape[1]:
        raise ValueError(f"scale has {s.size} entries for {q.shape[1]} "
                         f"output channels")
    backend = resolve_backend(backend, "q8_matmul")
    if backend == "xla":
        return _dequant_matmul_xla(x, q, s.reshape(1, -1))
    return _q8_matmul_pallas(x, q, s.reshape(-1), block_n=block_n,
                             block_k=block_k,
                             interpret=backend == "pallas_interpret")


def utest() -> None:
    """Quantization round-trip + matmul parity at f32 tolerances."""
    import numpy as np

    rng = np.random.RandomState(0)
    w = jnp.asarray(rng.randn(64, 96).astype(np.float32))
    q, s = quantize_q8(w)
    err = float(jnp.max(jnp.abs(q.astype(jnp.float32) * s - w)))
    assert err <= float(jnp.max(jnp.abs(w))) / 127.0 + 1e-6
    x = jnp.asarray(rng.randn(4, 64).astype(np.float32))
    got = q8_matmul(x, q, s.reshape(-1), backend="xla")
    want = x @ (q.astype(jnp.float32) * s)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
