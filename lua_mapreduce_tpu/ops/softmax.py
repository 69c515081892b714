"""Fused row-wise softmax / log_softmax.

The reference's flagship model ends in ``log_softmax``
(examples/APRIL-ANN/init.lua:12, kernel provided by the external APRIL-ANN
toolkit — SURVEY.md §2.4). Here it is one VPU pass per row block: max,
exp, sum, and normalization fused in VMEM, so logits make a single round
trip to HBM instead of the four a naive composition would cost (the op is
bandwidth-bound; fusion is the whole win on TPU).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from lua_mapreduce_tpu.ops import out_struct, resolve_backend


def _log_softmax_kernel(x_ref, o_ref):
    x = x_ref[:].astype(jnp.float32)
    m = jnp.max(x, axis=-1, keepdims=True)
    shifted = x - m
    lse = jnp.log(jnp.sum(jnp.exp(shifted), axis=-1, keepdims=True))
    o_ref[:] = (shifted - lse).astype(o_ref.dtype)


def _softmax_kernel(x_ref, o_ref):
    x = x_ref[:].astype(jnp.float32)
    m = jnp.max(x, axis=-1, keepdims=True)
    e = jnp.exp(x - m)
    o_ref[:] = (e / jnp.sum(e, axis=-1, keepdims=True)).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("kernel", "block_rows", "interpret"))
def _rowwise_pallas(x, kernel, block_rows=256, interpret=False):
    orig_shape = x.shape
    n = orig_shape[-1]
    x2 = x.reshape(-1, n)
    m = x2.shape[0]

    block_rows = min(block_rows, max(8, -(-m // 8) * 8))
    # VMEM guard: the kernel holds the block in f32 plus temps (~7 B/elem
    # with a ~11MB fixed overhead against the 16MB scoped budget, measured
    # on v5e at widths 8k-32k) — clamp rows so vocab-wide inputs (32k
    # logits) compile instead of OOMing scoped vmem
    fit = (5_000_000 // (7 * n)) // 8 * 8
    block_rows = max(8, min(block_rows, fit))
    pm, pn = -m % block_rows, -n % 128
    # column padding must not perturb the row max/sum → pad with -inf
    if pm or pn:
        x2 = jnp.pad(x2, ((0, pm), (0, pn)),
                     constant_values=jnp.finfo(x2.dtype).min)

    out = pl.pallas_call(
        kernel,
        grid=(x2.shape[0] // block_rows,),
        in_specs=[pl.BlockSpec((block_rows, x2.shape[1]), lambda i: (i, 0),
                               memory_space=pltpu.VMEM)],
        out_specs=pl.BlockSpec((block_rows, x2.shape[1]), lambda i: (i, 0),
                               memory_space=pltpu.VMEM),
        out_shape=out_struct(x2.shape, x.dtype, x2),
        # each row block is independent — let Mosaic parallelize
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(x2)
    return out[:m, :n].reshape(orig_shape)


# Pallas calls have no JVP rule; training differentiates through these, so
# each op carries its analytic VJP (elementwise — the VPU/XLA backward is
# already optimal, no kernel needed):
#   y = log_softmax(x):  dx = g − softmax(x)·Σg
#   y = softmax(x):      dx = y·(g − Σ(g·y))

@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _log_softmax_p(x, cfg):
    block_rows, interpret = cfg
    return _rowwise_pallas(x, _log_softmax_kernel, block_rows=block_rows,
                           interpret=interpret)


def _log_softmax_fwd(x, cfg):
    y = _log_softmax_p(x, cfg)
    return y, y


def _log_softmax_bwd(cfg, y, g):
    return (g - jnp.exp(y) * jnp.sum(g, axis=-1, keepdims=True),)


_log_softmax_p.defvjp(_log_softmax_fwd, _log_softmax_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _softmax_p(x, cfg):
    block_rows, interpret = cfg
    return _rowwise_pallas(x, _softmax_kernel, block_rows=block_rows,
                           interpret=interpret)


def _softmax_fwd(x, cfg):
    y = _softmax_p(x, cfg)
    return y, y


def _softmax_bwd(cfg, y, g):
    return (y * (g - jnp.sum(g * y, axis=-1, keepdims=True)),)


_softmax_p.defvjp(_softmax_fwd, _softmax_bwd)


def log_softmax(x, *, backend: str = "auto", block_rows: int = 256):
    """Numerically-stable log-softmax over the last axis."""
    backend = resolve_backend(backend, "softmax")
    if backend == "xla":
        return jax.nn.log_softmax(x, axis=-1)
    return _log_softmax_p(x, (block_rows, backend == "pallas_interpret"))


def softmax(x, *, backend: str = "auto", block_rows: int = 256):
    """Numerically-stable softmax over the last axis."""
    backend = resolve_backend(backend, "softmax")
    if backend == "xla":
        return jax.nn.softmax(x, axis=-1)
    return _softmax_p(x, (block_rows, backend == "pallas_interpret"))
