"""maxpool2d / avgpool2d — VPU window reductions.

APRIL-ANN's pooling kernels (SURVEY.md §2.4, BASELINE.json LeNet config)
re-expressed for TPU: a pooling window is KH·KW static strided slices
combined elementwise on the VPU — no sliding-window loop, no dynamic
shapes, and the batch dimension is the pipeline grid (one image's
activation block in VMEM at a time).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from lua_mapreduce_tpu.ops import out_struct, resolve_backend
from lua_mapreduce_tpu.ops.conv import _norm_stride


def _pool_kernel(x_ref, o_ref, *, kh, kw, sh, sw, ho, wo, mode):
    # Mosaic can't lower strided vector slices, so downsampling-by-stride
    # is expressed as unstrided slice → reshape → take lane 0: the
    # elements at i + m·sh are exactly reshape(ho, sh, …)[:, 0]. The
    # input block is pre-padded so every slice is full-size; padding
    # never lands in a kept lane.
    x = x_ref[0]
    c = x.shape[-1]
    acc = None
    for i in range(kh):
        for j in range(kw):
            sl = jax.lax.slice(x, (i, j, 0),
                               (i + ho * sh, j + wo * sw, c))
            sl = sl.reshape(ho, sh, wo, sw, c)[:, 0, :, 0, :]
            if acc is None:
                acc = sl if mode == "max" else sl.astype(jnp.float32)
            elif mode == "max":
                acc = jnp.maximum(acc, sl)
            else:
                acc = acc + sl
    if mode == "avg":
        acc = (acc / (kh * kw)).astype(o_ref.dtype)
    o_ref[0] = acc


@functools.partial(jax.jit,
                   static_argnames=("window", "stride", "mode", "interpret"))
def _pool_pallas(x, window, stride, mode, interpret=False):
    kh, kw = window
    sh, sw = stride
    n, h, w, c = x.shape
    ho = (h - kh) // sh + 1
    wo = (w - kw) // sw + 1
    hp, wp = (kh - 1) + ho * sh, (kw - 1) + wo * sw   # slice headroom
    if hp > h or wp > w:
        x = jnp.pad(x, ((0, 0), (0, hp - h), (0, wp - w), (0, 0)))
    return pl.pallas_call(
        functools.partial(_pool_kernel, kh=kh, kw=kw, sh=sh, sw=sw,
                          ho=ho, wo=wo, mode=mode),
        grid=(n,),
        in_specs=[pl.BlockSpec((1, hp, wp, c), lambda i: (i, 0, 0, 0),
                               memory_space=pltpu.VMEM)],
        out_specs=pl.BlockSpec((1, ho, wo, c), lambda i: (i, 0, 0, 0),
                               memory_space=pltpu.VMEM),
        out_shape=out_struct((n, ho, wo, c), x.dtype, x),
        # each image is independent — let Mosaic parallelize the batch
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(x)


def _pool_xla(x, window, stride, mode):
    # KH·KW static strided slices combined elementwise — NOT
    # lax.reduce_window, which has no linearization rule and kills
    # reverse-mode AD under shard_map/scan (the DP-trainer hot path).
    kh, kw = window
    sh, sw = stride
    n, h, w, c = x.shape
    ho = (h - kh) // sh + 1
    wo = (w - kw) // sw + 1
    out = None
    for i in range(kh):
        for j in range(kw):
            s = jax.lax.slice(
                x, (0, i, j, 0),
                (n, i + (ho - 1) * sh + 1, j + (wo - 1) * sw + 1, c),
                (1, sh, sw, 1))
            if out is None:
                out = s
            else:
                out = jnp.maximum(out, s) if mode == "max" else out + s
    if mode == "avg":
        out = out / (kh * kw)
    return out


# Pallas calls have no JVP rule; the backward pass reuses XLA's
# reduce-window gradient (select-and-scatter for max, uniform spread for
# avg) by differentiating the XLA forward — pooling is cheap, the extra
# forward in bwd is noise next to the convs around it.
@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _pool_p(x, cfg):
    window, stride, mode, interpret = cfg
    return _pool_pallas(x, window, stride, mode, interpret=interpret)


def _pool_p_fwd(x, cfg):
    return _pool_p(x, cfg), x


def _pool_p_bwd(cfg, x, g):
    window, stride, mode, _ = cfg
    _, vjp = jax.vjp(lambda x: _pool_xla(x, window, stride, mode), x)
    return vjp(g)


_pool_p.defvjp(_pool_p_fwd, _pool_p_bwd)


def _pool(x, window, stride, mode, backend):
    backend = resolve_backend(backend, f"{mode}pool2d")
    window = _norm_stride(window)
    stride = _norm_stride(stride if stride is not None else window)
    if backend == "xla":
        return _pool_xla(x, window, stride, mode)
    return _pool_p(x, (window, stride, mode,
                       backend == "pallas_interpret"))


def maxpool2d(x, window=2, stride=None, *, backend: str = "auto"):
    """VALID max pooling over NHWC; stride defaults to the window."""
    return _pool(x, window, stride, "max", backend)


def avgpool2d(x, window=2, stride=None, *, backend: str = "auto"):
    """VALID average pooling over NHWC; stride defaults to the window."""
    return _pool(x, window, stride, "avg", backend)
