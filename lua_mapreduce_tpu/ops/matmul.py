"""Tiled MXU matmul — the workhorse kernel.

All conv FLOPs route through this kernel (im2col → matmul, ops/conv.py),
the same role APRIL-ANN's BLAS/CUDA gemm plays for the reference's models
(SURVEY.md §2.4). Classic Pallas schedule: 3-D grid (M, N, K tiles), A and
B tiles streamed HBM→VMEM by the pipeline, partial products accumulated in
a float32 VMEM scratch across the K dimension, output written once on the
last K step. K is the innermost ("arbitrary") grid dimension so the
accumulator is live for exactly one (i, j) tile at a time.

Block sizes default to a size-adaptive schedule (see ``_auto_blocks``):
the kernel's HBM traffic is ``2·m·n·k·itemsize·(1/bm + 1/bn)`` bytes, so
fixed 256-tiles cap large bf16 matmuls at a ~64 TF/s bandwidth roofline
on a v5e (measured 2026-07: 20.5 ms at 8192³ ≡ the roofline's 21 ms
prediction) while 512-tiles double the arithmetic
intensity into compute-bound territory. Full analysis and the measured
evidence trail: docs/DESIGN.md §matmul; the on-chip sweep that validates
or overrides these defaults is benchmarks/matmul_tune.py.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from lua_mapreduce_tpu.ops import out_struct, resolve_backend


def _matmul_kernel(a_ref, b_ref, o_ref, acc_ref, *, n_k: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    acc_ref[:] += jnp.dot(a_ref[:], b_ref[:],
                          preferred_element_type=jnp.float32)

    @pl.when(k == n_k - 1)
    def _():
        o_ref[:] = acc_ref[:].astype(o_ref.dtype)


def _pad_to(x, m_mult, n_mult):
    m, n = x.shape
    pm, pn = -m % m_mult, -n % n_mult
    if pm or pn:
        x = jnp.pad(x, ((0, pm), (0, pn)))
    return x


def _auto_blocks(m: int, n: int, k: int) -> tuple:
    """Size-adaptive (bm, bn, bk), set by the on-chip sweep.

    HBM traffic is ``2·m·n·k·itemsize·(1/bm + 1/bn)`` (A re-read once
    per N-tile, B once per M-tile; bk cancels), so the M/N tiles set
    the arithmetic intensity: 256² tiles bound bf16 at ~64 TF/s on a
    v5e's ~820 GB/s — under half the 197 TF/s MXU peak — while the
    wide tiles here lift the roofline past peak (compute-bound). The
    round-4 sweep (benchmarks/matmul_tune.py →
    results/matmul_tune.json, v5e 2026-07-31) measured the winners:
    (1024, 1024, 512) at 4096³ (152.7 TF/s) and (512, 1024, 512) at
    8192³ (171.4 TF/s in the sweep; 151.6 = 0.896× XLA through the
    kernel bench of the same date — the
    shallower bm wins there on VMEM/pipeline pressure: the f32 acc at
    bm=1024 is 4 MB). VMEM at
    (512, 1024, 512) bf16: double-buffered A+B 3 MB + f32 acc 2 MB +
    out 1 MB ≈ 6 MB of the ~16 MB budget. Small problems keep 256²
    (less padding waste, the pipeline still overlaps); tiny dims clamp
    in _matmul_pallas as before."""
    if min(m, n) >= 1024 and k >= 512:
        # bk stays a multiple of the 128-lane native tiling (a raw
        # k//4 could be e.g. 625 and break Mosaic lowering); it cancels
        # out of the traffic formula — the sweep found deeper bk only
        # pays at bm=bn=512 (old schedule), not at the wide winners
        return (512 if max(m, n) >= 8192 else 1024), 1024, 512
    return 256, 256, 256


@functools.partial(
    jax.jit,
    static_argnames=("block_m", "block_n", "block_k", "out_dtype",
                     "interpret"))
def _matmul_pallas(a, b, block_m: int | None = None,
                   block_n: int | None = None, block_k: int | None = None,
                   out_dtype=None, interpret=False):
    m, k = a.shape
    k2, n = b.shape
    if k != k2:    # not assert: must survive python -O, else _pad_to
        raise ValueError(f"contracting dims differ: {k} vs {k2}")
    for nm, v in (("block_m", block_m), ("block_n", block_n),
                  ("block_k", block_k)):
        if v is not None and v <= 0:
            raise ValueError(f"{nm} must be positive, got {v}")
    auto_m, auto_n, auto_k = _auto_blocks(m, n, k)
    block_m = auto_m if block_m is None else block_m
    block_n = auto_n if block_n is None else block_n
    block_k = auto_k if block_k is None else block_k
    out_dtype = out_dtype or jnp.promote_types(a.dtype, b.dtype)

    # clamp blocks to the (padded-to-tile) problem, keep MXU/VPU alignment
    block_m = min(block_m, max(8, -(-m // 8) * 8))
    block_n = min(block_n, max(128, -(-n // 128) * 128))
    block_k = min(block_k, max(128, -(-k // 128) * 128))

    ap = _pad_to(a, block_m, block_k)
    bp = _pad_to(b, block_k, block_n)
    gm, gk = ap.shape[0] // block_m, ap.shape[1] // block_k
    gn = bp.shape[1] // block_n

    out = pl.pallas_call(
        functools.partial(_matmul_kernel, n_k=gk),
        grid=(gm, gn, gk),
        in_specs=[
            pl.BlockSpec((block_m, block_k), lambda i, j, kk: (i, kk),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((block_k, block_n), lambda i, j, kk: (kk, j),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j, kk: (i, j),
                               memory_space=pltpu.VMEM),
        out_shape=out_struct((ap.shape[0], bp.shape[1]), out_dtype,
                             ap, bp),
        scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        cost_estimate=pl.CostEstimate(
            flops=2 * m * n * k,
            bytes_accessed=(ap.size + bp.size) * ap.dtype.itemsize
            + m * n * jnp.dtype(out_dtype).itemsize,
            transcendentals=0),
        interpret=interpret,
    )(ap, bp)
    return out[:m, :n]


# Pallas calls have no JVP rule — training needs an explicit VJP. The
# backward pass is two more MXU matmuls: dA = g·Bᵀ, dB = Aᵀ·g.
@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _mm(a, b, cfg):
    block_m, block_n, block_k, out_dtype, interpret = cfg
    return _matmul_pallas(a, b, block_m=block_m, block_n=block_n,
                          block_k=block_k, out_dtype=out_dtype,
                          interpret=interpret)


def _mm_fwd(a, b, cfg):
    return _mm(a, b, cfg), (a, b)


def _mm_bwd(cfg, res, g):
    a, b = res
    block_m, block_n, block_k, _, interpret = cfg
    da = _matmul_pallas(g, b.T, block_m=block_m, block_n=block_n,
                        block_k=block_k, out_dtype=a.dtype,
                        interpret=interpret)
    db = _matmul_pallas(a.T, g, block_m=block_m, block_n=block_n,
                        block_k=block_k, out_dtype=b.dtype,
                        interpret=interpret)
    return da, db


_mm.defvjp(_mm_fwd, _mm_bwd)


def matmul(a, b, *, backend: str = "auto", block_m: int = None,
           block_n: int = None, block_k: int = None, out_dtype=None):
    """``a @ b`` with float32 MXU accumulation.

    Inputs may be any float dtype (bfloat16 recommended on TPU — the MXU
    natively consumes bf16 and accumulates f32); output defaults to the
    promoted input dtype. Differentiable via a custom VJP whose backward
    matmuls run through the same Pallas kernel. Block sizes default to
    the size-adaptive schedule (``_auto_blocks``); explicit values
    override (benchmarks/matmul_tune.py sweeps them on hardware).
    """
    backend = resolve_backend(backend, "matmul")
    if backend == "xla":
        return jnp.dot(a, b, preferred_element_type=jnp.float32).astype(
            out_dtype or jnp.promote_types(a.dtype, b.dtype))
    out_dtype = out_dtype or jnp.promote_types(a.dtype, b.dtype)
    cfg = (block_m, block_n, block_k, out_dtype,
           backend == "pallas_interpret")
    return _mm(a, b, cfg)
