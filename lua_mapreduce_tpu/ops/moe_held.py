"""The held experts of a decode step as one grouped call.

A decode step's tile is a few tokens (8 or 16 sessions), so an expert's
three matmuls are a stream: what the layer costs is reading the weights
of the experts its tokens chose, once. ``parallel/moe.moe_ffn_held`` put
every held expert behind a conditional of its own; a conditional is a
wall for the compiler's prefetch, so each touched expert began its three
matmuls on a cold stream, and the untouched ones still branched.

:func:`moe_held` is the layer's expert part for such a tile.
``_moe_held_pallas`` is given the list of touched experts (scalar
prefetch) and walks it: the weight tiles of a touched expert follow the
last one's with no gap, an untouched expert costs no byte and no step.
``_moe_held_xla`` is the conditionals: the other platforms' path, the
path of a tile too large for the kernel, and the oracle.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from lua_mapreduce_tpu.ops import out_struct, resolve_backend
from lua_mapreduce_tpu.ops.decode import _pad

# tokens a call takes: a decode step's (the cells' are 8 and 16)
MAX_TOKENS = 64
# what one copy of a (d, tile_f) gate or up tile, or a (tile_f, d) down
# tile, may carry. Narrow tiles win: the first tile's copies are the one
# wait nothing hides, and a step of the walk costs no more for being
# short. One layer alone, us a call, tile_f 128 / 256 / 512 beside the
# conditionals and the touched experts' bytes at 819 GB/s (my chip run,
# PR 38; benchmarks/results/moe_held_bench.json): (16 tokens, 32 held,
# 4096 x 2048) with 21 touched 1,456 / 1,575 / 1,464, conditionals 1,608,
# bytes 1,291; (8, 16, 7168 x 2048) with 4 touched 512 / 541 / (547 under
# a limit of 48 MB; 512 columns do not fit this one), conditionals 564,
# bytes 430.
_TILE_BYTES = 1 << 20
# what the kernel may hold in VMEM, said out loud so that every
# generation compiles the same tiling, and the part of it `_tiles` lets
# `_vmem_bytes` reach
_VMEM_LIMIT = 32 << 20
_VMEM_BUDGET = _VMEM_LIMIT * 3 // 4


def swiglu(x, wg, wu, wd):
    return (jax.nn.silu(x @ wg) * (x @ wu)) @ wd


def _vmem_bytes(t: int, d: int, tile_f: int, count: int,
                itemsize: int) -> int:
    """VMEM the kernel holds: two sets of three weight tiles, the
    tokens, the weights they enter with, the float32 output, and a
    step's float32 products."""
    sub = 32 // itemsize
    tiles = 3 * _pad(d, 128) * _pad(tile_f, 128) * itemsize
    x = _pad(t, sub) * _pad(d, 128) * itemsize
    combine = _pad(t, 8) * _pad(count, 128) * 4
    out = _pad(t, 8) * _pad(d, 128) * 4
    step = 3 * _pad(t, 8) * _pad(tile_f, 128) * 4 + out
    return 2 * (tiles + x + combine + out) + step


def _tiles(t: int, d: int, f: int, count: int, itemsize: int) -> int:
    """The width ``tile_f`` of the slice of an expert that one step
    reads (three tiles: gate and up (d, tile_f), down (tile_f, d)): the
    widest divisor of ``f`` that is a whole number of 128 lanes, whose
    tile stays within ``_TILE_BYTES`` and whose step fits
    ``_VMEM_BUDGET``; 128 where none is."""
    return max((w for w in range(128, f + 1, 128)
                if f % w == 0 and d * w * itemsize <= _TILE_BYTES
                and _vmem_bytes(t, d, w, count, itemsize) <= _VMEM_BUDGET),
               default=128)


def _takes(x, wg) -> bool:
    """What the kernel takes: a decode step's tokens, and experts whose
    two widths are whole numbers of lanes (Mosaic slices no narrower
    tile out of the stacks)."""
    return (x.shape[0] <= MAX_TOKENS and x.shape[1] % 128 == 0
            and wg.shape[2] % 128 == 0)


def _fold(e, x_ref, cmb_ref, wg, wu, wd, o_ref):
    """One (expert, tile): the tile's share of the expert's output,
    weighed, into the output block."""
    x = x_ref[...]
    g = jnp.dot(x, wg, preferred_element_type=jnp.float32)
    u = jnp.dot(x, wu, preferred_element_type=jnp.float32)
    h = (jax.nn.silu(g) * u).astype(x.dtype)
    y = jnp.dot(h, wd, preferred_element_type=jnp.float32)
    cmb = cmb_ref[...]
    mine = lax.broadcasted_iota(jnp.int32, cmb.shape, 1) == e
    weight = jnp.sum(jnp.where(mine, cmb, 0.0), axis=1, keepdims=True)
    o_ref[...] += y * weight


def _moe_held_kernel(ids_ref, n_ref, x_ref, cmb_ref, wg_hbm, wu_hbm, wd_hbm,
                     o_ref, gbuf, ubuf, dbuf, sem, *, tile_f, n_tiles):
    """Walk the ``n`` touched experts' ``n * n_tiles`` tiles: the copies
    of tile ``s + 1`` are in flight while tile ``s`` is folded."""
    total = n_ref[0] * n_tiles
    o_ref[...] = jnp.zeros_like(o_ref)

    def copies(s, slot):
        e = ids_ref[s // n_tiles]
        at = pl.ds(pl.multiple_of((s % n_tiles) * tile_f, tile_f), tile_f)
        return (
            pltpu.make_async_copy(wg_hbm.at[e, :, at], gbuf.at[slot],
                                  sem.at[slot, 0]),
            pltpu.make_async_copy(wu_hbm.at[e, :, at], ubuf.at[slot],
                                  sem.at[slot, 1]),
            pltpu.make_async_copy(wd_hbm.at[e, at, :], dbuf.at[slot],
                                  sem.at[slot, 2]))

    @pl.when(total > 0)
    def _():
        for c in copies(0, 0):
            c.start()

    def step(s, _):
        slot = s % 2

        @pl.when(s + 1 < total)
        def _():
            for c in copies(s + 1, 1 - slot):
                c.start()

        for c in copies(s, slot):
            c.wait()
        _fold(ids_ref[s // n_tiles], x_ref, cmb_ref, gbuf[slot], ubuf[slot],
              dbuf[slot], o_ref)
        return 0

    lax.fori_loop(0, total, step, 0)


def _touched(load):
    """(ids, n): the touched experts first, in their own order, and how
    many they are. No sort: an expert's place is the count of touched
    ones before it."""
    slots = jnp.arange(load.shape[0], dtype=jnp.int32)
    live = load > 0
    place = jnp.sum(live[None, :] & (slots[None, :] < slots[:, None]), axis=1)
    ids = jnp.sum(jnp.where(live[None, :] & (place[None, :] == slots[:, None]),
                            slots[None, :], 0), axis=1)
    return ids.astype(jnp.int32), jnp.sum(live).astype(jnp.int32).reshape(1)


@functools.partial(jax.jit, static_argnames=("tile_f", "interpret"))
def _moe_held_pallas(x, combine, load, wg, wu, wd, tile_f: int | None = None,
                     interpret: bool = False):
    t, d = x.shape
    count, _, f = wg.shape
    if tile_f is None:
        tile_f = _tiles(t, d, f, count, wg.dtype.itemsize)
    ids, n = _touched(load)
    whole = lambda shape: pl.BlockSpec(  # noqa: E731
        shape, lambda i, ids, n: (0, 0), memory_space=pltpu.VMEM)
    stack = pl.BlockSpec(memory_space=pl.ANY)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(1,),
        in_specs=[whole((t, d)), whole((t, count)), stack, stack, stack],
        out_specs=whole((t, d)),
        scratch_shapes=[pltpu.VMEM((2, d, tile_f), wg.dtype),
                        pltpu.VMEM((2, d, tile_f), wu.dtype),
                        pltpu.VMEM((2, tile_f, d), wd.dtype),
                        pltpu.SemaphoreType.DMA((2, 3))],
    )
    return pl.pallas_call(
        functools.partial(_moe_held_kernel, tile_f=tile_f,
                          n_tiles=f // tile_f),
        grid_spec=grid_spec,
        out_shape=out_struct((t, d), jnp.float32, x, wg),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name="_moe_held_pallas",
    )(ids, n, x, combine.astype(jnp.float32), wg, wu, wd)


def _moe_held_xla(x, combine, load, wg, wu, wd):
    """A conditional an expert: an expert that no token chose is never
    computed, so its weights are not read."""
    acc = jnp.zeros(x.shape, jnp.float32)
    for e in range(wg.shape[0]):
        def whole(acc, e=e):
            y = swiglu(x, wg[e], wu[e], wd[e]).astype(jnp.float32)
            return acc + y * combine[:, e:e + 1]

        acc = lax.cond(load[e] > 0, whole, lambda acc: acc, acc)
    return acc


def moe_held(x, combine, load, wg, wu, wd, *, backend: str = "auto"):
    """``sum_e combine[:, e] * swiglu_e(x)`` over the held experts with
    ``load[e] > 0``, in float32.

    x (T, d): the step's tokens; combine (T, count) float32: the weight
    with which each held expert enters a token's sum (zero where the
    token did not choose it); load (count,): the tokens that chose each;
    wg, wu (count, d, f) and wd (count, f, d): the stacked experts as the
    layer holds them. An expert's hidden values are rounded to ``x``'s
    type, its output is weighed and summed in float32. Returns (T, d)
    float32. What the kernel does not take (``_takes``: more than
    ``MAX_TOKENS`` tokens, a width that is no whole number of lanes)
    takes the conditionals whatever the platform."""
    backend = resolve_backend(backend, "moe_held")
    if backend == "xla" or not _takes(x, wg):
        return _moe_held_xla(x, combine, load, wg, wu, wd)
    return _moe_held_pallas(x, combine, load, wg, wu, wd,
                            interpret=backend == "pallas_interpret")
