"""Gradient accumulation: the microbatch value_and_grad fold.

ONE implementation shared by the DP trainer (train/harness.py) and both
transformer train steps (models/transformer.py) — the fold splits each
per-device batch tile into ``accum`` equal microbatches, scans
``value_and_grad`` over them keeping one microbatch's activations live
at a time, and returns the tile-mean (loss, grads): identical numbers
to the whole tile up to float associativity, activation memory ÷ accum.
The running sums are held in f32 regardless of the parameter dtype, so
bf16 params do not accumulate bf16 rounding across microbatches; the
result is cast back to each gradient leaf's natural dtype at the end.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax


def accum_value_and_grad(global_loss, params, arrays, accum: int):
    """Mean ``value_and_grad(global_loss)(params, *microbatch)`` over
    ``accum`` equal microbatches of ``arrays`` (split on the leading
    axis). ``global_loss(params, *arrays) -> scalar`` must be a MEAN
    over examples, so equal-size microbatch grads average exactly to
    the whole-tile grad.
    """
    rows = arrays[0].shape[0]
    if rows % accum:
        raise ValueError(f"per-device batch of {rows} rows does not "
                         f"split into grad_accum={accum}")
    micro = tuple(a.reshape(accum, rows // accum, *a.shape[1:])
                  for a in arrays)

    def body(carry, mb):
        loss_a, g_a = carry
        l, g = jax.value_and_grad(global_loss)(params, *mb)
        g32 = jax.tree.map(lambda acc, x: acc + x.astype(jnp.float32),
                           g_a, g)
        return (loss_a + l.astype(jnp.float32), g32), None

    # zeros_like (not zeros): inside shard_map a leaf that is sharded
    # over a mesh axis carries that axis in its type, as its gradient
    # does, and the scan's carry must type-match what it accumulates
    zeros = jax.tree.map(
        lambda p: jnp.zeros_like(p, dtype=jnp.float32), params)
    init = (jnp.float32(0.0), zeros)
    (loss_s, g_s), _ = lax.scan(body, init, micro)
    mean = jax.tree.map(
        lambda g, p: (g / accum).astype(p.dtype), g_s, params)
    return loss_s / accum, mean
