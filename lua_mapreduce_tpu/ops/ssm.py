"""The selective scan of a Mamba-1 state-space layer (Gu & Dao 2023),
in ``jax.numpy`` and ``lax.scan``: no kernel (PERF.md section 7).

    h_t = exp(delta_t * A) * h_{t-1} + (delta_t * u_t) outer B_t
    s_t = h_t . C_t + D * u_t

``u`` and ``delta`` are (.., E) values of the layer's inner width, ``B``
and ``C`` (.., N) of the state's; ``A`` is (N, E) and negative, ``D``
(E,). The state ``h`` is (B, N, E): the inner width on the lanes, the
state's 16 on the sublanes, so that a float32 state of 5120 x 16 is 320
KB a row and not the 2.6 MB a (.., E, 16) layout pads to on the chip.
It is float32 whatever the model's type (``STATE_DTYPE``): a rounding of
it is multiplied by every later decay.

Two forms give one state. :func:`selective_scan` takes a sequence, a
position at a time, so that what exists besides the inputs and the
outputs is the state, not a (B, L, E, N) tensor; :func:`selective_step`
is one position of it, the decode step's. :func:`causal_conv` is the
layer's depthwise convolution over positions with the ``K - 1`` inputs
before the sequence (its tail) handed in and out.
"""

from __future__ import annotations

import jax.numpy as jnp
from jax import lax

STATE_DTYPE = jnp.float32


def selective_step(h, u, delta, a, b, c, d):
    """One position: ``h`` (B, N, E) the state before it, ``u`` and
    ``delta`` (B, E), ``b`` and ``c`` (B, N), ``a`` (N, E), ``d`` (E,).
    Returns (the state after it, ``s`` (B, E) float32)."""
    f32 = jnp.float32
    u, delta = u.astype(f32), delta.astype(f32)
    decay = jnp.exp(delta[:, None, :] * a.astype(f32))
    h = (decay * h.astype(f32)
         + (delta * u)[:, None, :] * b.astype(f32)[:, :, None])
    s = jnp.sum(h * c.astype(f32)[:, :, None], axis=1) + d.astype(f32) * u
    return h.astype(STATE_DTYPE), s


def selective_scan(h, u, delta, a, b, c, d):
    """A sequence: ``u`` and ``delta`` (B, L, E), ``b`` and ``c`` (B, L,
    N), from the state ``h`` (B, N, E). Returns (the state after the
    last position, ``s`` (B, L, E) float32)."""

    def one(h, at):
        return selective_step(h, at[0], at[1], a, at[2], at[3], d)

    first = lambda x: jnp.moveaxis(x, 1, 0)  # noqa: E731
    h, s = lax.scan(one, h, (first(u), first(delta), first(b), first(c)))
    return h, jnp.moveaxis(s, 0, 1)


def causal_conv(u, tail, w, bias):
    """Depthwise causal convolution over positions: ``u`` (B, L, E),
    ``tail`` (B, K - 1, E) the inputs of the K - 1 positions before the
    first, ``w`` (K, E) the taps (``w[K - 1]`` meets the position's own
    input), ``bias`` (E,). Returns (out (B, L, E) float32, the tail a
    later sequence goes on from)."""
    taps, l = w.shape[0], u.shape[1]
    seen = jnp.concatenate([tail.astype(u.dtype), u], axis=1)
    f32 = jnp.float32
    out = bias.astype(f32) + sum(
        seen[:, j:j + l].astype(f32) * w[j].astype(f32) for j in range(taps))
    return out, seen[:, l:]
