"""The gated delta rule of Kimi Delta Attention (KDA; Kimi Linear,
arXiv:2510.26692): a linear-attention head whose state is a K x V
matrix, decayed per key channel and corrected toward each new value.

    S_t = (I - beta_t k_t k_t^T) diag(alpha_t) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t,          alpha_t = exp(g_t),  g_t <= 0

``q`` and ``k`` are (.., K) per head, ``v`` (.., V), ``g`` the log of the
decay (.., K), ``beta`` a scalar per head and position. The state is
float32 whatever the model's type (``STATE_DTYPE``): a rounding of it is
multiplied by every later decay.

:func:`kda_chunked` is the sequence's form (prefill, a chunk): chunks
of ``chunk`` positions in the WY / UT form (Yang et al. 2024, "Parallel
Sequence Modeling with Delta Rule"; the per-channel decay as Gated
DeltaNet and KDA carry it), a ``lax.scan`` over the chunks with the
state as the carry. Inside a chunk, with ``G`` the decays' cumulative
sum from the chunk's start (non-increasing, <= 0):

    A[t, i] = sum_c k_t[c] k_i[c] exp(G_t[c] - G_i[c])     (i < t)
    B[t, i] = sum_c q_t[c] k_i[c] exp(G_t[c] - G_i[c])     (i <= t)
    U = (I + diag(beta) A)^-1 diag(beta) (V - (K * exp G) S_0)
    O = (Q * exp G) S_0 + B U
    S_C = exp(G_C) * S_0 + (K * exp(G_C - G))^T U

Every exponent is a difference that is <= 0, so no exponential
overflows however strong the decay (``exp(-G)`` alone would). The
pairwise decays are reduced over ``c`` as they are formed, so that the
(C, C, K) tensor is never held.

:func:`kda_step` is one position, a decode step's: ``_kda_step_pallas``
reads each (session, head) state once and writes it once, in place
(``input_output_aliases``), a session's heads a grid step; in float32 on
the VPU (the contractions are matrix-vector). ``_kda_step_xla`` is the
composition for other platforms and the oracle.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from lua_mapreduce_tpu.ops import out_struct, resolve_backend

STATE_DTYPE = jnp.float32
CHUNK = 64
_HI = lax.Precision.HIGHEST
# a session's heads' state (32 x 128 x 128 float32 = 2 MiB) in and out,
# double-buffered, and the per-head temporaries
_VMEM_LIMIT = 32 << 20


def _chunk_rule(s, qkvgb):
    """One chunk from the state ``s`` (B, H, K, V) before it; ``qkvgb``
    are (B, H, C, ..) float32. Returns (the state after, O (B, H, C,
    V))."""
    q, k, v, g, beta = qkvgb
    c = q.shape[2]
    big_g = jnp.cumsum(g, axis=2)                               # (B,H,C,K)
    t_idx = jnp.arange(c)
    seen = t_idx[:, None] >= t_idx[None, :]                     # t >= i

    def pairwise(a):
        # sum_c a_t[c] k_i[c] exp(G_t[c] - G_i[c]) over i <= t
        diff = big_g[:, :, :, None, :] - big_g[:, :, None, :, :]
        decay = jnp.exp(jnp.where(seen[:, :, None], diff, -jnp.inf))
        return jnp.sum(a[:, :, :, None, :] * k[:, :, None, :, :] * decay,
                       axis=-1)

    strict = jnp.where(t_idx[:, None] > t_idx[None, :], pairwise(k), 0.0)
    scores = pairwise(q)
    lower = jnp.eye(c, dtype=jnp.float32) + beta[..., None] * strict
    rhs = beta[..., None] * jnp.concatenate([k * jnp.exp(big_g), v], axis=-1)
    solved = jax.scipy.linalg.solve_triangular(lower, rhs, lower=True,
                                               unit_diagonal=True)
    kdim = k.shape[-1]
    w, u = solved[..., :kdim], solved[..., kdim:]
    mm = functools.partial(jnp.einsum, precision=_HI,
                           preferred_element_type=jnp.float32)
    u = u - mm("bhck,bhkv->bhcv", w, s)
    o = (mm("bhck,bhkv->bhcv", q * jnp.exp(big_g), s)
         + mm("bhti,bhiv->bhtv", scores, u))
    last = big_g[:, :, -1:, :]                                  # (B,H,1,K)
    s = (jnp.exp(last[:, :, 0, :, None]) * s
         + mm("bhck,bhcv->bhkv", k * jnp.exp(last - big_g), u))
    return s, o


def kda_chunked(q, k, v, g, beta, state, *, chunk: int = CHUNK):
    """The rule over a sequence: ``q``, ``k`` (B, L, H, K), ``v`` (B, L,
    H, V), ``g`` (B, L, H, K) the log-decays, ``beta`` (B, L, H), from
    ``state`` (B, H, K, V). Positions past the last whole chunk are
    padded with no decay and no update (``beta`` 0), which leaves the
    state as it was. Returns (o (B, L, H, V) float32, the state after
    the last position)."""
    b, l, h, kdim = q.shape
    n = -(-l // chunk)
    pad = n * chunk - l
    f32 = jnp.float32

    def split(x):
        # (B, L, H, ..) -> (n, B, H, C, ..), zeros past the end
        x = jnp.pad(x.astype(f32), ((0, 0), (0, pad))
                    + ((0, 0),) * (x.ndim - 2))
        x = x.reshape(b, n, chunk, *x.shape[2:])
        return jnp.moveaxis(jnp.moveaxis(x, 1, 0), 2, 3)

    s, o = lax.scan(_chunk_rule, state.astype(STATE_DTYPE),
                    tuple(split(x) for x in (q, k, v, g, beta)))
    o = jnp.moveaxis(o, 0, 1)                             # (B, n, H, C, V)
    o = jnp.swapaxes(o, 2, 3).reshape(b, n * chunk, h, -1)
    return o[:, :l], s


def kda_recurrent(q, k, v, g, beta, state):
    """The rule a position at a time (``lax.scan``), the same arguments
    and result as :func:`kda_chunked`: the oracle of the chunked form."""
    first = lambda x: jnp.moveaxis(x.astype(jnp.float32), 1, 0)  # noqa: E731
    s, o = lax.scan(lambda s, at: _kda_step_xla(s, *at),
                    state.astype(STATE_DTYPE),
                    tuple(first(x) for x in (q, k, v, g, beta)))
    return jnp.moveaxis(o, 0, 1), s


def _kda_step_xla(s, q, k, v, g, beta):
    """One position: ``s`` (B, H, K, V), ``q``, ``k``, ``g`` (B, H, K),
    ``v`` (B, H, V), ``beta`` (B, H). Returns (the state after, o (B, H,
    V) float32)."""
    f32 = jnp.float32
    q, k, v, g, beta = (x.astype(f32) for x in (q, k, v, g, beta))
    s = jnp.exp(g)[..., None] * s.astype(f32)
    kv = jnp.sum(k[..., None] * s, axis=-2)
    s = s + k[..., None] * (beta[..., None] * (v - kv))[..., None, :]
    return s, jnp.sum(q[..., None] * s, axis=-2)


def _kda_step_kernel(s_ref, q_ref, k_ref, g_ref, v_ref, b_ref, so_ref, o_ref,
                     *, heads: int):
    """One session's heads. ``q``, ``k``, ``g`` come as (K, H) blocks,
    the key channels on the sublanes, so that a head's column meets the
    (K, V) state as it lies; ``v``, ``o`` as (H, V) rows and ``beta`` as
    (1, H)."""
    qt, kt, gt, bt = q_ref[0], k_ref[0], g_ref[0], b_ref[0]
    for h in range(heads):
        s = s_ref[0, h] * jnp.exp(gt[:, h:h + 1])             # (K, V)
        kc = kt[:, h:h + 1]                                   # (K, 1)
        kv = jnp.sum(kc * s, axis=0, keepdims=True)           # (1, V)
        u = bt[:, h:h + 1] * (v_ref[0, h:h + 1, :] - kv)
        s = s + kc * u
        so_ref[0, h] = s
        o_ref[0, h:h + 1, :] = jnp.sum(qt[:, h:h + 1] * s, axis=0,
                                       keepdims=True)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _kda_step_pallas(s, q, k, v, g, beta, interpret: bool = False):
    b, h, kdim, vdim = s.shape
    f32 = jnp.float32
    cols = [jnp.swapaxes(x.astype(f32), 1, 2) for x in (q, k, g)]  # (B,K,H)
    rows = v.astype(f32)
    bt = beta.astype(f32).reshape(b, 1, h)
    col_spec = pl.BlockSpec((1, kdim, h), lambda i: (i, 0, 0))
    state_spec = pl.BlockSpec((1, h, kdim, vdim), lambda i: (i, 0, 0, 0))
    row_spec = pl.BlockSpec((1, h, vdim), lambda i: (i, 0, 0))
    return pl.pallas_call(
        functools.partial(_kda_step_kernel, heads=h),
        grid=(b,),
        in_specs=[state_spec, col_spec, col_spec, col_spec, row_spec,
                  pl.BlockSpec((1, 1, h), lambda i: (i, 0, 0))],
        out_specs=[state_spec, row_spec],
        out_shape=[out_struct(s.shape, STATE_DTYPE, s, q),
                   out_struct((b, h, vdim), f32, s, q)],
        input_output_aliases={0: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name="_kda_step_pallas",
    )(s.astype(STATE_DTYPE), *cols, rows, bt)


def kda_step(state, q, k, v, g, beta, *, backend: str = "auto"):
    """One decode position of every session and head: ``state`` (B, H,
    K, V) float32, ``q``, ``k``, ``g`` (B, H, K), ``v`` (B, H, V),
    ``beta`` (B, H). Returns (the state after, o (B, H, V) float32)."""
    backend = resolve_backend(backend, "kda_step")
    if backend == "xla":
        return _kda_step_xla(state, q, k, v, g, beta)
    return _kda_step_pallas(state, q, k, v, g, beta,
                            interpret=backend == "pallas_interpret")
