"""The plain reference for Phi-4-mini-flash-reasoning (`model_type:
phi4flash`, a SambaY stack): its forward pass over ONE row of ids in
straightforward `jax.numpy` and float32 with every matmul at `highest`
precision. No kernel, no cache, no batch, no paired layout: a
`lax.scan` over positions for the state space, for every attention
layer two masked softmaxes a pair over all positions, a block of
queries at a time. It imports nothing of the program and takes no array
from it; the weights come from the seed through `weights_phi4flash.py`'s
table, a layer at a time (the served bfloat16 values, held in float32).

Every layer: `x = x + mixer(LN1(x))`, `x = x + down(up * silu(gate))
(LN2(x))`; LayerNorm with gain and bias; a final LayerNorm and the tied
head `x @ tok_emb.T`. No positional encoding. The mixer by layer
(`weights_phi4flash.layer_kind`):

    ssm    [u, z] = y W_in; u = silu(conv1d_causal(u, 4 taps) + b)
           [dt, B, C] = u W_x; delta = softplus(dt W_dt + b_dt)
           h_t = exp(delta_t A) h_{t-1} + (delta_t u_t) outer B_t,
           A = -exp(A_log); s_t = h_t C_t + D u_t
           out = (s_t silu(z_t)) W_out;  the memory layer hands on s_t
    gmu    out = (m_t silu(y W_1)) W_2, m the memory layer's s
    swa    qkv = y W_qkv + b; 20 query pairs (q1, q2) = heads (2j, 2j+1),
    full   10 key/value pairs = kv heads (2m, 2m+1), query pair j on
           pair j // 2; a position sees itself and, with a window, the
           window - 1 before it:
           a1 = softmax(q1 k1^T / 8) [v1|v2], a2 = softmax(q2 k2^T / 8) [v1|v2]
           lam = exp(lq1 . lk1) - exp(lq2 . lk2) + lam0,
           lam0 = 0.8 - 0.6 exp(-0.3 i)
           out = (RMSNorm_128(a1 - lam a2) g (1 - lam0)) W_o + b_o
    cross  q = y W_q + b; keys and values are the full-attention
           layer's, of every position up to the query's own; the rest
           as above with its own lam, g, W_o

`mode` lowers the precision of every weight matmul (the control).
`fault` plants, at the positions from `fault_from` on (a turn's
scanned positions, over the sound context: the least it can read),
what a wrong program would do: `no_lam` (the lam term dropped),
`no_subnorm` (the 128-norm dropped), `no_memory` (the memory units'
gate fed zeros), `state_reset` (every state and convolution tail reset
at `fault_from`), `window_short` / `window_long` (the window 511 / 513
where it is 512: one less, one more), `cross_own_kv` (a cross layer
reads keys and values made of its OWN input by the full-attention
layer's projection), `skip_newest` (the `SKIPPED` positions before
`fault_from` not attended: a tile of the cache dropped). Two more
(`PRECISION_FAULTS`, for the tests' tolerances, at every position):
`state_bf16` rounds the state-space state to bfloat16 after every
position, `softmax_bf16` the scores and the softmax's weights.
"""

from __future__ import annotations

import dataclasses
import functools
import sys

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from . import weights, weights_phi4flash as wts
from .reference_dsv32 import _layer_norm, _mm, logit_gaps

__all__ = ["Dims", "FAULTS", "forward", "logit_gaps"]

HI = lax.Precision.HIGHEST
FAULTS = ("no_lam", "no_subnorm", "no_memory", "state_reset",
          "window_short", "window_long", "cross_own_kv", "skip_newest")
PRECISION_FAULTS = ("state_bf16", "softmax_bf16")
SKIPPED = 512
QUERY_BLOCK = 128


@dataclasses.dataclass(frozen=True)
class Dims:
    d: int
    heads: int
    kv_heads: int
    hd: int
    window: int
    e: int
    n: int
    taps: int
    r: int
    eps: float
    layers: int

    @staticmethod
    def of(cfg: dict) -> "Dims":
        z = wts.sizes(cfg)
        return Dims(z["d"], z["h"], z["hkv"], z["hd"], cfg["sliding_window"],
                    z["e"], z["n"], z["taps"], z["r"],
                    float(cfg["layer_norm_eps"]), cfg["num_hidden_layers"])


def _rms(x, g, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def _late(pos, fault_from):
    """(T,) True at the positions a fault stands in."""
    return pos >= fault_from


def state_space(w: dict, y, pos, dims: Dims, mode, fault, fault_from):
    """The Mamba mixer over the row y (T, d). Returns (out (T, d), s
    (T, E) the scan's output before the gate)."""
    e, n, r = dims.e, dims.n, dims.r
    uz = _mm(y, w["in_W"], mode)
    u, z = uz[:, :e], uz[:, e:]
    reset = (pos == fault_from) if fault == "state_reset" \
        else jnp.zeros_like(pos, bool)
    a = -jnp.exp(w["A_log"])                                    # (N, E)

    # tap j of the 4 meets the input 3 - j positions back; a reset at
    # `fault_from` hides what lies before it from the positions after
    back = jnp.arange(dims.taps - 1, -1, -1)
    src = pos[:, None] - back[None, :]                          # (T, K)
    known = src >= 0
    if fault == "state_reset":
        known &= ~((pos[:, None] >= fault_from) & (src < fault_from))
    taps = jnp.where(known[..., None], u[jnp.clip(src, 0)], 0.0)  # (T, K, E)
    c = jnp.sum(taps * w["conv_W"][None], axis=1)
    u = jax.nn.silu(c + w["conv_b"])
    dbc = _mm(u, w["x_W"], mode)
    delta = jax.nn.softplus(_mm(dbc[:, :r], w["dt_W"], mode) + w["dt_b"])

    def step(h, at):
        u_t, d_t, b_t, c_t, reset_t = at
        h = jnp.where(reset_t, 0.0, h)
        h = jnp.exp(d_t[None, :] * a) * h + (d_t * u_t)[None, :] * b_t[:, None]
        if fault == "state_bf16":
            h = h.astype(jnp.bfloat16).astype(jnp.float32)
        return h, jnp.sum(h * c_t[:, None], axis=0) + w["D"] * u_t

    _, s = lax.scan(step, jnp.zeros((n, e)),
                    (u, delta, dbc[:, r:r + n], dbc[:, r + n:], reset),
                    unroll=16)
    return _mm(s * jax.nn.silu(z), w["out_W"], mode), s


def gated_memory(w: dict, y, memory, pos, mode, fault, fault_from):
    gate = jax.nn.silu(_mm(y, w["in_W"], mode))
    if fault == "no_memory":
        gate = jnp.where(_late(pos, fault_from)[:, None], 0.0, gate)
    return _mm(memory * gate, w["out_W"], mode)


def pairs_of(x, n_pairs: int, hd: int):
    """(T, 2 n_pairs hd) columns as (T, n_pairs, 2, hd): heads (2j, 2j +
    1) are pair j's first and second."""
    return x.reshape(x.shape[0], n_pairs, 2, hd)


def differential(w: dict, q, k, v, pos, layer, window, dims: Dims,
                 fault: str, fault_from):
    """Differential attention of the row's queries q (T, H hd) over the
    keys and values k, v (T, H_kv hd) of the same positions, causal,
    within ``window`` (T,) positions a query (0 = all). Returns the
    normed difference (T, H hd) that meets W_o."""
    t, hd = q.shape[0], dims.hd
    qp, kp = dims.heads // 2, dims.kv_heads // 2
    q = pairs_of(q, qp, hd)
    k, v = pairs_of(k, kp, hd), pairs_of(v, kp, hd)
    vv = v.reshape(t, kp, 2 * hd)                               # [v1 | v2]
    lam0 = 0.8 - 0.6 * jnp.exp(-0.3 * layer)    # ``layer`` may be traced
    lq1, lk1, lq2, lk2 = w["lam"]
    lam = jnp.exp(jnp.sum(lq1 * lk1)) - jnp.exp(jnp.sum(lq2 * lk2)) + lam0
    late = _late(pos, fault_from)
    lam = jnp.where(late & (fault == "no_lam"), 0.0, lam)       # (T,)
    pad = -t % QUERY_BLOCK
    cut = lambda a: jnp.pad(a, [(0, pad)] + [(0, 0)] * (a.ndim - 1)).reshape(  # noqa: E731
        -1, QUERY_BLOCK, *a.shape[1:])

    def block(args):
        qb, pq, wq, lateq = args                                # (Q, ...)
        seen = pos[None, :] <= pq[:, None]
        seen &= (wq[:, None] == 0) | (pq[:, None] - pos[None, :] < wq[:, None])
        if fault == "skip_newest":
            seen &= ~(lateq[:, None] & (pos[None, :] < fault_from)
                      & (pos[None, :] >= fault_from - SKIPPED))
        out = []
        for half in (0, 1):
            # query pair j reads key/value pair j // (qp / kp)
            kk = jnp.repeat(k[:, :, half], qp // kp, axis=1)    # (T, qp, hd)
            s = jnp.einsum("qjd,njd->jqn", qb[:, :, half], kk,
                           precision=HI) * hd ** -0.5
            if fault == "softmax_bf16":
                s = s.astype(jnp.bfloat16)
            p = jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf), -1)
            out.append(jnp.einsum("jqn,njd->qjd", p.astype(jnp.float32),
                                  jnp.repeat(vv, qp // kp, axis=1),
                                  precision=HI))
        return out[0], out[1]

    a1, a2 = lax.map(block, (cut(q), cut(pos), cut(window), cut(late)))
    a1, a2 = (a.reshape(-1, qp, 2 * hd)[:t] for a in (a1, a2))
    diff = a1 - lam[:, None, None] * a2
    normed = _rms(diff, w["sub_g"], dims.eps)
    if fault == "no_subnorm":
        normed = jnp.where(late[:, None, None], diff, normed)
    return (normed * (1.0 - lam0)).reshape(t, qp * 2 * hd)


def window_of(pos, kind: str, dims: Dims, fault: str, fault_from):
    """(T,) the window of each query position."""
    if kind != "swa":
        return jnp.zeros_like(pos)
    wrong = {"window_short": dims.window - 1,
             "window_long": dims.window + 1}.get(fault, dims.window)
    return jnp.where(_late(pos, fault_from), wrong, dims.window)


@functools.partial(jax.jit, static_argnames=(
    "kind", "dims", "mode", "fault"))
def mixer_part(w, x, pos, shared, kind, layer, dims, mode, fault, fault_from):
    """x + mixer(LN1(x)) of the row x (T, d) in layer ``layer`` (an
    operand: the layers of a kind share one compiled program).
    ``shared`` holds what
    earlier layers left: `memory` (T, E), `k` / `v` (T, H_kv hd) and the
    full-attention layer's `kv_W`, `kv_b` (for `cross_own_kv`). Returns
    (x, what this layer leaves, the mixer's output's size against the
    stream's)."""
    y = _layer_norm(x, w["ln1_g"], w["ln1_b"], dims.eps)
    left = {}
    q_cols, kv_cols = dims.heads * dims.hd, dims.kv_heads * dims.hd
    if kind == "ssm":
        out, left["memory"] = state_space(w, y, pos, dims, mode, fault,
                                          fault_from)
    elif kind == "gmu":
        out = gated_memory(w, y, shared["memory"], pos, mode, fault,
                           fault_from)
    else:
        window = window_of(pos, kind, dims, fault, fault_from)
        if kind == "cross":
            q = _mm(y, w["q_W"], mode) + w["q_b"]
            k, v = shared["k"], shared["v"]
        else:
            qkv = _mm(y, w["qkv_W"], mode) + w["qkv_b"]
            q, k, v = (qkv[:, :q_cols], qkv[:, q_cols:q_cols + kv_cols],
                       qkv[:, q_cols + kv_cols:])
            if kind == "full":
                left.update(k=k, v=v, kv_W=w["qkv_W"][:, q_cols:],
                            kv_b=w["qkv_b"][q_cols:])
        a = differential(w, q, k, v, pos, layer, window, dims, fault,
                         fault_from)
        if kind == "cross" and fault == "cross_own_kv":
            own = _mm(y, shared["kv_W"], mode) + shared["kv_b"]
            wrong = differential(w, q, own[:, :kv_cols], own[:, kv_cols:],
                                 pos, layer, window, dims, fault, fault_from)
            a = jnp.where(_late(pos, fault_from)[:, None], wrong, a)
        out = _mm(a, w["out_W"], mode) + w["out_b"]
    return x + out, left, jnp.sqrt(jnp.mean(out * out) / jnp.mean(x * x))


@functools.partial(jax.jit, static_argnames=("dims", "mode"))
def mlp_part(w, x, dims, mode):
    """x + MLP(LN2(x)), and the MLP's output's size against the
    stream's."""
    y = _layer_norm(x, w["ln2_g"], w["ln2_b"], dims.eps)
    out = _mm(jax.nn.silu(_mm(y, w["ff1_W"], mode)) * _mm(y, w["ff3_W"], mode),
              w["ff2_W"], mode)
    return x + out, jnp.sqrt(jnp.mean(out * out) / jnp.mean(x * x))


@functools.partial(jax.jit, static_argnames=("dims", "mode"))
def _logits(x, w, dims, mode):
    return _mm(_layer_norm(x, w["lnf_g"], w["lnf_b"], dims.eps),
               w["tok_emb"].T, mode)


class Weights:
    """The seed's leaves in float32, a group at a time."""

    def __init__(self, cfg: dict, seed: int):
        self.cfg, self.key = cfg, weights.seed_key(seed)

    def leaves(self, names, prefix="") -> dict:
        drawn = weights.make_leaves(
            self.key, wts.indexed(self.cfg, names), jnp.float32,
            via=jnp.bfloat16)
        return {k[len(prefix):]: v for k, v
                in wts.finish(self.cfg, drawn).items()}

    def layer(self, i: int) -> dict:
        return self.leaves(wts.layer_names(self.cfg, i), f"L{i}_")


def forward(cfg: dict, seed: int, ids: np.ndarray, n_last: int, *,
            mode=None, fault: str = "", fault_from=None,
            quiet: bool = False) -> np.ndarray:
    """The full causal forward over one row ``ids`` (T,). Returns the
    logits (n_last, vocab) of the last ``n_last`` positions. ``fault``
    stands in the positions from ``fault_from`` on (default: the first
    of those ``n_last``)."""
    dims, draw = Dims.of(cfg), Weights(cfg, seed)
    t = ids.shape[0]
    fault_from = jnp.int32(t - n_last if fault_from is None else fault_from)
    pos = jnp.arange(t, dtype=jnp.int32)
    ends = draw.leaves({"tok_emb", "lnf_g", "lnf_b"})
    x = ends["tok_emb"][jnp.asarray(ids, jnp.int32)]
    shared, shares = {}, []
    for i in range(dims.layers):
        w = draw.layer(i)
        kind = wts.layer_kind(cfg, i)
        x, left, mixer = mixer_part(w, x, pos, shared, kind, jnp.float32(i),
                                    dims, mode, fault, fault_from)
        if kind != "ssm" or i == wts.memory_layer(cfg):
            shared = {**shared, **left}
        x, mlp = mlp_part(w, x, dims, mode)
        shares.append(f"{kind} {float(mixer):.3f}/{float(mlp):.3f}")
    if not quiet:
        print("reference: the mixer's and the MLP's output against the "
              "stream, by layer: " + " ".join(shares), file=sys.stderr)
    return np.asarray(_logits(x[t - n_last:], ends, dims, mode))
