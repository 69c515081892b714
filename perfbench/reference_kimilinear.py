"""The plain reference for Kimi-Linear-48B-A3B-Instruct (`model_type:
kimi_linear`): its forward pass in straightforward `jax.numpy` and
float32, every matmul at `highest` precision (each pass also runs under
`jax.default_matmul_precision("highest")`). No kernel, no cache, no
chunked form, no batching of sessions. It imports nothing of the program
and takes no array from it; the weights come from the seed through
`weights_kimilinear.py`'s table, a group of a layer's leaves at a time
(the served bfloat16 values, held in float32).

    x <- x + Mix_i(RMSNorm x);  x <- x + FFN_i(RMSNorm x)      (eps 1e-5)
    KDA (kda_layers): [q | k | v] = silu(causal depthwise conv_4(y W_qkv))
        q = l2norm(q) 128^-1/2, k = l2norm(k)           (per head of 128)
        g = -exp(A_log[h]) softplus(y W_fa W_fb + dt_b), beta = sigmoid(y W_b)
        per head, a position at a time:
            S <- diag(exp g) S;  S <- S + beta k (v - S^T k)^T;  o = S^T q
        out = W_o [RMSNorm_head(o) o_g sigmoid(y W_ga W_gb)]
    MLA (full_attn_layers): q = y W_q (32 heads of 128 + 64);
        [c | k_r] = y W_kva, c = RMSNorm_512(c) kv_g
        [k_nope_h | v_h] = c W_kvb, k_h = [k_nope_h | k_r]  (no rotation:
        mla_use_nope); score = q_h . k_h 192^-1/2, softmax over s <= t
        out = concat_h(p v_h) W_o
    FFN: layer 1 a dense SwiGLU of 9216; the others s = sigmoid(h W_r) over
        256 experts, the top 8 on s + bias, weights the 8 s over their sum
        x 2.446, plus the shared SwiGLU expert (`reference_dsv32.ffn_part`)
    logits = RMSNorm(x) W_head

The share is the configuration's: the router runs over
`router_experts`, the experts `[first_expert_held, first_expert_held +
num_experts)` are computed and what the others would add is left out;
the vocabulary is the slice.

Two passes, as for the other session cells. `context_pass` runs the
forward over one context and keeps, for every layer, what later tokens
need of it: a KDA layer's state and the last three projected inputs (the
convolution's tail), a latent layer's rows. `tails_pass` runs tails
that go on from that context, each from the context's state and seeing
the context's rows and its own. A tails pass can be told which experts
its tokens go to (`forced`: the program's own choice), so that a choice
between near-tied scores is compared as a choice (`routing_miss`) and
the logits under the same one.

`mode` names a pass's control: a lower precision of every weight
matmul (`int8`), or of the KDA state alone (`state_bf16`: the state
held in bfloat16 from one position to the next, the rule's arithmetic
in float32, as a program that halves the state's bytes would compute
it); `fault` plants in a tails pass what a wrong program would do,
over the sound context: `no_decay` (alpha = 1), `beta_one` (beta = 1),
`no_out_gate` (the output gate dropped), `state_reset` (the KDA states
zero at the turn's start), `conv_tail_reset` (the convolutions' tails
zero at the turn's start), `latent_rope` (the 64-wide part of queries
and keys turned by plain rope, theta 10000), `no_shared` (the shared
expert dropped).

Departures: none from the equations; the l2norm's epsilon (1e-6) and the
other choices the configuration file lists under `assumed` are the
program's too.
"""

from __future__ import annotations

import dataclasses
import functools
import sys

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from . import weights, weights_kimilinear
from .reference_dsv32 import (HEAD_GROUP, HI, QUERY_BLOCK, _mm, _padded, _rms,
                              _visible, ffn_part, logit_gaps, routing_miss)

__all__ = ["Dims", "FAULTS", "context_pass", "tails_pass", "forward",
           "logit_gaps", "routing_miss"]

FAULTS = ("no_decay", "beta_one", "no_out_gate", "state_reset",
          "conv_tail_reset", "latent_rope", "no_shared")
# the controls that lower the KDA state's precision, not the weight
# matmuls', as (exponent, mantissa) bits: bfloat16's
STATE_MODES = {"state_bf16": (8, 7)}
L2_EPS = 1e-6


def _split(mode) -> tuple:
    """A pass's ``mode`` as (the weight matmuls' mode, the precision the
    KDA state is held in between positions: None for float32)."""
    if mode in STATE_MODES:
        return None, STATE_MODES[mode]
    return mode, None


@dataclasses.dataclass(frozen=True)
class Dims:
    d: int
    heads: int
    kv_rank: int
    nope: int
    rope: int
    v: int
    kda_heads: int
    kda_dim: int
    taps: int
    kinds: tuple        # by layer: "kda" or "latent"
    router_experts: int
    held_first: int
    held: int
    top_k: int
    route_scale: float
    vocab: int
    layers: int
    first_dense: int
    theta: float
    eps: float
    groups: int = 1
    topk_groups: int = 1

    @staticmethod
    def of(cfg: dict) -> "Dims":
        h, dk, taps = weights_kimilinear.kda_dims(cfg)
        layers = cfg["num_hidden_layers"]
        return Dims(
            cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["kv_lora_rank"], cfg["qk_nope_head_dim"],
            cfg["qk_rope_head_dim"], cfg["v_head_dim"], h, dk, taps,
            tuple(weights_kimilinear.kind(cfg, i) for i in range(layers)),
            cfg["router_experts"], cfg["first_expert_held"],
            cfg["num_experts"], cfg["num_experts_per_token"],
            float(cfg["routed_scaling_factor"]), cfg["vocab_size"], layers,
            cfg["first_k_dense_replace"], float(cfg["rope_theta"]),
            float(cfg["rms_norm_eps"]), cfg["num_expert_group"],
            cfg["topk_group"])


# --------------------------------------------------------------------------
# Kimi Delta Attention
# --------------------------------------------------------------------------

def _l2(x):
    return x * lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + L2_EPS)


def kda_inputs(w: dict, y, tail, dims: Dims, mode=None, fault: str = ""):
    """The rule's inputs for one sequence: y (N, d) the normed layer
    input, ``tail`` (taps - 1, 3 P) the projected inputs before it.
    Returns (q, k, v (N, H, D), g (N, H, D), beta (N, H), gate (N, P),
    the tail after it)."""
    n, h, dk = y.shape[0], dims.kda_heads, dims.kda_dim
    proj = _mm(y, w["qkv_W"], mode)
    seen = jnp.concatenate([tail, proj])
    conv = sum(seen[j:j + n] * w["conv_W"][j] for j in range(dims.taps))
    q, k, v = (a.reshape(n, h, dk) for a in
               jnp.split(jax.nn.silu(conv), 3, axis=-1))
    q, k = _l2(q) * dk ** -0.5, _l2(k)
    g = (-jnp.exp(w["A_log"])[:, None]
         * jax.nn.softplus(_mm(_mm(y, w["fa_W"], mode), w["fb_W"], mode)
                           + w["dt_b"]).reshape(n, h, dk))
    beta = jax.nn.sigmoid(_mm(y, w["b_W"], mode))
    if fault == "no_decay":
        g = jnp.zeros_like(g)
    if fault == "beta_one":
        beta = jnp.ones_like(beta)
    gate = jax.nn.sigmoid(_mm(_mm(y, w["ga_W"], mode), w["gb_W"], mode))
    if fault == "no_out_gate":
        gate = jnp.ones_like(gate)
    return q, k, v, g, beta, gate, seen[n:]


def delta_rule(state, q, k, v, g, beta, held=None):
    """The rule a position at a time from ``state`` (H, D, D): keys by
    values, rounded to ``held`` (exponent, mantissa bits) from one
    position to the next. The rounding is `lax.reduce_precision`: the
    TPU compiler may drop a cast to bfloat16 and back, and then nothing
    is rounded. Returns (o (N, H, D), the state after the last)."""
    def one(s, at):
        qt, kt, vt, gt, bt = at
        s = jnp.exp(gt)[:, :, None] * s
        kv = jnp.einsum("hkv,hk->hv", s, kt, precision=HI)
        s = s + bt[:, None, None] * kt[:, :, None] * (vt - kv)[:, None, :]
        o = jnp.einsum("hkv,hk->hv", s, qt, precision=HI)
        if held is not None:
            s = lax.reduce_precision(s, *held)
        return s, o

    state, o = lax.scan(one, state, (q, k, v, g, beta))
    return o, state


def kda_mixer(w: dict, y, tail, state, dims: Dims, mode=None, fault=""):
    """KDA of one sequence y (N, d) from the tail and state before it.
    Returns (out (N, d), the tail and the state after)."""
    mode, held = _split(mode)
    q, k, v, g, beta, gate, tail = kda_inputs(w, y, tail, dims, mode, fault)
    o, state = delta_rule(state, q, k, v, g, beta, held)
    o = _rms(o, w["o_g"], dims.eps).reshape(y.shape[0], -1) * gate
    return _mm(o, w["out_W"], mode), tail, state


@functools.partial(jax.jit, static_argnames=("dims", "mode", "fault"))
def kda_part(w, x, tails, states, dims, mode, fault):
    """x + KDA(RMSNorm(x)) of sequences x (R, N, d), each from its own
    tail and state. Returns (x, the tails and states after, the mixer's
    output's size against the stream's)."""
    y = _rms(x, w["ln1_g"], dims.eps)
    out, tails, states = jax.vmap(
        lambda yy, tt, ss: kda_mixer(w, yy, tt, ss, dims, mode, fault))(
            y, tails, states)
    return (x + out, tails, states,
            jnp.sqrt(jnp.mean(out * out) / jnp.mean(x * x)))


# --------------------------------------------------------------------------
# latent attention, un-absorbed, no positions
# --------------------------------------------------------------------------

def _rope(x, pos, dims: Dims):
    """Plain rope on the last axis (pairs (i, i + 32)): only the
    `latent_rope` fault turns anything."""
    half = dims.rope // 2
    freqs = dims.theta ** (-np.arange(half, dtype=np.float64) / half)
    ang = pos.astype(jnp.float32)[:, None] * jnp.asarray(freqs,
                                                         jnp.float32)[None]
    shape = (x.shape[0],) + (1,) * (x.ndim - 2) + (half,)
    cos, sin = jnp.cos(ang).reshape(shape), jnp.sin(ang).reshape(shape)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def key_rows(w: dict, y, dims: Dims, mode=None) -> dict:
    """What the tokens with normed layer input y (N, d) are to a query:
    the normed latent and the 64-wide key part all heads share, as
    projected."""
    kv = _mm(y, w["kva_W"], mode)
    return {"c_kv": _rms(kv[:, :dims.kv_rank], w["kv_g"], dims.eps),
            "k_r": kv[:, dims.kv_rank:]}


def attention(w: dict, y, pos_q, seg_q, keys: dict, dims: Dims, mode=None,
              turned: bool = False):
    """Multi-head attention of the queries with normed layer input y
    (Q, d) over all of ``keys`` (`key_rows` of N tokens, with their
    ``pos`` and ``seg``) that each may see; ``turned`` rotates the
    64-wide parts by their positions (the fault). The heads
    `HEAD_GROUP` at a time, each by blocks of queries. Returns out (Q,
    d)."""
    n, h = y.shape[0], dims.heads
    cut = lambda a: a.reshape(n // QUERY_BLOCK, QUERY_BLOCK,  # noqa: E731
                              *a.shape[1:])
    pos, seg = keys["pos"], keys["seg"]
    n_keys = pos.shape[0]
    hg = min(HEAD_GROUP, h)
    grouped = lambda m, width: m.reshape(  # noqa: E731
        m.shape[0], h // hg, hg * width).transpose(1, 0, 2)
    k_r = _rope(keys["k_r"], pos, dims) if turned else keys["k_r"]
    scale = (dims.nope + dims.rope) ** -0.5

    def group(ws):
        q = _mm(y, ws[0], mode).reshape(n, hg, dims.nope + dims.rope)
        if turned:
            q = jnp.concatenate([q[..., :dims.nope],
                                 _rope(q[..., dims.nope:], pos_q, dims)], -1)
        kvh = _mm(keys["c_kv"], ws[1], mode).reshape(n_keys, hg,
                                                     dims.nope + dims.v)
        k = jnp.concatenate(
            [kvh[..., :dims.nope],
             jnp.broadcast_to(k_r[:, None, :], (n_keys, hg, dims.rope))], -1)
        v = kvh[..., dims.nope:]

        def attend(args):
            qb, pq, sq = args
            seen = _visible(pq, sq, pos, seg)
            s = jnp.einsum("qhd,nhd->hqn", qb, k, precision=HI) * scale
            p = jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf), -1)
            return jnp.einsum("hqn,nhd->qhd", p, v, precision=HI)

        return lax.map(attend, (cut(q), cut(pos_q), cut(seg_q))).reshape(
            n, hg, dims.v)

    o = lax.map(group, (grouped(w["q_W"], dims.nope + dims.rope),
                        grouped(w["kvb_W"], dims.nope + dims.v)))
    o = o.transpose(1, 0, 2, 3).reshape(n, h * dims.v)
    return _mm(o, w["out_W"], mode)


@functools.partial(jax.jit, static_argnames=("dims", "mode", "turned"))
def latent_part(w, x, pos_q, seg_q, held, dims, mode, turned):
    """x + Attn(RMSNorm(x)) of the queries x (Q, d) over the keys
    ``held`` (earlier tokens' `key_rows` with ``pos`` and ``seg``) and
    their own. Returns (x, the queries' own rows, attention's output's
    size against the stream's)."""
    y = _rms(x, w["ln1_g"], dims.eps)
    rows = key_rows(w, y, dims, mode)
    keys = {k: jnp.concatenate([held[k], v]) for k, v in
            dict(rows, pos=pos_q, seg=seg_q).items()}
    a = attention(w, y, pos_q, seg_q, keys, dims, mode, turned)
    return x + a, rows, jnp.sqrt(jnp.mean(a * a) / jnp.mean(x * x))


@functools.partial(jax.jit, static_argnames=("dims", "mode"))
def _logits(x, lnf_g, head_w, dims, mode):
    return _mm(_rms(x, lnf_g, dims.eps), head_w, mode)


class Weights:
    """The seed's leaves in float32, a group at a time."""

    def __init__(self, cfg: dict, seed: int):
        self.cfg, self.key = cfg, weights.seed_key(seed)

    def leaves(self, names, prefix="") -> dict:
        drawn = weights.make_leaves(
            self.key, weights_kimilinear.indexed(self.cfg, names),
            jnp.float32, via=jnp.bfloat16)
        return {k[len(prefix):]: v.astype(jnp.float32) for k, v
                in weights_kimilinear.finish(self.cfg, drawn).items()}

    def layer(self, i: int, ffn: bool) -> dict:
        """Layer i's mixer leaves, or its FFN's."""
        names = weights_kimilinear.layer_names(self.cfg, i)
        of_ffn = {k for k in names
                  if "_ff" in k or "_moe_" in k or "_ln2_" in k}
        return self.leaves(of_ffn if ffn else names - of_ffn, f"L{i}_")


def _fresh(dims: Dims, rows: int) -> tuple:
    """Zero tails and states for ``rows`` sequences."""
    p = dims.kda_heads * dims.kda_dim
    return (jnp.zeros((rows, dims.taps - 1, 3 * p)),
            jnp.zeros((rows, dims.kda_heads, dims.kda_dim, dims.kda_dim)))


def context_pass(cfg: dict, seed: int, context: np.ndarray,
                 mode=None) -> list:
    """The forward over one context (T,): for every layer what later
    tokens need of it (float32): a KDA layer's `tail` and `state`, a
    latent layer's rows. A layer's weights at a time. Sound unless
    ``mode`` lowers its precision: what a program of that precision
    would have prefilled (the control)."""
    dims, draw = Dims.of(cfg), Weights(cfg, seed)
    t = context.shape[0]
    ids = jnp.asarray(_padded(context.astype(np.int32), 0))
    pos = jnp.asarray(_padded(np.arange(t, dtype=np.int32), 0))
    # a padded token is a segment of its own: it sees itself only
    seg = jnp.asarray(_padded(np.zeros(t, np.int32), -1))
    none = {"c_kv": jnp.zeros((0, dims.kv_rank)),
            "k_r": jnp.zeros((0, dims.rope)),
            "pos": jnp.zeros((0,), jnp.int32),
            "seg": jnp.zeros((0,), jnp.int32)}
    state = []
    with jax.default_matmul_precision("highest"):
        x = draw.leaves({"tok_emb"})["tok_emb"][ids]
        for i in range(dims.layers):
            w = draw.layer(i, False)
            if dims.kinds[i] == "kda":
                xt, tail, s, _ = kda_part(w, x[None, :t], *_fresh(dims, 1),
                                          dims, mode, "")
                x = x.at[:t].set(xt[0])
                state.append({"tail": tail[0], "state": s[0]})
            else:
                x, rows, _ = latent_part(w, x, pos, seg, none, dims,
                                         _split(mode)[0], False)
                state.append({k: v[:t] for k, v in rows.items()})
            x, _ = ffn_part(draw.layer(i, True), x, i < dims.first_dense,
                            dims, _split(mode)[0], "", None)
    return state


def tails_pass(cfg: dict, seed: int, state: list, tails: np.ndarray,
               runs: list) -> list:
    """The forward over tails (R, n) that go on from a context
    (`context_pass`'s ``state`` of T tokens): tail r at positions T ..
    T + n - 1, from the context's states and seeing its rows and
    itself. Every entry of ``runs`` is one such forward, a dict of
    `mode` (the control: the precision of every weight matmul, or the
    KDA state's, as the module's docstring says), `fault`
    (one of `FAULTS`) and `forced`: experts (expert layers, R, n, k)
    that the tails' routing is to use in place of the router's own
    choice, which is still returned. The runs share a layer's weights,
    drawn once. Returns for each a dict: `logits` (R, n, vocab) and
    `experts` (expert layers, R, n, k), the router's own choice. The
    first run's share of each mixer's output in the stream, layer by
    layer, goes on standard error."""
    dims, draw = Dims.of(cfg), Weights(cfg, seed)
    latent = next(s for s in state if "c_kv" in s)
    t, (r, n) = latent["c_kv"].shape[0], tails.shape
    ids = jnp.asarray(_padded(tails.reshape(-1).astype(np.int32), 0))
    pos = jnp.asarray(_padded(np.tile(t + np.arange(n, dtype=np.int32), r),
                              0))
    seg = jnp.asarray(_padded(np.repeat(1 + np.arange(r, dtype=np.int32), n),
                              -1))
    q = ids.shape[0]
    held_pos = {"pos": jnp.arange(t, dtype=jnp.int32),
                "seg": jnp.zeros((t,), jnp.int32)}
    own_choice = [[] for _ in runs]
    shares = []
    with jax.default_matmul_precision("highest"):
        ends = draw.leaves({"tok_emb", "lnf_g", "head_W"})
        xs = [ends["tok_emb"][ids] for _ in runs]
        forced = [_forced_experts(run.get("forced"), r * n, q, dims)
                  for run in runs]
        for i in range(dims.layers):
            w = draw.layer(i, False)
            for j, run in enumerate(runs):
                fault = run.get("fault", "")
                if dims.kinds[i] == "kda":
                    tail = state[i]["tail"] * (fault != "conv_tail_reset")
                    s = state[i]["state"] * (fault != "state_reset")
                    xt, _, _, share = kda_part(
                        w, xs[j][:r * n].reshape(r, n, -1),
                        jnp.broadcast_to(tail, (r,) + tail.shape),
                        jnp.broadcast_to(s, (r,) + s.shape), dims,
                        run.get("mode"), fault)
                    xs[j] = xs[j].at[:r * n].set(xt.reshape(r * n, -1))
                else:
                    xs[j], _, share = latent_part(
                        w, xs[j], pos, seg, dict(state[i], **held_pos), dims,
                        _split(run.get("mode"))[0], fault == "latent_rope")
                if j == 0:
                    shares.append(float(share))
            w = draw.layer(i, True)
            moe = i - dims.first_dense
            for j, run in enumerate(runs):
                xs[j], own = ffn_part(
                    w, xs[j], moe < 0, dims, _split(run.get("mode"))[0],
                    run.get("fault", ""),
                    None if moe < 0 else jnp.asarray(forced[j][moe]))
                if own is not None:
                    own_choice[j].append(
                        np.asarray(own)[:r * n].reshape(r, n, -1))
        print("reference: each mixer's output against the stream, by "
              "layer: " + " ".join(f"{dims.kinds[i]} {s:.3f}"
                                   for i, s in enumerate(shares)),
              file=sys.stderr)
        return [{"logits": np.asarray(_logits(
                    xs[j][:r * n], ends["lnf_g"], ends["head_W"], dims,
                    _split(run.get("mode"))[0])).reshape(r, n, -1),
                 "experts": np.stack(own_choice[j])}
                for j, run in enumerate(runs)]


def _forced_experts(forced, live: int, q: int, dims: Dims) -> np.ndarray:
    """`tails_pass`'s ``forced`` as it is handed to the layers: (expert
    layers, Q, k), the padded queries' rows negative (their own choice
    stands), as every row is where nothing is forced."""
    experts = np.full((dims.layers - dims.first_dense, q, dims.top_k), -1,
                      np.int32)
    if forced is not None:
        experts[:, :live] = forced.reshape(forced.shape[0], live, -1)
    return experts


def forward(cfg: dict, seed: int, context: np.ndarray, tails: np.ndarray,
            **run) -> dict:
    """One run of `tails_pass` after `context_pass`: a full causal
    forward over ``context + tail`` for every tail."""
    return tails_pass(cfg, seed, context_pass(cfg, seed, context), tails,
                      [run])[0]
