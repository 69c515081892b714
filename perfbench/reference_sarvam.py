"""The plain reference for sarvam-105b (`model_type: sarvam_mla`): its
forward pass in straightforward `jax.numpy` and float32 with every
matmul at `highest` precision. No kernel, no cache, no absorbed form:
keys and values are built for every head from the latent, every query
attends every position up to its own, the router is the family's
(sigmoid scores, choice on score + bias, weights normalised over the
chosen and scaled) with one shared expert. It imports nothing of the
program and takes no array from it; the weights come from the seed
through `weights_sarvam.py`'s table, a layer at a time (the served
bfloat16 values, held in float32). What it has in common with the
other latent model's reference (rope under YaRN, RMSNorm, SwiGLU, the
router, the expert layer over a share, the lower-precision control) it
takes from `reference_dsv32.py`.

    q = y W_q (or through a normed latent where the configuration has
        q_lora_rank); per head RMSNorm_192(q) * q_g where use_qk_norm;
        q_nope | q_rope, rope on q_rope
    [c | k_r] = y W_kva; c = RMSNorm_512(c) * kv_g; rope on k_r
    [k_nope_h | v_h] = c W_kvb;  k_h = [k_nope_h | k_r]
    score_h(t, s) = q_h(t) . k_h(s) * 192^-0.5 * m^2, softmax over s <= t
    out = concat_h(sum_s p_h(s) v_h(s)) W_o

The share is the configuration's: the router runs over
`router_experts`, the experts `[first_expert_held, first_expert_held +
num_experts)` are computed and what the others would add is left out;
the vocabulary is the slice.

Two passes, as for DeepSeek. `context_pass` runs the forward over one
context and keeps, for every layer, what its tokens are to a later
query (the latent and the rope key). `tails_pass` runs tails that go on
from that context, each seeing the context and itself. A tails pass can
be told which experts its tokens go to (`forced`: the program's own
choice), so that a choice between near-tied scores is compared as a
choice (`routing_miss`) and the logits under the same one.

`mode` lowers the precision of every weight matmul of a pass (the
control); `fault` plants in a tails pass what a wrong program would do,
over the sound context: `skip_newest` (the 512 positions before the
query's own not attended: a tile of the cache dropped), `no_k_rope` (the
rope key's term left out of the scores), `no_q_gain` (the qk-norm's
gain left out), `no_shared`, `no_bias`, `no_scale`.
"""

from __future__ import annotations

import dataclasses
import functools
import sys

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from . import weights, weights_sarvam
from .reference_dsv32 import (HEAD_GROUP, HI, QUERY_BLOCK, _logits, _mm,
                              _padded, _rms, _rope, _visible, ffn_part,
                              logit_gaps, routing_miss, softmax_scale)

__all__ = ["Dims", "FAULTS", "context_pass", "tails_pass", "forward",
           "logit_gaps", "routing_miss"]

FAULTS = ("skip_newest", "no_k_rope", "no_q_gain", "no_shared", "no_bias",
          "no_scale")
SKIPPED = 512


@dataclasses.dataclass(frozen=True)
class Dims:
    d: int
    heads: int
    q_rank: int
    qk_norm: bool
    kv_rank: int
    nope: int
    rope: int
    v: int
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
    yarn_factor: float
    yarn_original: int
    beta_fast: float
    beta_slow: float
    mscale_all_dim: float
    # one group: the router chooses among all experts
    groups: int = 1
    topk_groups: int = 1

    @staticmethod
    def of(cfg: dict) -> "Dims":
        y = cfg["rope_scaling"]
        return Dims(
            cfg["hidden_size"], cfg["num_attention_heads"],
            cfg.get("q_lora_rank") or 0, bool(cfg["use_qk_norm"]),
            cfg["kv_lora_rank"], cfg["qk_nope_head_dim"],
            cfg["qk_rope_head_dim"], cfg["v_head_dim"],
            cfg["router_experts"], cfg["first_expert_held"],
            cfg["num_experts"], cfg["num_experts_per_tok"],
            float(cfg["routed_scaling_factor"]), cfg["vocab_size"],
            cfg["num_hidden_layers"], cfg["first_k_dense_replace"],
            float(cfg["rope_theta"]), float(cfg["rms_norm_eps"]),
            float(y["factor"]), y["original_max_position_embeddings"],
            float(y["beta_fast"]), float(y["beta_slow"]),
            float(y["mscale_all_dim"]))


def key_rows(w: dict, y, pos, dims: Dims, mode=None) -> dict:
    """What the tokens with normed block input y (N, d) are to a query:
    the normed latent and the rotated rope key that all heads share."""
    kv = _mm(y, w["kva_W"], mode)
    return {"c_kv": _rms(kv[:, :dims.kv_rank], w["kv_g"], dims.eps),
            "k_r": _rope(kv[:, dims.kv_rank:], pos, dims)}


def attention(w: dict, y, pos_q, seg_q, keys: dict, dims: Dims, mode=None,
              fault: str = ""):
    """Multi-head attention of the queries with normed block input y
    (Q, d) over all of ``keys`` (`key_rows` of N tokens, with their
    ``pos`` and ``seg``) that each may see. The heads `HEAD_GROUP` at a
    time (so that float32 keys and values of 32k positions fit): a
    group's keys and values are built from the latent, used by blocks
    of queries, and dropped. Returns out (Q, d)."""
    n, h = y.shape[0], dims.heads
    blocks = n // QUERY_BLOCK
    cut = lambda a: a.reshape(blocks, QUERY_BLOCK, *a.shape[1:])  # noqa: E731
    pos, seg = keys["pos"], keys["seg"]
    n_keys = pos.shape[0]
    q_in, w_q = y, w.get("q_W")
    if dims.q_rank:
        q_in = _rms(_mm(y, w["qa_W"], mode), w["qa_g"], dims.eps)
        w_q = w["qb_W"]
    scale = softmax_scale(dims)
    hg = min(HEAD_GROUP, h)
    grouped = lambda m, width: m.reshape(  # noqa: E731
        m.shape[0], h // hg, hg * width).transpose(1, 0, 2)
    k_r = keys["k_r"] * (0.0 if fault == "no_k_rope" else 1.0)

    def group(weights):
        q = _mm(q_in, weights[0], mode).reshape(n, hg, dims.nope + dims.rope)
        if dims.qk_norm:
            q = _rms(q, 1.0 if fault == "no_q_gain" else w["q_g"], dims.eps)
        q = jnp.concatenate([q[..., :dims.nope],
                             _rope(q[..., dims.nope:], pos_q, dims)], -1)
        kvh = _mm(keys["c_kv"], weights[1], mode).reshape(
            n_keys, hg, dims.nope + dims.v)
        k = jnp.concatenate(
            [kvh[..., :dims.nope],
             jnp.broadcast_to(k_r[:, None, :], (n_keys, hg, dims.rope))], -1)
        v = kvh[..., dims.nope:]

        def attend(args):
            qb, pq, sq = args
            seen = _visible(pq, sq, pos, seg)
            if fault == "skip_newest":
                seen &= ~((pos[None, :] < pq[:, None])
                          & (pos[None, :] >= pq[:, None] - SKIPPED))
            s = jnp.einsum("qhd,nhd->hqn", qb, k, precision=HI) * scale
            p = jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf), -1)
            return jnp.einsum("hqn,nhd->qhd", p, v, precision=HI)

        return lax.map(attend, (cut(q), cut(pos_q), cut(seg_q))).reshape(
            n, hg, dims.v)

    o = lax.map(group, (grouped(w_q, dims.nope + dims.rope),
                        grouped(w["kvb_W"], dims.nope + dims.v)))
    o = o.transpose(1, 0, 2, 3).reshape(n, h * dims.v)
    return _mm(o, w["out_W"], mode)


@functools.partial(jax.jit, static_argnames=("dims", "mode", "fault"))
def attention_part(w, x, pos_q, seg_q, held, dims, mode, fault):
    """x + Attn(RMSNorm(x)) of the queries x over the keys ``held``
    (earlier tokens' `key_rows` with ``pos`` and ``seg``) and their
    own. Returns (x, the queries' own rows, attention's output's size
    against the stream's: the share the weight scales are set for)."""
    y = _rms(x, w["ln1_g"], dims.eps)
    rows = key_rows(w, y, pos_q, dims, mode)
    keys = {k: jnp.concatenate([held[k], v]) for k, v in
            dict(rows, pos=pos_q, seg=seg_q).items()}
    a = attention(w, y, pos_q, seg_q, keys, dims, mode, fault)
    return x + a, rows, jnp.sqrt(jnp.mean(a * a) / jnp.mean(x * x))


class Weights:
    """The seed's leaves in float32, a group at a time."""

    def __init__(self, cfg: dict, seed: int):
        self.cfg, self.key = cfg, weights.seed_key(seed)

    def leaves(self, names, prefix="") -> dict:
        drawn = weights.make_leaves(
            self.key, weights_sarvam.indexed(self.cfg, names), jnp.float32,
            via=jnp.bfloat16)
        return {k[len(prefix):]: v for k, v
                in weights_sarvam.finish(self.cfg, drawn).items()}

    def layer(self, i: int, ffn: bool) -> dict:
        """Layer i's attention leaves, or its FFN's."""
        names = weights_sarvam.layer_names(self.cfg, i)
        of_ffn = {k for k in names
                  if "_ff" in k or "_moe_" in k or "_ln2_" in k}
        return self.leaves(of_ffn if ffn else names - of_ffn, f"L{i}_")


def context_pass(cfg: dict, seed: int, context: np.ndarray,
                 mode=None) -> list:
    """The forward over one context (T,): for every layer what its
    tokens are to a later query (`key_rows`, float32). A layer's weights
    at a time. Sound unless ``mode`` lowers its precision: what a
    program of that precision would have prefilled (the control)."""
    dims, draw = Dims.of(cfg), Weights(cfg, seed)
    t = context.shape[0]
    ids = jnp.asarray(_padded(context.astype(np.int32), 0))
    pos = jnp.asarray(_padded(np.arange(t, dtype=np.int32), 0))
    # a padded token is a segment of its own: it sees itself only
    seg = jnp.asarray(_padded(np.zeros(t, np.int32), -1))
    x = draw.leaves({"tok_emb"})["tok_emb"][ids]
    none = {"c_kv": jnp.zeros((0, dims.kv_rank)),
            "k_r": jnp.zeros((0, dims.rope)),
            "pos": jnp.zeros((0,), jnp.int32),
            "seg": jnp.zeros((0,), jnp.int32)}
    state = []
    for i in range(dims.layers):
        x, rows, _ = attention_part(draw.layer(i, False), x, pos, seg, none,
                                    dims, mode, "")
        x, _ = ffn_part(draw.layer(i, True), x, i < dims.first_dense, dims,
                        mode, "", None)
        state.append({k: v[:t] for k, v in rows.items()})
    return state


def tails_pass(cfg: dict, seed: int, state: list, tails: np.ndarray,
               runs: list) -> list:
    """The forward over tails (R, n) that go on from a context
    (`context_pass`'s ``state`` of T tokens): tail r at positions T ..
    T + n - 1 sees the context and itself. Every entry of ``runs`` is
    one such forward, a dict of `mode` (the precision of every weight
    matmul: the control), `fault` (one of `FAULTS`) and `forced`:
    experts (expert layers, R, n, k) that the tails' routing is to use
    in place of the router's own choice, which is still returned. The
    runs share a layer's weights, drawn once. Returns for each a dict:
    `logits` (R, n, vocab) and `experts` (expert layers, R, n, k), the
    router's own choice. The first run's share of attention's output in
    the stream, layer by layer, goes on standard error."""
    dims, draw = Dims.of(cfg), Weights(cfg, seed)
    t, (r, n) = state[0]["c_kv"].shape[0], tails.shape
    ids = jnp.asarray(_padded(tails.reshape(-1).astype(np.int32), 0))
    pos = jnp.asarray(_padded(np.tile(t + np.arange(n, dtype=np.int32), r),
                              0))
    seg = jnp.asarray(_padded(np.repeat(1 + np.arange(r, dtype=np.int32), n),
                              -1))
    q = ids.shape[0]
    held_pos = {"pos": jnp.arange(t, dtype=jnp.int32),
                "seg": jnp.zeros((t,), jnp.int32)}
    ends = draw.leaves({"tok_emb", "lnf_g", "head_W"})
    xs = [ends["tok_emb"][ids] for _ in runs]
    forced = [_forced_experts(run.get("forced"), r * n, q, dims)
              for run in runs]
    own_choice = [[] for _ in runs]
    shares = []
    for i in range(dims.layers):
        held = dict(state[i], **held_pos)
        moe = i - dims.first_dense
        w = draw.layer(i, False)
        for j, run in enumerate(runs):
            xs[j], _, share = attention_part(
                w, xs[j], pos, seg, held, dims, run.get("mode"),
                run.get("fault", ""))
            if j == 0:
                shares.append(float(share))
        w = draw.layer(i, True)
        for j, run in enumerate(runs):
            xs[j], own = ffn_part(
                w, xs[j], moe < 0, dims, run.get("mode"),
                run.get("fault", ""),
                None if moe < 0 else jnp.asarray(forced[j][moe]))
            if own is not None:
                own_choice[j].append(
                    np.asarray(own)[:r * n].reshape(r, n, -1))
    print("reference: attention's output against the stream, by layer: "
          + " ".join(f"{s:.3f}" for s in shares), file=sys.stderr)
    out = []
    for j, run in enumerate(runs):
        logits = _logits(xs[j][:r * n], ends["lnf_g"], ends["head_W"], dims,
                         run.get("mode"))
        out.append({"logits": np.asarray(logits).reshape(r, n, -1),
                    "experts": np.stack(own_choice[j]) if own_choice[j]
                    else np.zeros((0, r, n, dims.top_k), np.int32)})
    return out


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
