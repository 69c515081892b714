"""Expert parallelism: switch-style mixture-of-experts with all_to_all
token routing.

The third shuffle topology of the framework (after the keyed psum and the
partitionfn-bucketed all_to_all of parallel/tpu_engine.py): the ROUTER is
a learned partitionfn — each token picks an expert, tokens are bucketed
per expert under a fixed capacity (static shapes: XLA cannot trace
data-dependent bucket sizes), and one ``all_to_all`` over the ``ep`` mesh
axis carries every device's buckets to the devices owning those experts,
exactly how the reference's map outputs travel to their partition's
reducer (SURVEY.md §2.6). A second all_to_all brings expert outputs home,
where the gate's combine weights merge them.

Capacity semantics are the standard switch-transformer ones: per device
tile, expert e keeps the first ``capacity`` tokens routed to it (position
by cumulative count in token order); overflow tokens are DROPPED — their
combine weight is zero, so they pass through the residual connection
unchanged. The load-balancing auxiliary loss (fraction-routed ×
mean-gate-probability, scaled by E) keeps the router from collapsing onto
few experts.

Two forms, golden-diffed in tests: :func:`moe_ffn_reference` (one device,
all experts local) and :func:`moe_ffn_shard` (inside shard_map, experts
sharded over ``ep``) — identical routing, identical outputs.
"""

from __future__ import annotations

import functools
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from lua_mapreduce_tpu.ops.moe_held import moe_held, swiglu as _swiglu
from lua_mapreduce_tpu.utils.profiling import scope

Params = Dict[str, jnp.ndarray]


def init_moe(key, d_model: int, d_ff: int, n_experts: int,
             dtype=jnp.float32, prefix: str = "moe") -> Params:
    """Router + per-expert FFN weights (E stacked), flat name→array."""
    k1, k2, k3 = jax.random.split(key, 3)
    s1 = 1.0 / jnp.sqrt(jnp.asarray(d_model, jnp.float32))
    s2 = 1.0 / jnp.sqrt(jnp.asarray(d_ff, jnp.float32))
    return {
        f"{prefix}_router_W": s1 * jax.random.normal(
            k1, (d_model, n_experts), dtype),
        f"{prefix}_w1": s1 * jax.random.normal(
            k2, (n_experts, d_model, d_ff), dtype),
        f"{prefix}_b1": jnp.zeros((n_experts, d_ff), dtype),
        f"{prefix}_w2": s2 * jax.random.normal(
            k3, (n_experts, d_ff, d_model), dtype),
        f"{prefix}_b2": jnp.zeros((n_experts, d_model), dtype),
    }


def _route(x, router_w, n_experts: int, capacity: int, top_k: int = 1):
    """Top-k routing with capacity: returns (dispatch (T,E,C) one-hot,
    combine (T,E,C) gate-weighted, aux_loss scalar). x is the flat
    (T, d) token tile of ONE device.

    ``top_k=1`` is the switch transformer; ``top_k>1`` is the
    Mixtral-style generalization: each token is dispatched to its k
    highest-gated experts, combine weights RENORMALIZED over the
    selected k (pre-drop, so a capacity-dropped expert's share is lost
    through the residual rather than silently inflating the survivor).
    Capacity is per (expert, tile) across ALL k rounds — round j's
    tokens take slots after rounds < j's, so total bucket occupancy
    never exceeds C and the dispatch einsum shapes stay static."""
    gates = jax.nn.softmax(x.astype(jnp.float32) @ router_w.astype(
        jnp.float32), axis=-1)                          # (T, E)
    t = x.shape[0]
    counts = jnp.zeros((n_experts,), jnp.float32)       # slots used
    dispatch = jnp.zeros((t, n_experts, capacity), jnp.float32)
    combine = jnp.zeros((t, n_experts, capacity), jnp.float32)
    sel_sum = jnp.zeros((t,), jnp.float32)              # renorm denom
    frac = jnp.zeros((n_experts,), jnp.float32)
    # all k choices in ONE top_k call — iterated argmax-and-mask over
    # softmax probs re-picks expert 0 when non-selected gates underflow
    # to exactly 0.0 (router margin > ~103 nats), silently consuming a
    # foreign expert's capacity slot
    _, topk_idx = jax.lax.top_k(gates, top_k)           # (T, k)
    for j in range(top_k):
        expert = topk_idx[:, j]                         # (T,)
        onehot = jax.nn.one_hot(expert, n_experts, dtype=jnp.float32)
        # position of each token within its expert's bucket: this
        # round's token order, offset by earlier rounds' occupancy
        pos = ((jnp.cumsum(onehot, axis=0) - 1.0)
               + counts[None, :]) * onehot              # (T, E)
        kept = onehot * (pos < capacity)                # drop overflow
        counts = counts + jnp.sum(kept, axis=0)
        pos_c = jax.nn.one_hot(jnp.sum(pos, axis=-1).astype(jnp.int32),
                               capacity, dtype=jnp.float32)  # (T, C)
        disp_j = kept[:, :, None] * pos_c[:, None, :]   # (T, E, C)
        gate_j = jnp.sum(gates * kept, axis=-1)         # (T,) kept gate
        dispatch = dispatch + disp_j
        combine = combine + disp_j * gate_j[:, None, None]
        sel_sum = sel_sum + jnp.sum(gates * onehot, axis=-1)
        frac = frac + jnp.mean(onehot, axis=0)
    if top_k > 1:
        combine = combine / jnp.maximum(sel_sum, 1e-9)[:, None, None]
    # switch aux loss generalized: E * Σ_e (fraction routed_e / k) ×
    # mean_prob_e (reduces to the switch loss at k = 1)
    prob = jnp.mean(gates, axis=0)
    aux = n_experts * jnp.sum((frac / top_k) * prob)
    return dispatch, combine, aux


def _route_sorted(x, router_w, n_experts: int, capacity: int,
                  top_k: int = 1):
    """Sort-based routing with IDENTICAL semantics to :func:`_route`
    (same top-k selection, same first-C-in-token-order capacity fill,
    rounds filling in round-major order, pre-drop renormalization, same
    aux loss) but without ever materializing the (T, E, C) dispatch/
    combine tensors or their O(T·E·C·d) contraction FLOPs.

    The one-hot einsum formulation costs 2·T·E·C·d FLOPs per dispatch
    AND combine and streams two T·E·C f32 tensors through HBM per
    layer — at the bench shape (T=16384, E=8, C=4096, d=1024) that is
    2×1.1e12 matmul FLOPs and 2×2.0 GiB of one-hot traffic to move
    64 MB of activations. Routing is a PERMUTATION, not a contraction:
    one stable argsort of the (T·k,) expert assignments orders tokens
    by (expert, round, token), ranks within each expert group come
    from an exclusive-cumsum of the per-expert counts, and dispatch/
    combine become row gathers (exact — no arithmetic on the
    activations at all, vs the einsum's summation of one-hot
    products). Returns

    - ``token_of_slot`` (E, C) int32 — which token fills each expert
      slot (arbitrary where invalid),
    - ``round_of_slot`` (E, C) int32 — which top-k round owns each
      slot (arbitrary where invalid),
    - ``slot_valid``   (E, C) bool  — slot actually filled,
    - ``slot_of_tok``  (k, T) int32 — each routing round's slot per
      token, E·C (one past the end) when dropped,
    - ``gate_of_tok``  (k, T) f32   — combine weight per round
      (renormalized, zero when dropped),
    - ``aux`` scalar — the same load-balancing loss as :func:`_route`.

    Kept slots ↔ kept (round, token) pairs are a BIJECTION, so both
    directions of the dispatch/combine data movement — including their
    TRANSPOSES — are gathers; the custom VJPs below use that to keep
    the backward pass scatter-free (XLA's transpose of a gather is a
    serialized scatter-add on TPU, which would hand back a chunk of
    the einsum formulation's cost in the training step).
    """
    gates = jax.nn.softmax(x.astype(jnp.float32) @ router_w.astype(
        jnp.float32), axis=-1)                          # (T, E)
    t = x.shape[0]
    _, topk_idx = jax.lax.top_k(gates, top_k)           # (T, k)
    # flat order i = j·T + t ⇒ ascending i is (round, token)-lex — the
    # exact order _route fills capacity in (round j after rounds < j,
    # token order within a round)
    expert_flat = topk_idx.T.reshape(-1)                # (k·T,)
    order = jnp.argsort(expert_flat, stable=True)       # (k·T,)
    counts = jnp.bincount(expert_flat, length=n_experts)  # (E,)
    starts = jnp.cumsum(counts) - counts                # exclusive
    # rank of each sorted element within its expert's group
    rank_sorted = jnp.arange(t * top_k) - starts[expert_flat[order]]
    kept_sorted = rank_sorted < capacity
    slot_sorted = jnp.where(
        kept_sorted, expert_flat[order] * capacity + rank_sorted,
        n_experts * capacity)                           # E·C = dropped
    # scatter the slot ids back to (round, token) order — int32 only,
    # k·T elements; the activation rows themselves are never scattered
    slot_of_tok = jnp.zeros((t * top_k,), jnp.int32).at[order].set(
        slot_sorted.astype(jnp.int32)).reshape(top_k, t)
    # slot → token: group e occupies sorted positions
    # [starts[e], starts[e] + counts[e]); its first C fill the slots
    pos = jnp.clip(starts[:, None] + jnp.arange(capacity)[None, :],
                   0, t * top_k - 1)                    # (E, C)
    slot_valid = jnp.arange(capacity)[None, :] < counts[:, None]
    tok_sorted = order % t                              # token of sorted elt
    token_of_slot = tok_sorted[pos].astype(jnp.int32)   # (E, C)
    round_of_slot = (order // t)[pos].astype(jnp.int32)  # (E, C)

    sel_gates = jnp.take_along_axis(gates, topk_idx, axis=1)  # (T, k)
    kept_tok = (slot_of_tok < n_experts * capacity)     # (k, T)
    gate_of_tok = jnp.where(kept_tok, sel_gates.T, 0.0)
    if top_k > 1:
        # pre-drop renormalization over the selected k (matches _route:
        # a dropped expert's share is lost through the residual)
        gate_of_tok = gate_of_tok / jnp.maximum(
            jnp.sum(sel_gates, axis=1), 1e-9)[None, :]
    prob = jnp.mean(gates, axis=0)
    frac = counts.astype(jnp.float32) / t
    aux = n_experts * jnp.sum((frac / top_k) * prob)
    return (token_of_slot, round_of_slot, slot_valid, slot_of_tok,
            gate_of_tok, aux)


def _flat_with_sentinel(a):
    """(E, C, d) → (E·C + 1, d) with a ZERO row at index E·C — the
    sentinel every ``slot_of_tok`` dropped-token entry points at. The
    zero row is load-bearing for gradient correctness in both VJPs:
    dropped (round, token) pairs must read exactly 0."""
    e, c, d = a.shape
    return jnp.concatenate(
        [a.reshape(e * c, d), jnp.zeros((1, d), a.dtype)], axis=0)


@jax.custom_vjp
def _dispatch_gather(xf, token_of_slot, slot_valid, slot_of_tok):
    """(T, d) tokens → (E, C, d) expert buckets by row gather; the VJP
    is the INVERSE gather (via ``slot_of_tok``), not a scatter-add."""
    return jnp.where(slot_valid[..., None], xf[token_of_slot], 0.0)


def _dispatch_gather_fwd(xf, token_of_slot, slot_valid, slot_of_tok):
    return (_dispatch_gather(xf, token_of_slot, slot_valid, slot_of_tok),
            slot_of_tok)


def _dispatch_gather_bwd(slot_of_tok, dxe):
    # sentinel row E·C reads zero: dropped (round, token) pairs get no
    # cotangent, exactly like the scatter-add transpose would produce
    dx = jnp.sum(_flat_with_sentinel(dxe)[slot_of_tok], axis=0)  # (T, d)
    return dx, None, None, None


_dispatch_gather.defvjp(_dispatch_gather_fwd, _dispatch_gather_bwd)


@jax.custom_vjp
def _combine_gather(ye, gate_of_tok, token_of_slot, round_of_slot,
                    slot_valid, slot_of_tok):
    """(E, C, d) expert outputs → (T, d) tokens, gate-weighted; both
    VJP operand paths (``ye`` and the differentiable ``gate_of_tok``,
    through which the router trains) are gathers via the slot↔token
    bijection."""
    return jnp.sum(gate_of_tok[..., None]
                   * _flat_with_sentinel(ye)[slot_of_tok], axis=0)


def _combine_gather_fwd(ye, gate_of_tok, token_of_slot, round_of_slot,
                        slot_valid, slot_of_tok):
    out = _combine_gather(ye, gate_of_tok, token_of_slot, round_of_slot,
                          slot_valid, slot_of_tok)
    return out, (ye, gate_of_tok, token_of_slot, round_of_slot,
                 slot_valid, slot_of_tok)


def _combine_gather_bwd(res, dout):
    ye, gate_of_tok, token_of_slot, round_of_slot, slot_valid, \
        slot_of_tok = res
    # d ye[s] = gate(s) · dout[token(s)] — pure gathers over (E, C)
    gate_of_slot = gate_of_tok[round_of_slot, token_of_slot]  # (E, C)
    dye = jnp.where(slot_valid[..., None],
                    gate_of_slot[..., None] * dout[token_of_slot], 0.0)
    # d gate[j, t] = dout[t] · ye_flat[slot_of_tok[j, t]]
    dgate = jnp.sum(_flat_with_sentinel(ye)[slot_of_tok]
                    * dout[None, :, :], axis=-1)
    return dye, dgate, None, None, None, None


_combine_gather.defvjp(_combine_gather_fwd, _combine_gather_bwd)


def _expert_ffn(w1, b1, w2, b2, x):
    """Batched expert FFN: x (E, C, d) → (E, C, d), one einsum pair on
    the MXU per layer."""
    h = jax.nn.gelu(jnp.einsum("ecd,edf->ecf", x, w1) + b1[:, None, :])
    return jnp.einsum("ecf,efd->ecd", h, w2) + b2[:, None, :]


def _moe_ffn(params: Params, x, capacity: int, prefix: str,
             ep_axis, top_k: int = 1, impl: str = "sorted"
             ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """One body for both forms — ``ep_axis=None`` keeps everything local
    (the oracle); a mesh axis inserts the two all_to_all shuffles. The
    two forms are contractually golden-diffed, so they MUST share this
    routing/compute path.

    ``impl`` picks the dispatch/combine machinery around the (identical)
    expert FFN and all_to_all shuffles: ``"sorted"`` (default) routes by
    argsort + row gathers; ``"einsum"`` is the one-hot contraction
    oracle. DESIGN §14: at the bench shape the einsum form's dispatch/
    combine contractions alone cost 2×1.1e12 FLOPs per layer — 8× the
    expert FFN's useful work — which is measurably the entire
    472 ms - 164 ms step gap vs dense; the sorted form removes those
    FLOPs and the 2×2 GiB one-hot HBM streams entirely."""
    w = {k[len(prefix) + 1:]: v for k, v in params.items()
         if k.startswith(prefix + "_")}
    n_experts = w["router_W"].shape[1]          # GLOBAL expert count
    xf = x.astype(jnp.float32)
    if impl == "sorted":
        (tok_of_slot, round_of_slot, slot_valid, slot_of_tok,
         gate_of_tok, aux) = _route_sorted(x, w["router_W"], n_experts,
                                           capacity, top_k=top_k)
        xe = _dispatch_gather(xf, tok_of_slot, slot_valid, slot_of_tok)
    elif impl == "einsum":
        dispatch, combine, aux = _route(x, w["router_W"], n_experts,
                                        capacity, top_k=top_k)
        xe = jnp.einsum("tec,td->ecd", dispatch, xf)
    else:
        raise ValueError(f"unknown moe impl {impl!r}")
    if ep_axis is not None:
        # (E, C, d) → (E/ep, ep·C, d): device p receives every peer's
        # bucket for its local experts — the shuffle
        xe = lax.all_to_all(xe, ep_axis, split_axis=0, concat_axis=1,
                            tiled=True)
    ye = _expert_ffn(w["w1"].astype(jnp.float32),
                     w["b1"].astype(jnp.float32),
                     w["w2"].astype(jnp.float32),
                     w["b2"].astype(jnp.float32), xe)
    if ep_axis is not None:
        # inverse shuffle: outputs return to their source devices
        ye = lax.all_to_all(ye, ep_axis, split_axis=1, concat_axis=0,
                            tiled=True)
    if impl == "sorted":
        # combine = per-round row gather from the flat (E·C)+1 slot
        # table (zero sentinel row = dropped), gate-weighted; under
        # ep the all_to_all above restored the LOCAL tile's (E, C, d)
        # bucket geometry, so the slot bijection still holds
        out = _combine_gather(ye, gate_of_tok, tok_of_slot,
                              round_of_slot, slot_valid, slot_of_tok)
    else:
        out = jnp.einsum("tec,ecd->td", combine, ye)
    if ep_axis is not None:
        # aux is per-tile; average across the ep group so every device
        # carries the same scalar (replicated, ready for the loss)
        aux = lax.pmean(aux, ep_axis)
    return out.astype(x.dtype), aux


def moe_ffn_reference(params: Params, x, *, capacity: int,
                      prefix: str = "moe", top_k: int = 1,
                      impl: str = "sorted"
                      ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Single-device oracle: (T, d) tokens → ((T, d) out, aux loss)."""
    return _moe_ffn(params, x, capacity, prefix, None, top_k=top_k,
                    impl=impl)


def moe_ffn_shard(params: Params, x, *, capacity: int, ep_axis: str,
                  prefix: str = "moe", top_k: int = 1,
                  impl: str = "sorted"
                  ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Expert-parallel form (inside shard_map): router weights are
    replicated, expert weights are LOCAL slices (E/ep experts per
    device); two all_to_alls move token buckets out and back.

    Equivalent to the reference with the same capacity per (device,
    expert) bucket: each device's tile routes independently, so a
    reference run over the concatenated tiles with per-tile routing
    produces identical outputs (the golden-diff in tests).
    """
    return _moe_ffn(params, x, capacity, prefix, ep_axis, top_k=top_k,
                    impl=impl)


# --------------------------------------------------------------------------
# the dropless layer: sigmoid scores, group-limited top-k, SwiGLU experts,
# a shared expert, and a chip that is told which experts it holds
# --------------------------------------------------------------------------

def init_moe_held(key, d_model: int, d_ff: int, n_experts: int,
                  held: Tuple[int, int], n_shared: int = 0,
                  dtype=jnp.float32, prefix: str = "moe",
                  router_bias: bool = True) -> Params:
    """The router over all ``n_experts`` (weights and, with
    ``router_bias``, the selection bias, zero at initialisation:
    training's load balancing sets it) and the SwiGLU weights of the
    ``held = (first, count)`` experts, stacked; with ``n_shared`` the
    shared experts side by side, one SwiGLU of that many times the
    width (its down projection sums theirs)."""
    _check_held(held, n_experts)
    ks = jax.random.split(key, 8)
    count = held[1]
    s1, s2 = d_model ** -0.5, d_ff ** -0.5
    out = {
        f"{prefix}_router_W": s1 * jax.random.normal(
            ks[0], (d_model, n_experts), dtype),
        f"{prefix}_wg": s1 * jax.random.normal(
            ks[2], (count, d_model, d_ff), dtype),
        f"{prefix}_wu": s1 * jax.random.normal(
            ks[3], (count, d_model, d_ff), dtype),
        f"{prefix}_wd": s2 * jax.random.normal(
            ks[4], (count, d_ff, d_model), dtype),
    }
    if router_bias:
        out[f"{prefix}_router_b"] = jnp.zeros((n_experts,), jnp.float32)
    if n_shared:
        w = n_shared * d_ff
        out[f"{prefix}_sg"] = s1 * jax.random.normal(ks[5], (d_model, w),
                                                     dtype)
        out[f"{prefix}_su"] = s1 * jax.random.normal(ks[6], (d_model, w),
                                                     dtype)
        out[f"{prefix}_sd"] = w ** -0.5 * jax.random.normal(
            ks[7], (w, d_model), dtype)
    return out


def _check_held(held: Tuple[int, int], n_experts: int) -> None:
    first, count = held
    if first < 0 or count < 1 or first + count > n_experts:
        raise ValueError(f"held={held} is no range of the {n_experts} "
                         f"experts")


def route_grouped(x, router_w, bias, *, top_k: int, n_groups: int,
                  topk_groups: int, scale: float):
    """Sigmoid scores with a group-limited choice (DeepSeek-V3's
    `noaux_tc`): the choice is made on ``score + bias`` (on the score
    where ``bias`` is None): a group's
    score is the sum of its two highest, the ``topk_groups`` best groups
    stay, and the ``top_k`` highest of what they hold are selected. The
    weights are the scores themselves (no bias), normalised over the
    selected and scaled. Returns (expert (T, k) int32, weight (T, k)
    float32). Nothing is dropped: every token keeps all its k."""
    sc = jax.nn.sigmoid(x.astype(jnp.float32)
                        @ router_w.astype(jnp.float32))        # (T, E)
    t, e = sc.shape
    choice = sc if bias is None else sc + bias.astype(jnp.float32)
    if n_groups > 1:
        grouped = choice.reshape(t, n_groups, e // n_groups)
        group_score = jnp.sum(lax.top_k(grouped, 2)[0], axis=-1)
        _, keep = lax.top_k(group_score, topk_groups)          # (T, g)
        kept = jnp.zeros((t, n_groups), bool).at[
            jnp.arange(t)[:, None], keep].set(True)
        choice = jnp.where(jnp.repeat(kept, e // n_groups, axis=1),
                           choice, -jnp.inf)
    _, expert = lax.top_k(choice, top_k)
    picked = jnp.take_along_axis(sc, expert, axis=-1)
    weight = scale * picked / jnp.sum(picked, axis=-1, keepdims=True)
    return expert.astype(jnp.int32), weight


# tokens one expert takes at a time. A tile no larger than this goes
# through a touched expert whole (`ops/moe_held.moe_held`: a decode step's
# B tokens as one grouped call on the chip): gathering the few rows that
# chose it would cost the step more small operations than the rows it
# saves. A larger tile (a prefill chunk) is gathered, this many of an
# expert's own tokens at a time, so that an expert works on the 1/32 of
# the tile that chose it and not on all of it.
_EXPERT_CHUNK = 512


def moe_ffn_held(params: Params, x, *, held: Tuple[int, int], top_k: int,
                 n_groups: int, topk_groups: int, scale: float,
                 prefix: str = "moe", shared: bool = True):
    """This chip's part of the expert layer for the flat (T, d) tile
    ``x``: the router runs over all experts, and of the result
    ``sum_i g_i E_i(x)`` the terms of the held experts are computed,
    plus the shared experts' sum (once on every chip; ``shared=False``
    leaves them to another share). No capacity and no dropped token; what the
    absent experts would add is left out, and no exchange runs. A router
    without ``router_b`` chooses on its scores alone.

    An expert that no token of the tile chose is never computed, so its
    weights are not read: at decode, a step streams the experts it
    touches (``ops/moe_held.moe_held``: on the chip one call walks
    them). Tokens of a large tile go through their expert
    ``_EXPERT_CHUNK`` at a time, as many chunks as the routing needs.

    Returns (out (T, d), stats): ``held_assignments``, the routed
    (token, expert) pairs that fell on held experts,
    ``experts_touched``, the held experts with at least one token, and
    ``experts`` (T, top_k), the experts each token was routed to."""
    first, count = held
    t, d = x.shape
    with scope("lm.moe.route"):
        expert, weight = route_grouped(
            x, params[f"{prefix}_router_W"], params.get(f"{prefix}_router_b"),
            top_k=top_k, n_groups=n_groups, topk_groups=topk_groups,
            scale=scale)
        local = expert - first                              # (T, k)
        # (T, k, count): a one-hot of nothing where the expert is absent
        onehot = jax.nn.one_hot(local, count, dtype=jnp.float32)
        # (T, count): the weight with which each held expert enters
        combine = jnp.sum(onehot * weight[..., None], axis=1)
        routed = jnp.sum(onehot, axis=1) > 0
        load = jnp.sum(routed, axis=0)                      # (count,)
    wg, wu, wd = (params[f"{prefix}_{n}"] for n in ("wg", "wu", "wd"))

    def chunked(e, acc):
        order = jnp.nonzero(routed[:, e], size=t, fill_value=0)[0]
        order = jnp.pad(order, (0, _EXPERT_CHUNK))

        def body(state):
            i, acc = state
            idx = lax.dynamic_slice(order, (i * _EXPERT_CHUNK,),
                                    (_EXPERT_CHUNK,))
            live = (i * _EXPERT_CHUNK + jnp.arange(_EXPERT_CHUNK)) < load[e]
            y = _swiglu(x[idx], wg[e], wu[e], wd[e])
            g = jnp.where(live, combine[idx, e], 0.0)
            return i + 1, acc.at[idx].add(
                y.astype(jnp.float32) * g[:, None])

        return lax.while_loop(lambda s: s[0] * _EXPERT_CHUNK < load[e], body,
                              (jnp.int32(0), acc))[1]

    with scope("lm.moe.experts"):
        if t <= _EXPERT_CHUNK:
            acc = moe_held(x, combine, load, wg, wu, wd)
        else:
            acc = jnp.zeros((t, d), jnp.float32)
            for e in range(count):
                acc = lax.cond(load[e] > 0, functools.partial(chunked, e),
                               lambda acc: acc, acc)
    if shared and f"{prefix}_sg" in params:
        with scope("lm.moe.shared"):
            acc = acc + _swiglu(x, params[f"{prefix}_sg"],
                                params[f"{prefix}_su"],
                                params[f"{prefix}_sd"]).astype(jnp.float32)
    stats = {"held_assignments": jnp.sum(load).astype(jnp.int32),
             "experts_touched": jnp.sum(load > 0).astype(jnp.int32),
             "experts": expert}
    return acc.astype(x.dtype), stats
