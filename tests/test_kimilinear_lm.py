"""Kimi-Linear-48B-A3B-Instruct's layers in the program (Kimi Delta
Attention, the gated delta rule with a recurrent state, beside latent
attention with no positions, one per-layer pattern; the grouped dropless
expert layer of a chip that holds a share, one shared expert) against
the benchmark's plain float32 reference
(`perfbench/reference_kimilinear.py`, which shares no code with the
program), and the rule's chunked form and decode kernel against the
recurrence. CPU, tiny widths, seeded.

Tolerances: the program and the reference compute in float32 on the
same bfloat16-valued weights, the program's matmuls at `highest`
precision too; what is left is the order of float32 sums (the chunked
rule against the token recurrence, a blocked online softmax against a
whole one), 1e-4 of logits whose deviation is about 1, and 1e-4 of the
rule's outputs and states, whose values are of order 1."""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from lua_mapreduce_tpu.models import attention_kinds as kinds
from lua_mapreduce_tpu.models import transformer as tfm
from lua_mapreduce_tpu.ops import kda
from lua_mapreduce_tpu.parallel import moe
from perfbench import reference_dsv32
from perfbench import reference_kimilinear as ref
from perfbench import weights, weights_kimilinear as wts
from perfbench.model_kimilinear import program_config

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
with open(os.path.join(ROOT, "perfbench", "tests", "data",
                       "tiny-kimilinear.json")) as f:
    TINY = json.load(f)
SEED = 2 ** 31 + 13
CFG = program_config(TINY)
# float32 sums in another order (the module's docstring)
TOL = 1e-4


@pytest.fixture(scope="module")
def params():
    """The seed's weights as both hold them (bfloat16 values, float32)."""
    leaves = weights.make_leaves(weights.seed_key(SEED), wts.indexed(TINY),
                                 jnp.float32, via=jnp.bfloat16)
    return {k: v.astype(jnp.float32)
            for k, v in wts.finish(TINY, leaves).items()}


def ids(rows: int, length: int) -> np.ndarray:
    return weights.token_rows(SEED, 0, rows, length, TINY["vocab_size"])


# --------------------------------------------------------------------------
# the rule: chunked, stepped, recurrent
# --------------------------------------------------------------------------

def rule_inputs(b=2, length=45, h=3, dk=16, dv=16, seed=0, strength=None):
    """Normed queries and keys as the layer makes them, decays from
    strong (exp(3) a step) to weak (exp(-6)), a nonzero start state."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    q = kinds._l2_heads(jax.random.normal(ks[0], (b, length, h, dk))) \
        * dk ** -0.5
    k = kinds._l2_heads(jax.random.normal(ks[1], (b, length, h, dk)))
    v = jax.random.normal(ks[2], (b, length, h, dv))
    rate = (jax.random.uniform(ks[3], (b, length, h, dk), minval=-6,
                               maxval=3) if strength is None
            else jnp.full((b, length, h, dk), strength))
    g = -jnp.exp(rate)
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (b, length, h)))
    state = jax.random.normal(ks[5], (b, h, dk, dv))
    return q, k, v, g, beta, state


@pytest.mark.parametrize("chunk", [16, 64])
def test_the_chunked_rule_is_the_recurrence(chunk):
    """45 positions (no whole number of chunks of either size) from a
    nonzero state: every output and the state after the last."""
    args = rule_inputs()
    with jax.default_matmul_precision("highest"):
        want_o, want_s = kda.kda_recurrent(*args)
        got_o, got_s = kda.kda_chunked(*args, chunk=chunk)
    np.testing.assert_allclose(got_o, want_o, atol=TOL)
    np.testing.assert_allclose(got_s, want_s, atol=TOL)


@pytest.mark.parametrize("strength", [np.log(60.0), np.log(1e-4)])
def test_the_chunked_rule_holds_under_any_decay(strength):
    """A decay of exp(-60) a step (a chunk's cumulative log-decay reaches
    -3840: exp(-G) alone would be inf) and one of exp(-1e-4): finite, and
    the recurrence's."""
    args = rule_inputs(length=70, strength=strength)
    with jax.default_matmul_precision("highest"):
        want_o, want_s = kda.kda_recurrent(*args)
        got_o, got_s = kda.kda_chunked(*args)
    assert bool(jnp.all(jnp.isfinite(got_o)) & jnp.all(jnp.isfinite(got_s)))
    np.testing.assert_allclose(got_o, want_o, atol=TOL)
    np.testing.assert_allclose(got_s, want_s, atol=TOL)


@pytest.mark.parametrize("b,h,dk", [(2, 3, 16), (1, 32, 128)])
def test_the_step_kernel_is_the_jnp_step(b, h, dk):
    """`_kda_step_pallas` in interpret mode against the composition, at a
    tiny size and at the model's 32 heads of 128."""
    q, k, v, g, beta, state = rule_inputs(b, 1, h, dk, dk)
    q, k, v, g, beta = (x[:, 0] for x in (q, k, v, g, beta))
    want_s, want_o = kda.kda_step(state, q, k, v, g, beta, backend="xla")
    got_s, got_o = kda.kda_step(state, q, k, v, g, beta,
                                backend="pallas_interpret")
    np.testing.assert_allclose(got_o, want_o, atol=1e-5)
    np.testing.assert_allclose(got_s, want_s, atol=1e-5)


# --------------------------------------------------------------------------
# the model against the reference
# --------------------------------------------------------------------------

def test_the_stack_is_the_published_lists():
    """Layers 1-3 KDA, layer 4 latent (the tiny file's lists, 1-indexed);
    the first layer dense; no positions anywhere."""
    assert CFG.attn_pattern == ("kda", "kda", "kda", "latent")
    got = [type(tfm.attention_kind(CFG, i)) for i in range(CFG.n_layers)]
    assert got == [kinds.KimiDelta] * 3 + [kinds.Latent]
    assert [tfm.moe_layer(CFG, i) for i in range(4)] == [False] + [True] * 3
    assert not CFG.rope and CFG.positions == "none"
    full = program_config(json.load(open(os.path.join(
        ROOT, "perfbench", "configs",
        "kimi-linear-48b-a3b-instruct.serve-ep16.json"))))
    assert [i + 1 for i, k in enumerate(full.attn_pattern)
            if k == "latent"] == [4, 8, 12, 16, 20, 24, 27]


def test_the_full_forward_matches_the_plain_reference(params):
    row = ids(2, 40)
    with jax.default_matmul_precision("highest"):
        got = np.asarray(tfm.transformer_apply(params, jnp.asarray(row),
                                               cfg=CFG))
    for r in range(2):
        want = ref.forward(TINY, SEED, row[r, :1], row[r:r + 1, 1:])
        np.testing.assert_allclose(got[r, 1:], want["logits"][0], atol=TOL)


def turn(params, row, context, n_new, chunk=None):
    """A prefill of ``context`` positions, then a turn of ``n_new``."""
    with jax.default_matmul_precision("highest"):
        caches, last = tfm.prefill(params, jnp.asarray(row[:, :context]),
                                   cfg=CFG, total=context + n_new,
                                   chunk=chunk)
        scanned = tfm.decode_caches(caches, cfg=CFG, p_len=context,
                                    total=context + n_new)
        toks, caches, stats = tfm.decode_from(
            params, scanned, jnp.asarray(row[:, context]), context, n_new,
            cfg=CFG, stats=True)
    return last, np.asarray(toks), caches, stats


@pytest.mark.parametrize("chunk", [None, 8])
def test_prefill_then_decode_is_the_reference(params, chunk):
    """24 positions of context, 16 decoded: the prefill's last logits,
    and every served token at the reference's best with the reference's
    experts, the reference going on from its own state of the context
    token by token."""
    context, n_new = 24, 16
    row = ids(2, context + 1)
    last, toks, _, stats = turn(params, row, context, n_new, chunk)
    for r in range(2):
        state = ref.context_pass(TINY, SEED, row[r, :context])
        tails = np.concatenate([row[r, context:], toks[r, :-1]])[None]
        out = ref.tails_pass(TINY, SEED, state, tails, [{}])[0]
        assert ref.logit_gaps(out["logits"], toks[r:r + 1]).max() < TOL
        experts = np.asarray(stats["experts"])[:, :, r]       # (n, L, k)
        assert ref.routing_miss(experts.transpose(1, 0, 2)[:, None],
                                out["experts"]) == 0.0
        whole = ref.forward(TINY, SEED, row[r, :1],
                            row[r:r + 1, 1:context])["logits"][0, -1]
        np.testing.assert_allclose(np.asarray(last[r]), whole, atol=TOL)


def test_the_same_turn_twice_is_the_same_through_the_snapshot(params):
    """A turn made again from the same position finds the KDA states and
    the convolutions' tails as the first one found them: the same tokens
    and the same states after. Without the snapshot (its position
    forgotten) the second turn goes on from the first one's end and
    serves other tokens."""
    context, n_new = 24, 8
    row = ids(2, context + 1)
    _, first, caches, _ = turn(params, row, context, n_new)
    kept = {k: np.asarray(v) for k, v in caches.items()}
    again, caches2 = tfm.decode_from(
        params, caches, jnp.asarray(row[:, context]), context, n_new,
        cfg=CFG)
    np.testing.assert_array_equal(np.asarray(again), first)
    for name in ("L0_state", "L1_state", "L2_conv"):
        np.testing.assert_array_equal(np.asarray(caches2[name]), kept[name])
    lost = {k: jnp.asarray(v) for k, v in kept.items()}
    lost.update({f"L{i}_at0": jnp.asarray(-1, jnp.int32) for i in range(3)})
    other, _ = tfm.decode_from(params, lost, jnp.asarray(row[:, context]),
                               context, n_new, cfg=CFG)
    assert not np.array_equal(np.asarray(other), first)


@pytest.mark.parametrize("layer,moves", [(0, True), (3, False)])
def test_no_layer_has_positions(params, layer, moves):
    """Neither layer turns anything by position. With the 19 inputs
    before the last in reverse order, the last position's output of the
    KDA layer (a recurrence) changes; the latent layer's (no rotation: a
    query sees the set of keys up to its own) does not."""
    kind = tfm.attention_kind(CFG, layer)
    y = jax.random.normal(jax.random.PRNGKey(3), (1, 20, TINY["hidden_size"]))
    pos = jnp.arange(20)
    out, _ = kind.full(params, f"L{layer}", y, pos, None)
    order = jnp.concatenate([jnp.arange(18, -1, -1), jnp.array([19])])
    rev, _ = kind.full(params, f"L{layer}", y[:, order], pos, None)
    gap = float(jnp.max(jnp.abs(rev[:, -1] - out[:, -1])))
    assert (gap > 1e-3) == moves, gap


def test_four_shares_add_up_to_the_uncut_layer(params):
    """Over all shares of the router's experts (8 of 32 held, as 16 of
    256 are on each of EP16's chips), the held parts plus the shared
    expert counted once are the uncut reference layer. Each share here
    gets its own slice of the uncut reference's stacks."""
    h = jax.random.normal(jax.random.PRNGKey(7), (64, TINY["hidden_size"]))
    e, n = TINY["router_experts"], 4
    per = e // n
    w = {k[len("L1_"):]: v for k, v in params.items()
         if k.startswith("L1_moe_")}
    key = jax.random.PRNGKey(5)
    stacks = {name: jax.random.normal(jax.random.fold_in(key, j),
                                      (e,) + w[name].shape[1:]) * 0.2
              for j, name in enumerate(("moe_wg", "moe_wu", "moe_wd"))}
    dims = dataclasses.replace(ref.Dims.of(TINY), held_first=0, held=e)
    with jax.default_matmul_precision("highest"):
        whole, _ = reference_dsv32.experts(dict(w, **stacks), h, dims)
        parts = 0.0
        for s in range(n):
            mine = dict(w, **{k: v[s * per:(s + 1) * per]
                              for k, v in stacks.items()})
            out, _ = moe.moe_ffn_held(
                mine, h, held=(s * per, per),
                top_k=TINY["num_experts_per_token"], n_groups=1,
                topk_groups=1, scale=TINY["routed_scaling_factor"],
                prefix="moe", shared=s == 0)
            parts = parts + out
    np.testing.assert_allclose(parts, whole, atol=TOL)


# --------------------------------------------------------------------------
# what is refused, by name
# --------------------------------------------------------------------------

@pytest.mark.parametrize("change,match", [
    (dict(linear=None), "'kda' layers take linear's sizes"),
    (dict(attn_pattern=()), "linear sizes without an attn_pattern"),
    (dict(attn_pattern=("kda", "swa", "kda", "latent")),
     "no grouped-query layers"),
    (dict(attn_pattern=("kda", "kda", "kda", "mamba")),
     "attn_pattern names each"),
    (dict(norm="ln"), "latent attention is written for norm='rms'"),
])
def test_check_arch_names_what_is_wrong(change, match):
    with pytest.raises(ValueError, match=match):
        tfm._check_arch(dataclasses.replace(CFG, **change))


def test_the_train_steps_and_the_sharded_forward_refuse_the_kda_kind():
    import optax
    from jax.sharding import Mesh
    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1), ("dp", "sp"))
    for make in (lambda: tfm.make_sharded_apply(CFG, mesh),
                 lambda: tfm.make_train_step(CFG, mesh, optax.adam(1e-3))):
        with pytest.raises(ValueError, match="the KDA kind"):
            make()


def test_a_recurrent_state_has_no_int8_form():
    with pytest.raises(ValueError, match="no int8 form"):
        tfm.decode_caches({}, cfg=CFG, p_len=8, total=8, kv_q8=True)


def test_the_caches_are_the_states_the_snapshot_and_the_latent_rows(params):
    """What a turn carries: a KDA layer's float32 state (B, H, D, D) and
    bfloat16 tail (B, 3, 3 P) with one snapshot of each, the latent
    layer's rows of every position."""
    caches = tfm.decode_caches(
        tfm._layer_caches(CFG, lambda kind, p: kind.empty(p, 2, 32,
                                                          jnp.bfloat16)),
        cfg=CFG, p_len=24, total=32)
    h, dk, taps = wts.kda_dims(TINY)
    for i in range(3):
        for name in (f"L{i}_state", f"L{i}_state0"):
            assert caches[name].shape == (2, h, dk, dk)
            assert caches[name].dtype == jnp.float32
        for name in (f"L{i}_conv", f"L{i}_conv0"):
            assert caches[name].shape == (2, taps - 1, 3 * h * dk)
            assert caches[name].dtype == jnp.bfloat16
    assert caches["L3_ckv"].shape == (2, 32, TINY["kv_lora_rank"]
                                      + TINY["qk_rope_head_dim"])
    assert "L3_at0" not in caches
