"""command-a-plus-05-2026's layers in the program (three window layers
with RoPE to one full layer with no positions, one period of an
attention pattern; the parallel block under one gain-only LayerNorm; the
grouped dropless expert layer of a chip that holds a share, its router
without a bias, four shared experts averaged) against the benchmark's
plain float32 reference (`perfbench/reference_cmdaplus.py`, which
shares no code with the program), and the decode kernel's tile chooser
at every cell's shape. CPU, tiny widths, seeded.

Tolerances: the program and the reference compute in float32 on the
same bfloat16-valued weights, the program's matmuls at `highest`
precision too; what is left is the order of float32 sums (a blocked
online softmax against a whole one, the shared experts as one wide
matmul against four), 1e-4 of logits whose deviation is about 1."""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from lua_mapreduce_tpu.models import attention_kinds as kinds
from lua_mapreduce_tpu.models import transformer as tfm
from lua_mapreduce_tpu.ops import decode as dec
from lua_mapreduce_tpu.ops import mla_decode
from lua_mapreduce_tpu.parallel import moe
from perfbench import reference_cmdaplus as ref
from perfbench import weights, weights_cmdaplus as wts
from perfbench.model_cmdaplus import program_config

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
with open(os.path.join(ROOT, "perfbench", "tests", "data",
                       "tiny-cmdaplus.json")) as f:
    TINY = json.load(f)
SEED = 2 ** 31 + 11
CFG = program_config(TINY)
# float32 sums in another order (the module's docstring)
TOL = 1e-4


@pytest.fixture(scope="module")
def leaves():
    """The seed's draws (bfloat16 values, float32)."""
    return weights.make_leaves(weights.seed_key(SEED), wts.indexed(TINY),
                               jnp.float32, via=jnp.bfloat16)


@pytest.fixture(scope="module")
def drawn(leaves):
    """The weights as the reference holds them."""
    return wts.finish(TINY, leaves)


@pytest.fixture(scope="module")
def params(leaves):
    """The same in the program's form: q and k in its order, the shared
    experts' down projection over their count."""
    return wts.program_form(TINY, leaves)


def ids(rows: int, length: int) -> np.ndarray:
    return weights.token_rows(SEED, 0, rows, length, TINY["vocab_size"])


def test_the_stack_is_one_period_of_the_published_pattern():
    assert CFG.attn_pattern == ("swa", "swa", "swa", "full_nope")
    got = [tfm.attention_kind(CFG, i) for i in range(CFG.n_layers)]
    assert [(k.window, k.nope, k.one_form) for k in got] == [
        (8, False, True)] * 3 + [(0, True, True)]
    assert CFG.parallel_block and CFG.norm == "ln_gain"
    assert not CFG.moe_router_bias


def test_the_full_forward_matches_the_plain_reference(params):
    row = ids(2, 40)
    with jax.default_matmul_precision("highest"):
        got = np.asarray(tfm.transformer_apply(params, jnp.asarray(row),
                                               cfg=CFG))
    for r in range(2):
        want = ref.forward(TINY, SEED, row[r, :1], row[r:r + 1, 1:])
        np.testing.assert_allclose(got[r, 1:], want["logits"][0], atol=TOL)


@pytest.mark.parametrize("chunk", [None, 8])
def test_prefill_then_decode_across_the_windows_wrap_is_the_reference(
        params, chunk):
    """24 positions of context in a window of 8 (the rolling buffer has
    wrapped twice), 16 decoded: the prefill's last logits, and every
    served token at the reference's best, with the experts the reference
    chooses."""
    context, n_new = 24, 16
    row = ids(2, context + 1)
    with jax.default_matmul_precision("highest"):
        caches, last = tfm.prefill(params, jnp.asarray(row[:, :context]),
                                   cfg=CFG, total=context + n_new,
                                   chunk=chunk)
        scanned = tfm.decode_caches(caches, cfg=CFG, p_len=context,
                                    total=context + n_new)
        toks, _, stats = tfm.decode_from(
            params, scanned, jnp.asarray(row[:, context]), context, n_new,
            cfg=CFG, stats=True)
    toks = np.asarray(toks)
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


def layer_input(rows=1, length=20, seed=3):
    return jax.random.normal(jax.random.PRNGKey(seed),
                             (rows, length, TINY["hidden_size"]))


@pytest.mark.parametrize("edge,seen", [(0, False), (1, True)])
def test_a_window_query_sees_its_own_and_the_window_minus_one_before(
        params, edge, seen):
    """The window's edge key: at position t a window layer reads t - 7 ..
    t (8 keys, its own among them) and not t - 8. A change at t - 8 + edge
    reaches t's output where it is seen."""
    kind = tfm.attention_kind(CFG, 0)
    y, pos, t = layer_input(), jnp.arange(20), 15
    moved = y.at[:, t - kind.window + edge].add(1.0)
    out, _ = kind.full(params, "L0", y, pos, None)
    out2, _ = kind.full(params, "L0", moved, pos, None)
    assert bool(jnp.any(jnp.abs(out2[:, t] - out[:, t]) > 1e-3)) == seen


@pytest.mark.parametrize("layer,moved,spread_moves", [
    (0, False, True), (3, False, False)])
def test_positions_turn_the_window_layers_and_not_the_full_one(
        params, layer, moved, spread_moves):
    """NoPE: the full layer's output depends on the order of the
    positions alone. Moved all by 1000 neither layer changes (rope turns
    q and k alike, so a score sees differences of positions); spread
    three times as far apart the window layer's changes and the full
    layer's does not (the window layer taken without its window here, so
    that what it sees stays the same)."""
    kind = tfm.attention_kind(CFG, layer)
    if kind.window:
        kind = dataclasses.replace(kind, window=0)
    y, pos = layer_input(), jnp.arange(20)
    out, _ = kind.full(params, f"L{layer}", y, pos, None)
    for other, moves in ((pos + 1000, moved), (3 * pos, spread_moves)):
        got, _ = kind.full(params, f"L{layer}", y, other, None)
        gap = float(jnp.max(jnp.abs(got - out)))
        assert (gap > 1e-3) == moves, (other, gap)


def test_the_parallel_block_is_not_the_sequential_one(params):
    """x + attn(LN x) + ffn(LN x) against x + attn(LN x) then + ffn(LN of
    that): the same weights (the one gain for both norms) give other
    logits; the parallel form is the reference's."""
    row = jnp.asarray(ids(1, 24))
    seq_cfg = dataclasses.replace(CFG, parallel_block=False)
    seq_params = dict(params, **{f"L{i}_ln2_g": params[f"L{i}_ln1_g"]
                                 for i in range(CFG.n_layers)})
    with jax.default_matmul_precision("highest"):
        par = tfm.transformer_apply(params, row, cfg=CFG)
        seq = tfm.transformer_apply(seq_params, row, cfg=seq_cfg)
    assert float(jnp.max(jnp.abs(par - seq))) > 0.05
    assert "L0_ln2_g" not in tfm.init_transformer(jax.random.PRNGKey(0), CFG)


def reference_dims(held_first: int, held: int) -> ref.Dims:
    return dataclasses.replace(ref.Dims.of(TINY), held_first=held_first,
                               held=held)


def layer_weights(params, i=0) -> dict:
    return {k[len(f"L{i}_"):]: v for k, v in params.items()
            if k.startswith(f"L{i}_moe_")}


def program_experts(params, h, held, shared):
    w = {f"moe_{k[4:]}": v for k, v in layer_weights(params).items()}
    out, _ = moe.moe_ffn_held(
        w, h, held=held, top_k=TINY["num_experts_per_tok"], n_groups=1,
        topk_groups=1, scale=1.0, prefix="moe", shared=shared)
    return out


def test_the_shared_experts_are_averaged(drawn, params):
    """The program's one wide SwiGLU, its down projection a quarter of
    the drawn one (exactly: a power of two), is the mean of the four
    experts the reference computes apart; on the drawn weights it is
    their sum, four times that."""
    h = layer_input(1, 64)[0]       # the reference takes whole blocks
    w = layer_weights(drawn)
    np.testing.assert_array_equal(
        4 * layer_weights(params)["moe_sd"], w["moe_sd"])
    dims = reference_dims(TINY["first_expert_held"], TINY["num_experts"])
    with jax.default_matmul_precision("highest"):
        mean, _ = ref.experts(w, h, dims)
        total, _ = ref.experts(w, h, dims, fault="shared_sum")
        routed, _ = ref.experts(w, h, dims, shared=False)
        held = (TINY["first_expert_held"], TINY["num_experts"])
        got = program_experts(params, h, held, True)
        summed = program_experts(drawn, h, held, True)
    np.testing.assert_allclose(got, mean, atol=TOL)
    np.testing.assert_allclose(summed, total, atol=TOL)
    np.testing.assert_allclose(total - routed, 4 * (mean - routed),
                               atol=TOL)


def test_eight_shares_add_up_to_the_uncut_layer(drawn, params):
    """Over all shares of the router's experts (2 of 16 held, as 16 of
    128 are on each of EP8's chips), the held parts plus the shared
    experts counted once are the uncut reference layer. The program's
    share holds the draw of one share's experts; each share here gets
    its own slice of the uncut reference's stacks."""
    h = layer_input(1, 64)[0]       # the reference takes whole blocks
    e, n = TINY["router_experts"], 8
    per = e // n
    w = layer_weights(params)
    key = jax.random.PRNGKey(5)
    stacks = {name: jax.random.normal(jax.random.fold_in(key, j),
                                      (e,) + w[name].shape[1:]) * 0.2
              for j, name in enumerate(("moe_wg", "moe_wu", "moe_wd"))}
    uncut = dict(layer_weights(drawn), **stacks)
    with jax.default_matmul_precision("highest"):
        whole, _ = ref.experts(uncut, h, reference_dims(0, e))
        parts = 0.0
        for s in range(n):
            mine = dict(w, **{k: v[s * per:(s + 1) * per]
                              for k, v in stacks.items()})
            program = {f"L0_{k}": v for k, v in mine.items()}
            parts = parts + program_experts(program, h, (s * per, per),
                                            shared=s == 0)
    np.testing.assert_allclose(parts, whole, atol=TOL)


# the decode kernel's tile choice at every cell's shape: (rows = sessions
# x kv rows, cache slots, row width, query rows a kv row) -> (rows a grid
# step, positions a grid step). The first four are the choices the cells
# had before this PR and may not move
TILES = [
    ((32 * 8, 512, 128, 4), (8, 512)),          # Mistral chat
    ((8 * 8, 4096, 128, 4), (8, 512)),          # Mistral long context
    ((32 * 10, 16416, 128, 4), (8, 512)),       # Phi: the whole cache
    ((32 * 10, 512, 128, 4), (8, 512)),         # Phi: a rolling window
    ((16 * 8, 32832, 128, 16), (8, 512)),       # command-a-plus: full layer
    ((16 * 8, 4096, 128, 16), (8, 512)),        # command-a-plus: a window
]


@pytest.mark.parametrize("shape,tiles", TILES)
def test_the_tile_chooser_keeps_every_cells_choice(shape, tiles):
    rows, s_len, d, g = shape
    assert dec._tiles(rows, s_len, d, 2, g, False) == tiles


def test_the_latent_tile_chooser_keeps_sarvams_choice():
    assert mla_decode._tiles(16, 32832, 576, 512, 64, 2) == (2, 1024)


@pytest.mark.parametrize("t", [5, 8, 13])
def test_the_decode_kernel_at_sixteen_query_rows_is_its_composition(t):
    """g = 16 query rows a kv row (128 heads over 8): the kernel in
    interpret mode against the XLA composition, over a whole cache and a
    rolling one."""
    key = jax.random.PRNGKey(t)
    q = jax.random.normal(key, (2, 2, 16, 128), jnp.float32)
    k, v = (jax.random.normal(jax.random.fold_in(key, j), (2, 2, 8, 128),
                              jnp.float32) for j in (1, 2))
    for roll in (False, True):
        want = dec.decode_attention(q, k, v, t, roll=roll, backend="xla")
        got = dec.decode_attention(q, k, v, t, roll=roll,
                                   backend="pallas_interpret")
        np.testing.assert_allclose(got, want, atol=1e-5)


# --------------------------------------------------------------------------
# what is refused, by name
# --------------------------------------------------------------------------

@pytest.mark.parametrize("change,match", [
    (dict(attn_pattern=("swa", "global")), "attn_pattern names each"),
    (dict(window=0), "'swa' layers need window"),
    (dict(norm="layer"), "unknown norm"),
    (dict(moe_router="switch", moe_capacity=4, ffn="gelu"),
     "grouped expert layer's"),
    (dict(moe_experts=0, moe_held=None), "grouped expert layer's"),
    (dict(latent=tfm.LatentAttention(0, 64, 32, 32, 32)),
     "a stack with latent attention has no grouped-query layers"),
])
def test_check_arch_names_what_is_wrong(change, match):
    with pytest.raises(ValueError, match=match):
        tfm._check_arch(dataclasses.replace(CFG, **change))


@pytest.mark.parametrize("change,named", [
    ({}, "an attention pattern"),
    (dict(attn_pattern=()), "a parallel block"),
    (dict(attn_pattern=(), parallel_block=False),
     "the grouped expert layer"),
])
def test_the_train_steps_and_the_sharded_forward_refuse_it_by_name(
        change, named):
    import optax
    from jax.sharding import Mesh
    cfg = dataclasses.replace(CFG, **change)
    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1), ("dp", "sp"))
    for make in (lambda: tfm.make_sharded_apply(cfg, mesh),
                 lambda: tfm.make_train_step(cfg, mesh, optax.adam(1e-3))):
        with pytest.raises(ValueError, match=named):
            make()


def test_a_patterns_cache_has_no_int8_form():
    with pytest.raises(ValueError, match="no int8 form"):
        tfm.decode_caches({}, cfg=CFG, p_len=8, total=8, kv_q8=True)


def test_the_half_order_pairs_the_interleaved_columns():
    """Column 2j of a checkpoint's head lands at j, 2j + 1 at j + hd/2;
    v keeps its order."""
    order = wts.half_order(TINY)
    hd, h, hkv = TINY["head_dim"], TINY["num_attention_heads"], \
        TINY["num_key_value_heads"]
    head = order[:hd]
    assert list(head[:hd // 2]) == list(range(0, hd, 2))
    assert list(head[hd // 2:]) == list(range(1, hd, 2))
    assert list(order[(h + hkv) * hd:]) == list(range((h + hkv) * hd,
                                                      (h + 2 * hkv) * hd))
    assert sorted(order) == list(range((h + 2 * hkv) * hd))
    # the rotate-half rope on the permuted head is GPT-J's on the original
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 5, 1, hd))
    pos = jnp.arange(5)
    gptj = ref.rope_gptj(x[0], pos, ref.Dims.of(TINY))
    half = kinds._rope(x[..., head], pos, float(TINY["rope_theta"]))
    np.testing.assert_allclose(half[0], gptj[..., head], atol=1e-5)
