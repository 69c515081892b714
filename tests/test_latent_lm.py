"""DeepSeek-V3.2-Exp's layers in the program (latent attention in the
absorbed form, the indexer's top-k, the grouped dropless expert layer
of a chip that holds a share) against the benchmark's plain float32
reference (`perfbench/reference_dsv32.py`, which shares no code with
the program), and the session entry `decode_from` against
`greedy_decode`. CPU, tiny widths, float32, seeded."""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from lua_mapreduce_tpu.models import attention_kinds as kinds
from lua_mapreduce_tpu.models import transformer as tfm
from lua_mapreduce_tpu.ops import sparse_mla
from lua_mapreduce_tpu.parallel import moe
from perfbench import reference_dsv32 as ref
from perfbench import weights, weights_dsv32
from perfbench.model_dsv32 import program_config as dsv32_program_config

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
with open(os.path.join(ROOT, "perfbench", "tests", "data",
                       "tiny-dsv32.json")) as f:
    TINY = json.load(f)
SEED = 2 ** 31 + 5


def published(**changes) -> dict:
    return dict(TINY, **changes)


def params_of(cfg: dict) -> dict:
    """The seed's weights as the reference holds them (bfloat16 values in
    float32), for the program."""
    return weights_dsv32.finish(cfg, weights.make_leaves(
        weights.seed_key(SEED), weights_dsv32.indexed(cfg), jnp.float32,
        via=jnp.bfloat16))


def ids(rows: int, length: int, vocab: int) -> np.ndarray:
    return weights.token_rows(SEED, 0, rows, length, vocab)


def reference_logits(cfg: dict, row: np.ndarray, **kw):
    """The reference's logits and selections for every position of one
    row: the row as a context of one token and one tail."""
    out = ref.forward(cfg, SEED, row[:1], row[None, 1:], **kw)
    return out["logits"], out["selected"]


@pytest.mark.parametrize("changes", [
    {"first_k_dense_replace": 3, "index_topk": 64},   # latent attention alone
    {"first_k_dense_replace": 3},                     # and the indexer's choice
    {},                                               # and the expert layers
], ids=["mla", "mla+indexer", "whole"])
def test_the_full_forward_matches_the_plain_reference(changes):
    """Absorbed latent attention over gathered rows = the reference's
    un-absorbed attention under a mask, on logits."""
    cfg = published(**changes)
    row = ids(1, 40, cfg["vocab_size"])[0]
    logits = tfm.transformer_apply(params_of(cfg), row[None],
                                   cfg=dsv32_program_config(cfg))[0]
    want, _ = reference_logits(cfg, row)
    # the reference's tail starts at position 1
    np.testing.assert_allclose(np.asarray(logits)[1:], want[0], atol=2e-4)


@pytest.mark.parametrize("chunk", [None, 8])
def test_prefill_then_decode_from_matches_the_full_forward(chunk):
    cfg = published()
    pcfg = dsv32_program_config(cfg)
    params = params_of(cfg)
    context = ids(2, 24, cfg["vocab_size"])
    caches, last = tfm.prefill(params, jnp.asarray(context), cfg=pcfg,
                               total=32, chunk=chunk)
    caches = tfm.decode_caches(caches, cfg=pcfg, p_len=24, total=32)
    first = jnp.argmax(last, -1).astype(jnp.int32)
    tokens, caches, stats = tfm.decode_from(params, caches, first, 24, 8,
                                            cfg=pcfg, stats=True)
    tokens = np.asarray(tokens)
    for b in range(2):
        row = np.concatenate([context[b], np.asarray(first)[b:b + 1],
                              tokens[b]])
        want, selected = reference_logits(cfg, row[:-1])
        # the first token comes from prefill's logits, the rest from the scan
        served = row[24:]
        gaps = ref.logit_gaps(want[:, 22:], served[None])
        assert gaps.max() < 1e-4, gaps
        # what the scan's attention read is what the reference selects
        mine = np.asarray(stats["selected"])[:, :, b]         # (8, L, K)
        miss = ref.selection_miss(
            mine.transpose(1, 0, 2)[:, None],
            selected[:, :, 23:, :])
        assert miss == 0.0
    assert stats["held_assignments"].shape == (8, 2)
    # a second turn from the same position over the returned caches
    again, _ = tfm.decode_from(params, caches, first, 24, 8, cfg=pcfg)
    assert np.array_equal(np.asarray(again), tokens)


def session_turn(cfg, batch=2, context_len=24, n_new=8):
    """(context, first ids, tokens, counters) of one counted turn."""
    pcfg = dsv32_program_config(cfg)
    params = params_of(cfg)
    context = ids(batch, context_len, cfg["vocab_size"])
    caches, last = tfm.prefill(params, jnp.asarray(context), cfg=pcfg,
                               total=context_len + n_new)
    caches = tfm.decode_caches(caches, cfg=pcfg, p_len=context_len,
                               total=context_len + n_new)
    first = jnp.argmax(last, -1).astype(jnp.int32)
    tokens, _, stats = tfm.decode_from(params, caches, first, context_len,
                                       n_new, cfg=pcfg, stats=True)
    return (context, np.asarray(first), np.asarray(tokens),
            {k: np.asarray(v) for k, v in stats.items()})


@pytest.mark.parametrize("what", ["its-own", "elsewhere"])
def test_the_reference_forced_to_choices(what):
    """Forced to the positions and experts the program chose (which in
    float32 are its own), the reference gives what it gives unforced;
    forced elsewhere its logits move, what it reports as its own
    choice does not, and the two misses count the difference."""
    cfg = published()
    context, first, tokens, stats = session_turn(cfg)
    assert stats["experts"].shape == (8, 2, 2, 3)     # steps, layers, B, k
    tails = np.concatenate([first[:1], tokens[0, :-1]])[None]
    selected = stats["selected"][:, :, 0].transpose(1, 0, 2)[:, None]
    experts = stats["experts"][:, :, 0].transpose(1, 0, 2)[:, None]
    if what == "elsewhere":
        selected = np.broadcast_to(np.arange(8), selected.shape)
        experts = (experts + 1) % cfg["router_experts"]
    state = ref.context_pass(cfg, SEED, context[0])
    free, forced = ref.tails_pass(cfg, SEED, state, tails,
                                  [{}, {"forced": (selected, experts)}])
    assert np.array_equal(forced["selected"][0], free["selected"][0])
    miss = (ref.selection_miss(selected, forced["selected"]),
            ref.routing_miss(experts, forced["experts"]))
    if what == "its-own":
        np.testing.assert_allclose(forced["logits"], free["logits"],
                                   atol=1e-5)
        assert miss == (0.0, 0.0)
        assert ref.logit_gaps(forced["logits"], tokens[:1]).max() < 1e-4
    else:
        assert np.abs(forced["logits"] - free["logits"]).max() > 0.01
        assert miss[0] > 0.3 and miss[1] > 0.3


def test_routing_miss_by_hand():
    mine = np.array([[[[0, 1, 2], [3, 4, 5]]]])
    theirs = np.array([[[[2, 1, 0], [3, 9, 8]]]])
    assert ref.routing_miss(mine, theirs) == pytest.approx(2 / 6)


@pytest.mark.parametrize("batch", [1, 12], ids=["few-rows", "many-rows"])
def test_the_indexer_breaks_ties_as_the_reference_does(batch):
    """Scores from a few integers, so that most are tied: the lower
    position wins in the reference and in both of the program's ways
    (without a sort for the few rows of a decode step, `lax.top_k` for
    the many of a prefill block)."""
    rng = np.random.default_rng(3)
    scores = rng.integers(0, 3, (batch, 6, 32)).astype(np.float32)
    assert (scores[..., 0].size > sparse_mla._FEW_ROWS) == (batch == 12)
    pos = np.arange(26, 32)
    seen = np.arange(32)[None, :] <= pos[:, None]
    idx, valid = sparse_mla.select_top_k(
        jnp.where(seen[None], scores, -jnp.inf), 8)
    dims = dataclasses.replace(ref.Dims.of(TINY), index_topk=8)
    for b in range(batch):
        want = ref.select_keys(jnp.asarray(scores[b]), jnp.asarray(seen),
                               jnp.asarray(pos), jnp.arange(32), dims, False)
        mine = np.zeros((6, 32), bool)
        mine[np.arange(6)[:, None], np.asarray(idx[b])] = np.asarray(valid[b])
        assert np.array_equal(mine, np.asarray(want))
    # fewer keys than top_k: all of them, the rest invalid
    few = jnp.broadcast_to(jnp.where(jnp.arange(32) <= 4, 1.0, -jnp.inf),
                           (batch, 6, 32))
    idx, valid = sparse_mla.select_top_k(few, 8)
    assert sorted(np.asarray(idx[0, 0])[np.asarray(valid[0, 0])]) == \
        [0, 1, 2, 3, 4]


@pytest.mark.parametrize("rows,length,k,where", [
    (8, 32832, 2048, "spread"), (1, 32832, 2048, "spread"),
    (8, 32832, 2048, "first-block"), (8, 32832, 2048, "last-blocks"),
    (1, 32832, 2048, "last-blocks"), (3, 1000, 64, "spread"),
    (3, 1000, 64, "first-block"), (3, 1000, 64, "last-blocks"),
    (2, 1024, 64, "last-blocks"), (1, 131072, 2048, "last-blocks"),
    (1, 66000, 66000, "everywhere"),
])
def test_positions_are_where_nonzero_finds_them(rows, length, k, where):
    """`_positions` against `np.nonzero`, row for row: up to ``k`` True
    spread over the row, all inside the first block of 128 lanes, or all
    in the last blocks; rows that end inside a block of 128 (32,832 and
    1,000 positions) and one that ends with a block (1,024); a row of
    131,072, and one with more True before a block than two bytes count
    in bfloat16 (65,792: the count before a block rides as bytes)."""
    rng = np.random.default_rng(rows * length + k)
    chosen = np.zeros((rows, length), bool)
    for r in range(rows):
        n = k if r == 0 else int(rng.integers(0, k + 1))
        if where == "everywhere":
            at = slice(None)
        elif where == "first-block":
            at = rng.choice(128, min(n, 128), replace=False)
        elif where == "last-blocks":
            at = length - 1 - rng.choice(k + 40, n, replace=False)
        else:
            at = rng.choice(length, n, replace=False)
        chosen[r, at] = True
    got = np.asarray(sparse_mla._positions(jnp.asarray(chosen), k))
    assert got.shape == (rows, k) and got.dtype == np.int32
    assert got.min() >= 0 and got.max() < length
    for r in range(rows):
        want = np.nonzero(chosen[r])[0]
        assert np.array_equal(got[r, :len(want)], want)


def test_a_row_too_long_to_count_exactly_is_refused():
    """The running counts are float32: a row of 2**24 positions or more
    is refused where the program is traced, not answered wrongly."""
    row = jax.ShapeDtypeStruct((1, 2 ** 24), bool)
    with pytest.raises(ValueError, match="2\\*\\*24"):
        jax.eval_shape(lambda c: sparse_mla._positions(c, 8), row)


@pytest.mark.parametrize("batch", [1, 12], ids=["few-rows", "many-rows"])
def test_a_key_not_seen_is_never_selected_wherever_it_lies(batch):
    """Minus infinities BETWEEN finite scores, and fewer finite scores
    than ``top_k`` in some rows: every valid index is of a finite score,
    every finite score of such a row is chosen, and a row has
    min(k, finite) valid entries, in both of the program's ways."""
    rng = np.random.default_rng(7)
    k, length = 24, 300
    scores = rng.normal(size=(batch, 6, length)).astype(np.float32)
    scores[:, :, rng.choice(length, 120, replace=False)] = -np.inf
    for q, finite in enumerate([0, 1, 5, 23]):          # rows short of k
        gone = rng.permutation(length)[finite:]
        scores[:, q, gone] = -np.inf
    assert (scores[..., 0].size > sparse_mla._FEW_ROWS) == (batch == 12)
    idx, valid = map(np.asarray,
                     sparse_mla.select_top_k(jnp.asarray(scores), k))
    assert idx.min() >= 0 and idx.max() < length
    for b in range(batch):
        for q in range(6):
            finite = np.isfinite(scores[b, q])
            mine = idx[b, q][valid[b, q]]
            assert len(set(mine)) == len(mine) == min(k, finite.sum())
            assert finite[mine].all()
            if finite.sum() <= k:
                assert set(mine) == set(np.nonzero(finite)[0])
            else:
                kth = np.sort(scores[b, q][finite])[-k]
                assert scores[b, q][mine].min() >= kth
                assert set(np.nonzero(scores[b, q] > kth)[0]) <= set(mine)


def test_the_kth_largest_key_is_exact():
    rng = np.random.default_rng(5)
    keys = rng.integers(0, 2 ** 32, size=(3, 2, 700), dtype=np.uint32)
    keys[0, 0, :] = 2 ** 32 - 1                 # all bits set, all tied
    for k in (1, 9, 700):
        got = np.asarray(sparse_mla.kth_largest_key(jnp.asarray(keys), k))
        assert np.array_equal(got, np.sort(keys, -1)[..., -k])
    floats = rng.normal(size=(2, 500)).astype(np.float32)
    floats[0, :5] = [-np.inf, np.inf, 0.0, -0.0, 3.5]
    order = np.asarray(sparse_mla._sortable(jnp.asarray(floats)))
    assert np.array_equal(np.argsort(order, -1, kind="stable"),
                          np.argsort(floats, -1, kind="stable"))


def test_index_scores_are_the_weighted_relu_sum():
    rng = np.random.default_rng(4)
    q = rng.normal(size=(2, 3, 4, 8)).astype(np.float32)
    w = rng.normal(size=(2, 3, 4)).astype(np.float32)
    k = rng.normal(size=(2, 10, 8)).astype(np.float32)
    pos = np.array([4, 7, 9])
    got = np.asarray(sparse_mla.index_scores(q, w, k, jnp.asarray(pos)))
    want = np.einsum("bqjs,bqj->bqs",
                     np.maximum(np.einsum("bqjd,bsd->bqjs", q, k), 0), w)
    for i, p in enumerate(pos):
        np.testing.assert_allclose(got[:, i, :p + 1], want[:, i, :p + 1],
                                   rtol=1e-5, atol=1e-5)
        assert np.all(np.isneginf(got[:, i, p + 1:]))


ROUTER = dict(top_k=3, n_groups=4, topk_groups=2, scale=2.5)


def router_inputs(tokens=64, d=16, experts=16, bias_std=0.02):
    rng = np.random.default_rng(7)
    return (rng.normal(size=(tokens, d)).astype(np.float32),
            (rng.normal(size=(d, experts)) / 4).astype(np.float32),
            (bias_std * rng.normal(size=experts)).astype(np.float32))


def test_the_router_matches_the_reference():
    x, w, b = router_inputs()
    expert, weight = moe.route_grouped(x, w, b, **ROUTER)
    dims = dataclasses.replace(ref.Dims.of(TINY), router_experts=16)
    want_e, want_w, own = ref.route({"moe_router_W": w, "moe_router_b": b},
                                    jnp.asarray(x), dims)
    assert np.array_equal(np.asarray(own), np.asarray(want_e))
    assert np.array_equal(np.asarray(expert), np.asarray(want_e))
    np.testing.assert_allclose(np.asarray(weight), np.asarray(want_w),
                               rtol=1e-6)


def test_the_router_keeps_to_its_groups_and_scales_its_weights():
    x, w, b = router_inputs()
    expert, weight = moe.route_grouped(x, w, b, **ROUTER)
    expert, weight = np.asarray(expert), np.asarray(weight)
    assert np.all(np.array([len(set(e // 4)) for e in expert]) <= 2)
    assert np.all([len(set(e)) == 3 for e in expert])
    np.testing.assert_allclose(weight.sum(-1), 2.5, rtol=1e-5)


def test_the_bias_moves_the_choice_and_not_the_weight():
    x, w, _ = router_inputs()
    none, w0 = moe.route_grouped(x, w, np.zeros(16, np.float32), **ROUTER)
    bias = np.zeros(16, np.float32)
    bias[5] = 10.0                      # expert 5 (and its group) always
    expert, weight = moe.route_grouped(x, w, bias, **ROUTER)
    expert, weight = np.asarray(expert), np.asarray(weight)
    assert np.all(np.any(expert == 5, axis=-1))
    assert not np.array_equal(expert, np.asarray(none))
    # its weight is its sigmoid score's share, under 2.5, not 10's
    sc = 1 / (1 + np.exp(-(x @ w)))
    picked = np.take_along_axis(sc, expert, -1)
    np.testing.assert_allclose(
        weight, 2.5 * picked / picked.sum(-1, keepdims=True), rtol=1e-5)


def held_layer(tokens: int, held, shared=True, seed=0):
    # (widths of whole lanes: what the grouped kernel takes)
    d, ff, experts = 128, 128, 16
    params = moe.init_moe_held(jax.random.PRNGKey(1), d, ff, experts,
                               (0, experts), n_shared=1)
    # initialisation leaves the selection bias at zero: work its path
    params["moe_router_b"] = 0.02 * jax.random.normal(
        jax.random.PRNGKey(9), (experts,))
    first, count = held
    mine = {k: (v[first:first + count] if k[4:] in ("wg", "wu", "wd") else v)
            for k, v in params.items()}
    x = jax.random.normal(jax.random.PRNGKey(2 + seed), (tokens, d))
    return moe.moe_ffn_held(mine, x, held=held, shared=shared, **ROUTER), \
        params, x


@pytest.fixture
def path(request, monkeypatch):
    """`kernel`: what the chip decides for a decode step's tile, the
    grouped call of `ops/moe_held.py`, interpreted here."""
    if request.param == "kernel":
        from lua_mapreduce_tpu import ops
        monkeypatch.setattr(ops, "default_backend",
                            lambda op=None: "pallas_interpret")
    return request.param


# one pass; chunks of 512; one pass through the kernel
HELD_CASES = pytest.mark.parametrize(
    "tokens,path", [(8, "xla"), (700, "xla"), (8, "kernel")],
    indirect=["path"])


@HELD_CASES
def test_the_shares_add_up_to_the_uncut_layer(tokens, path):
    """The guide's test: 4 shares of 4 of the 16 experts, the shared
    expert counted once, give what the whole layer gives."""
    (whole, stats), _, _ = held_layer(tokens, (0, 16))
    parts = [held_layer(tokens, (4 * i, 4), shared=i == 0)[0]
             for i in range(4)]
    total = sum(out for out, _ in parts)
    np.testing.assert_allclose(np.asarray(total), np.asarray(whole),
                               rtol=1e-4, atol=1e-5)
    assert int(stats["held_assignments"]) == 3 * tokens     # nothing dropped
    assert sum(int(s["held_assignments"]) for _, s in parts) == 3 * tokens


@HELD_CASES
def test_the_held_layer_is_the_weighted_sum_of_its_experts(tokens, path):
    (out, stats), params, x = held_layer(tokens, (4, 8))
    expert, weight = moe.route_grouped(x, params["moe_router_W"],
                                       params["moe_router_b"], **ROUTER)
    want = moe._swiglu(x, params["moe_sg"], params["moe_su"],
                       params["moe_sd"])
    for e in range(4, 12):
        g = jnp.sum(jnp.where(expert == e, weight, 0.0), -1)
        want = want + g[:, None] * moe._swiglu(
            x, params["moe_wg"][e], params["moe_wu"][e], params["moe_wd"][e])
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), rtol=1e-4,
                               atol=1e-5)
    inside = np.asarray((expert >= 4) & (expert < 12))
    assert int(stats["held_assignments"]) == inside.sum()
    assert int(stats["experts_touched"]) == len(set(
        np.asarray(expert)[inside]))


DENSE = tfm.TransformerConfig.llama_style(
    vocab=64, d_model=32, n_heads=4, n_kv_heads=2, n_layers=2, d_ff=64,
    max_seq=128)


@pytest.mark.parametrize("cfg,kv_q8", [
    (DENSE, False), (DENSE, True),
    (dataclasses.replace(DENSE, window=16), False),      # rolling cache
    (dataclasses.replace(DENSE, window=16), True),
    (dataclasses.replace(DENSE, window=32), False),      # window = the whole
    (dataclasses.replace(DENSE, head_dim=16, tied_head=False,
                         norm_eps=1e-6), False),
], ids=["gqa", "gqa-q8", "rolling", "rolling-q8", "window-is-total",
        "wide-heads-untied"])
def test_decode_from_after_prefill_is_greedy_decode(cfg, kv_q8):
    params = tfm.init_transformer(jax.random.PRNGKey(0), cfg)
    prompt = jnp.arange(40, dtype=jnp.int32).reshape(2, 20) % cfg.vocab
    want = np.asarray(tfm.greedy_decode(params, prompt, 12, cfg=cfg,
                                        use_prefill=True, kv_q8=kv_q8))
    caches, last = tfm.prefill(params, prompt, cfg=cfg, total=32)
    caches = tfm.decode_caches(caches, cfg=cfg, p_len=20, total=32,
                               kv_q8=kv_q8)
    first = jnp.argmax(last, -1).astype(jnp.int32)
    tokens, _ = tfm.decode_from(params, caches, first, 20, 11, cfg=cfg,
                                kv_q8=kv_q8)
    got = np.concatenate([np.asarray(prompt), np.asarray(first)[:, None],
                          np.asarray(tokens)], axis=1)
    assert np.array_equal(got, want)
    if cfg.window == 32:
        # one rule says whether a cache rolls (`_rolls`): a window as long
        # as the whole decode rolls with p mod window = p, from scratch too
        assert kinds._cache_shape(cfg, 32) == (True, 32)
        assert kinds._cache_shape(cfg, 24) == (False, 24)
        assert np.array_equal(np.asarray(tfm.greedy_decode(
            params, prompt, 12, cfg=cfg)), want)


def test_head_dim_epsilon_and_the_untied_head_are_configuration():
    cfg = dataclasses.replace(DENSE, head_dim=16, tied_head=False)
    params = tfm.init_transformer(jax.random.PRNGKey(0), cfg)
    assert params["L0_qkv_W"].shape == (32, (4 + 2 * 2) * 16)
    assert params["L0_out_W"].shape == (4 * 16, 32)
    assert params["head_W"].shape == (32, 64)
    tokens = jnp.arange(16, dtype=jnp.int32).reshape(1, 16)
    base = tfm.transformer_apply(params, tokens, cfg=cfg)
    other = dict(params, head_W=2 * params["head_W"])
    np.testing.assert_allclose(
        np.asarray(tfm.transformer_apply(other, tokens, cfg=cfg)),
        2 * np.asarray(base), rtol=1e-5)
    coarse = tfm.transformer_apply(
        params, tokens, cfg=dataclasses.replace(cfg, norm_eps=1.0))
    assert not np.allclose(np.asarray(coarse), np.asarray(base), atol=1e-3)


def test_what_is_served_only_says_so_where_it_is_trained():
    import optax
    from jax.sharding import Mesh
    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1), ("dp", "sp"))
    with pytest.raises(ValueError, match="served"):
        tfm.make_train_step(dsv32_program_config(TINY), mesh,
                            optax.adam(1e-3))
    with pytest.raises(ValueError, match="grouped"):
        tfm.init_transformer(jax.random.PRNGKey(0), dataclasses.replace(
            DENSE, moe_experts=8, moe_router="grouped", ffn="gelu"))
    with pytest.raises(ValueError, match="range"):
        tfm.init_transformer(jax.random.PRNGKey(0), dataclasses.replace(
            DENSE, moe_experts=8, moe_router="grouped", moe_top_k=2,
            moe_held=(6, 4)))


def test_yarn_frequencies_are_the_references():
    pcfg = dsv32_program_config(TINY)
    np.testing.assert_allclose(
        kinds._yarn_freqs(pcfg.latent, pcfg.rope_base),
        ref.yarn_frequencies(ref.Dims.of(TINY)), rtol=1e-6)
    real = dict(TINY, qk_rope_head_dim=64, rope_scaling=dict(
        TINY["rope_scaling"], original_max_position_embeddings=4096))
    freqs = ref.yarn_frequencies(ref.Dims.of(real))
    plain = 1.0 / 10000 ** (np.arange(0, 64, 2) / 64)
    assert freqs[0] == plain[0] and np.isclose(freqs[-1], plain[-1] / 40)
