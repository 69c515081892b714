"""sarvam-105b's layers in the program (latent attention in the absorbed
form over a whole cache, direct queries with the qk-norm, the grouped
dropless expert layer of a chip that holds a share) against the
benchmark's plain float32 reference (`perfbench/reference_sarvam.py`,
un-absorbed, which shares no code with the program), and the latent
flash-decode kernel against its XLA composition. CPU, tiny widths,
seeded."""

import functools
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from lua_mapreduce_tpu import ops
from lua_mapreduce_tpu.models import attention_kinds as kinds
from lua_mapreduce_tpu.models import transformer as tfm
from lua_mapreduce_tpu.ops import mla_decode
from lua_mapreduce_tpu.parallel import moe
from perfbench import reference_sarvam as ref
from perfbench import weights, weights_kimilinear, weights_sarvam
from perfbench.model_kimilinear import program_config as kimi_config
from perfbench.model_sarvam import program_config

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
with open(os.path.join(ROOT, "perfbench", "tests", "data",
                       "tiny-sarvam.json")) as f:
    TINY = json.load(f)
SEED = 2 ** 31 + 7
CONTEXT, TOTAL, N_NEW = 24, 32, 8

# the parts a latent model may have or lack (no indexer in any of them):
# sarvam-105b's own, the qk-norm off, and queries through a latent
PARTS = {"direct+qknorm": {}, "direct": {"use_qk_norm": False},
         "q-latent+qknorm": {"q_lora_rank": 32}}


def params_of(cfg: dict, dtype=jnp.float32) -> dict:
    """The seed's weights as the reference holds them (bfloat16 values),
    for the program, in ``dtype``."""
    return weights_sarvam.finish(cfg, weights.make_leaves(
        weights.seed_key(SEED), weights_sarvam.indexed(cfg), dtype,
        via=jnp.bfloat16))


def ids(rows: int, length: int, vocab: int) -> np.ndarray:
    return weights.token_rows(SEED, 0, rows, length, vocab)


def reference_pass(cfg: dict, row: np.ndarray) -> tuple:
    """The reference's logits for positions 1.. of one row (the row as a
    context of one token and one tail) and, layer by layer, the cache
    rows `[c_kv | k_rope]` of every position."""
    state = ref.context_pass(cfg, SEED, row)
    rows = [np.concatenate([np.asarray(s["c_kv"]), np.asarray(s["k_r"])], -1)
            for s in state]
    return ref.forward(cfg, SEED, row[:1], row[None, 1:])["logits"][0], rows


@pytest.mark.parametrize("parts", sorted(PARTS))
def test_the_full_forward_matches_the_plain_reference(parts):
    """Absorbed latent attention over all rows up to the query's own =
    the reference's un-absorbed attention, on logits. float32: the gap
    is summation order (2e-4 of logits of size 1-3); bfloat16 anywhere
    in the program would read 1e-2 and more."""
    cfg = dict(TINY, **PARTS[parts])
    row = ids(1, 40, cfg["vocab_size"])[0]
    logits = tfm.transformer_apply(params_of(cfg), row[None],
                                   cfg=program_config(cfg))[0]
    want, _ = reference_pass(cfg, row)
    np.testing.assert_allclose(np.asarray(logits)[1:], want, atol=2e-4)


def served(cfg, params, chunk):
    """A prefill of two contexts and one turn of the session entry:
    (context, first ids, tokens, caches, counters)."""
    pcfg = program_config(cfg)
    context = ids(2, CONTEXT, cfg["vocab_size"])
    caches, last = tfm.prefill(params, jnp.asarray(context), cfg=pcfg,
                               total=TOTAL, chunk=chunk)
    caches = tfm.decode_caches(caches, cfg=pcfg, p_len=CONTEXT, total=TOTAL)
    first = jnp.argmax(last, -1).astype(jnp.int32)
    tokens, caches, stats = tfm.decode_from(params, caches, first, CONTEXT,
                                            N_NEW, cfg=pcfg, stats=True)
    return context, np.asarray(first), np.asarray(tokens), caches, stats


@pytest.mark.parametrize("chunk", [None, 8])
@pytest.mark.parametrize("parts", sorted(PARTS))
def test_prefill_then_decode_from_matches_the_full_forward(parts, chunk):
    """Through the cache: the rows that prefill (whole or in chunks) and
    the scan wrote are the reference's latents and rope keys of one full
    forward, layer by layer (so every layer's output at every position
    is), and every served token is the reference's best within 1e-4 of
    its logit. float32."""
    cfg = dict(TINY, **PARTS[parts])
    context, first, tokens, caches, stats = served(cfg, params_of(cfg), chunk)
    assert "selected" not in stats          # nothing selects
    assert stats["held_assignments"].shape == (N_NEW, 2)
    assert stats["experts"].shape == (N_NEW, 2, 2, 3)
    for b in range(2):
        row = np.concatenate([context[b], first[b:b + 1], tokens[b]])
        want, rows = reference_pass(cfg, row[:-1])
        # the first token comes from prefill's logits, the rest from the scan
        gaps = ref.logit_gaps(want[None, CONTEXT - 2:], row[None, CONTEXT:])
        assert gaps.max() < 1e-4, gaps
        for i, layer in enumerate(rows):
            np.testing.assert_allclose(
                np.asarray(caches[f"L{i}_ckv"])[b], layer, atol=2e-5)
        assert set(caches) == {f"L{i}_ckv" for i in range(3)}


@pytest.mark.parametrize("chunk", [None, 8])
def test_served_in_bfloat16_stays_within_its_rounding(chunk):
    """The served type: bfloat16 weights, activations and cache against
    the float32 reference on the same values. A logit of size 2-3 that
    went through three bfloat16 layers is off by 0.03-0.07 here (2^-8 of
    itself a rounding, some tens of roundings): limit 0.15 on prefill's
    logits, and 0.15 on the gap by which a served token lies below the
    reference's best. It is bfloat16's and nobody else's: the float32
    tests above hold the same numbers to 2e-4 and 1e-4, which these
    readings fail by a factor of a hundred. Every layer dense: at width
    64 a token whose third expert of 16 is a near tie goes elsewhere on
    a rounding and its logits move by 0.3, which is a choice and not a
    precision (the chip's comparison forces the reference to the
    program's experts for that reason); the float32 tests hold the
    expert layers."""
    cfg = dict(TINY, first_k_dense_replace=3)
    pcfg = program_config(cfg)
    params = params_of(cfg, jnp.bfloat16)
    context, first, tokens, caches, _ = served(cfg, params, chunk)
    _, last = tfm.prefill(params, jnp.asarray(context), cfg=pcfg,
                          total=TOTAL, chunk=chunk)
    assert caches["L0_ckv"].dtype == jnp.bfloat16
    for b in range(2):
        row = np.concatenate([context[b], first[b:b + 1], tokens[b]])
        want, _ = reference_pass(cfg, row[:-1])
        off = np.abs(np.asarray(last)[b] - want[CONTEXT - 2]).max()
        assert 2e-3 < off < 0.15, off
        gaps = ref.logit_gaps(want[None, CONTEXT - 2:], row[None, CONTEXT:])
        assert gaps.max() < 0.15, gaps


@pytest.mark.parametrize("fault", ["skip_newest", "no_k_rope", "no_q_gain"])
def test_an_attention_fault_moves_the_logits(fault):
    """What the controls plant on the chip, at the tiny size: the
    reference with the fault in its tail's positions is further from the
    sound reference than float32's 2e-4."""
    cfg = dict(TINY)
    row = ids(1, 40, cfg["vocab_size"])[0]
    sound = ref.forward(cfg, SEED, row[:24], row[None, 24:])["logits"]
    faulty = ref.forward(cfg, SEED, row[:24], row[None, 24:],
                         fault=fault)["logits"]
    moved = np.abs(sound - faulty).max()
    # at 40 positions the newest 512 are all of them but the query's own
    assert moved > 1e-2, (fault, moved)


# --------------------------------------------------------------------------
# the latent flash-decode kernel against its XLA composition
# --------------------------------------------------------------------------

def kernel_inputs(b, h, s_len, width, dtype, seed=0):
    kq, kc = jax.random.split(jax.random.PRNGKey(seed))
    return (jax.random.normal(kq, (b, h, width), dtype),
            jax.random.normal(kc, (b, s_len, width), dtype))


@pytest.mark.parametrize("b,s_len,t,tiles", [
    (2, 2500, 5, (2, 1024)),      # t in the first chunk: the others are dead
    (2, 2500, 1023, (2, 1024)),   # the last position of a whole chunk
    (2, 2500, 1024, (2, 1024)),   # the first of the next
    (2, 2500, 2499, (2, 1024)),   # the cache full, its last chunk ragged
    (4, 100, 57, (4, 128)),       # one chunk, hanging over the end
    (11, 2500, 2100, (1, 1024)),  # one row a step: 11 rows divide by none
    (1, 20000, 19999, (1, 2048)),  # one row: a longer chunk
    (8, 2048, 1500, (4, 1024)),   # several rows a step, whole chunks only
])
def test_the_latent_decode_kernel_matches_its_composition(b, s_len, t, tiles):
    q, cache = kernel_inputs(b, 4, s_len, 160, jnp.float32, seed=s_len + t)
    assert mla_decode._tiles(b, s_len, 160, 128, 4, 4) == tiles
    args = dict(v_rank=128, scale=0.3)
    want = mla_decode.mla_decode_attention(q, cache, jnp.int32(t),
                                           backend="xla", **args)
    got = mla_decode.mla_decode_attention(q, cache, jnp.int32(t),
                                          backend="pallas_interpret", **args)
    assert got.shape == (b, 4, 128) and got.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)


def test_the_latent_decode_kernel_in_bfloat16():
    """The served type: the kernel rounds its softmax weights to
    bfloat16 a chunk at a time against each chunk's running maximum, the
    composition once against the row's: 2^-8 of a value of size 1."""
    q, cache = kernel_inputs(2, 4, 2500, 160, jnp.bfloat16)
    args = dict(v_rank=128, scale=0.3)
    want = mla_decode.mla_decode_attention(q, cache, jnp.int32(2300),
                                           backend="xla", **args)
    got = mla_decode.mla_decode_attention(q, cache, jnp.int32(2300),
                                          backend="pallas_interpret", **args)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=8e-3)


def test_the_latent_decode_kernel_reads_nothing_past_t():
    """Slots past ``t`` hold what an earlier turn left there (anything
    finite): a turn reads none it has not written."""
    q, cache = kernel_inputs(2, 4, 2500, 160, jnp.float32)
    dirty = cache.at[:, 1301:].set(1e3)
    for backend in ("xla", "pallas_interpret"):
        out = mla_decode.mla_decode_attention(
            q, dirty, jnp.int32(1300), v_rank=128, scale=0.3, backend=backend)
        clean = mla_decode.mla_decode_attention(
            q, cache, jnp.int32(1300), v_rank=128, scale=0.3, backend=backend)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(clean))


@pytest.mark.parametrize("rows", [1, "chosen"])
@pytest.mark.parametrize("b,s_len,ts", [
    (16, 1000, (256, 257, 319, 383)),   # lanes 0, 1, 63, 127 of a tile
    (2, 4160, range(4096, 4160)),       # the Kimi cell's last tile, all
    (1, 32832, range(32768, 32832)),    # the sarvam cell's last tile, all
    (3, 300, (299, 300, 10 ** 6)),      # past the end: clamped, as XLA's
])
def test_the_latent_row_put_is_the_dynamic_update_slice(b, s_len, ts, rows):
    """The decode step's in-place put of a row into a (B, S, 576)
    bfloat16 cache, a position after another as a scan makes them, is
    `lax.dynamic_update_slice` bit for bit: every slot but the one
    written (the other lanes of its tile column, and of a column that
    hangs over the cache's end) is as it was, at one row a grid step
    and at the rows `_put_rows` chooses."""
    width = 576
    if rows == "chosen":
        rows = mla_decode._put_rows(b, width, 2)
        assert rows == {16: 8, 2: 2, 1: 1, 3: 3}[b]
    bits = lambda x: np.asarray(x).view(np.uint16)  # noqa: E731
    kc, kr = jax.random.split(jax.random.PRNGKey(s_len))
    put = want = jax.random.normal(kc, (b, s_len, width), jnp.bfloat16)
    for i, t in enumerate(ts):
        row = jax.random.normal(jax.random.fold_in(kr, i), (b, 1, width),
                                jnp.bfloat16)
        want = jax.lax.dynamic_update_slice(want, row, (0, t, 0))
        put = mla_decode._latent_row_put(put, row, jnp.int32(t), rows=rows,
                                         interpret=True)
        # the written tile column at each step, the whole cache at the end
        col = slice(min(t, s_len - 1) // 128 * 128, None)
        np.testing.assert_array_equal(bits(put[:, col][:, :128]),
                                      bits(want[:, col][:, :128]))
    assert put.shape == want.shape and put.dtype == want.dtype
    np.testing.assert_array_equal(bits(put), bits(want))


def tiny_stack(model: str) -> tuple:
    """(program config, bfloat16 weights, two contexts) of a tiny
    sarvam-shaped stack (every layer latent) or Kimi-shaped one (three
    KDA layers, then a latent one without positions)."""
    if model == "sarvam":
        return (program_config(TINY), params_of(TINY, jnp.bfloat16),
                ids(2, CONTEXT, TINY["vocab_size"]))
    with open(os.path.join(ROOT, "perfbench", "tests", "data",
                           "tiny-kimilinear.json")) as f:
        cfg = json.load(f)
    return (kimi_config(cfg), weights_kimilinear.make_params(cfg, SEED),
            weights.token_rows(SEED, 0, 2, CONTEXT, cfg["vocab_size"]))


@pytest.fixture
def fresh_traces():
    """`decode_from` traced anew: what a test patches below it is not in
    the jit's key."""
    jax.clear_caches()
    yield
    jax.clear_caches()


@pytest.mark.parametrize("model", ["sarvam", "kimi"])
def test_a_turn_is_the_same_with_the_row_put_as_with_the_update_slice(
        model, monkeypatch, fresh_traces):
    """The session entry on the latent kernel's backend (interpret mode
    here), where a decode step's row goes in through the in-place put,
    serves the tokens and leaves the caches of the same turn with the
    row written by `lax.dynamic_update_slice`, bit for bit; the put is
    traced once a latent layer."""
    pcfg, params, context = tiny_stack(model)
    monkeypatch.setattr(ops, "default_backend", lambda op=None: (
        "pallas_interpret" if op == "mla_decode_attention" else "xla"))
    traced = []
    put = mla_decode._latent_row_put
    monkeypatch.setattr(mla_decode, "_latent_row_put",
                        lambda *a, **k: traced.append(1) or put(*a, **k))

    caches, last = tfm.prefill(params, jnp.asarray(context), cfg=pcfg,
                               total=TOTAL)
    start = tfm.decode_caches(caches, cfg=pcfg, p_len=CONTEXT, total=TOTAL)
    first = jnp.argmax(last, -1).astype(jnp.int32)

    def turn():
        jax.clear_caches()
        # (the entry donates its caches)
        tokens, caches = tfm.decode_from(
            params, {k: jnp.copy(v) for k, v in start.items()}, first,
            CONTEXT, N_NEW, cfg=pcfg)
        return np.asarray(tokens), {k: np.asarray(v)
                                    for k, v in caches.items()}

    tokens, caches = turn()
    latent = [k for k in caches if k.endswith("_ckv")]
    assert len(traced) == len(latent) == {"sarvam": 3, "kimi": 1}[model]
    assert caches[latent[0]].dtype == jnp.bfloat16
    monkeypatch.setattr(kinds, "latent_row_put", functools.partial(
        mla_decode.latent_row_put, backend="xla"))
    want_tokens, want_caches = turn()
    assert len(traced) == len(latent)
    np.testing.assert_array_equal(tokens, want_tokens)
    assert set(caches) == set(want_caches)
    for k, v in caches.items():
        np.testing.assert_array_equal(v, want_caches[k], err_msg=k)


@pytest.mark.parametrize("s_len,first,q_len", [
    (700, 0, 40),        # from the start: one key block
    (700, 600, 100),     # the cache's last block starts where it still fits
    (5000, 2048, 64),    # several key blocks, none past the queries read
])
def test_the_causal_form_is_the_decode_form_position_by_position(
        s_len, first, q_len):
    """A block of queries over the growing cache = each query's decode
    attention at its own position; key blocks past the block's last
    position are never read (NaN there)."""
    kq, kc = jax.random.split(jax.random.PRNGKey(s_len))
    q = jax.random.normal(kq, (2, q_len, 4, 160), jnp.float32)
    cache = jax.random.normal(kc, (2, s_len, 160), jnp.float32)
    pos = first + jnp.arange(q_len)
    kb = min(2048, s_len)
    dead = ((first + q_len - 1) // kb + 1) * kb
    got = mla_decode.mla_causal_attention(
        q, cache.at[:, dead:].set(jnp.nan), pos, v_rank=128, scale=0.3)
    for i in (0, q_len // 2, q_len - 1):
        want = mla_decode.mla_decode_attention(
            q[:, i], cache, pos[i], v_rank=128, scale=0.3, backend="xla")
        np.testing.assert_allclose(np.asarray(got[:, i]), np.asarray(want),
                                   atol=1e-5)


# --------------------------------------------------------------------------
# the share tied to the model
# --------------------------------------------------------------------------

def test_four_shares_add_up_to_the_uncut_layer():
    """EP4 at the tiny size: the parts of an expert layer's result that
    the four shares give (held = (0, 4), (4, 4), (8, 4), (12, 4)), the
    shared expert counted once, add up to what the reference gives for
    the uncut layer (all 16 experts held)."""
    cfg = dict(TINY)
    uncut = dict(cfg, num_experts=cfg["router_experts"], first_expert_held=0)
    whole = ref.Weights(uncut, SEED).layer(1, True)
    x = jax.random.normal(jax.random.PRNGKey(3), (64, cfg["hidden_size"]),
                          jnp.float32)
    # ffn_part norms its input and adds the residual: take both back out
    y = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True)
                          + cfg["rms_norm_eps"]) * whole["ln2_g"]
    want = ref.ffn_part(whole, x, False, ref.Dims.of(uncut), None, "",
                        None)[0] - x
    total = jnp.zeros_like(x)
    n = cfg["num_experts"]
    for share in range(cfg["router_experts"] // n):
        held = (share * n, n)
        params = {f"moe_{k[4:]}": (v[held[0]:held[0] + n]
                                   if k in ("moe_wg", "moe_wu", "moe_wd")
                                   else v)
                  for k, v in whole.items() if k.startswith("moe_")}
        out, stats = moe.moe_ffn_held(
            params, y, held=held, top_k=cfg["num_experts_per_tok"],
            n_groups=1, topk_groups=1,
            scale=cfg["routed_scaling_factor"], shared=share == 0)
        total = total + out
    np.testing.assert_allclose(np.asarray(total), np.asarray(want),
                               atol=2e-5)
    assert int(stats["experts"].shape[1]) == cfg["num_experts_per_tok"]


# --------------------------------------------------------------------------
# the configuration's two absences
# --------------------------------------------------------------------------

@pytest.mark.parametrize("change,named", [
    ({"index_top_k": 4}, "index_heads"),
    ({"index_top_k": 4, "index_heads": 2, "index_dim": 8}, "q_rank"),
    ({"index_heads": 2, "index_dim": 8}, "index_heads"),
    ({"q_rank": -1}, "q_rank"),
])
def test_a_wrong_latent_field_is_named(change, named):
    import dataclasses
    pcfg = program_config(TINY)
    bad = dataclasses.replace(pcfg, latent=dataclasses.replace(
        pcfg.latent, **change))
    with pytest.raises(ValueError, match=named):
        tfm.init_transformer(jax.random.PRNGKey(0), bad)


def test_a_latent_cache_has_no_int8_form():
    pcfg = program_config(TINY)
    params = params_of(TINY)
    with pytest.raises(ValueError, match="kv_q8"):
        tfm.greedy_decode(params, jnp.zeros((1, 4), jnp.int32), 2, cfg=pcfg,
                          kv_q8=True)


def test_the_leaves_follow_the_parts():
    """`q_W` and `q_g` stand where the query latent's leaves stood, and
    the indexer's are absent with it."""
    names = {k.split("_", 1)[1] for k in tfm.init_transformer(
        jax.random.PRNGKey(0), program_config(TINY)) if k.startswith("L1_")}
    assert {"q_W", "q_g", "kva_W", "kv_g", "kvb_W", "out_W"} <= names
    assert not names & {"qa_W", "qa_g", "qb_W", "iq_W", "ik_W", "iw_W",
                        "ik_g", "ik_b"}
    table = {n for n, _, _ in weights_sarvam.layer_leaves(TINY, 1)}
    assert {f"L1_{n}" for n in names} == table
