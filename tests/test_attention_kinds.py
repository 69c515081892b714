"""The seam of `models/attention_kinds.py`: what a layer's attention is
(its weights, what it caches, the cache's format, the attention over it)
is known by its kind alone, and `models/transformer.py` asks
`attention_kind(cfg, i)` and nothing more. So a kind that the package
has never seen, handed out by that lookup, is served by every entry with
no edit to any of them. CPU, tiny widths, float32, seeded.

The jitted entries cache on `cfg`, not on the lookup: every case has a
configuration no other test compiles."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from lua_mapreduce_tpu.models import attention_kinds as kinds
from lua_mapreduce_tpu.models import transformer as tfm
from perfbench import weights, weights_dsv32, weights_phi4flash
from perfbench.model_dsv32 import program_config as dsv32_program_config
from perfbench.model_phi4flash import program_config as phi4flash_config

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SEED = 2 ** 31 + 5


class Renamed(kinds.GroupedQuery):
    """Grouped-query attention under other cache leaf names."""
    leaves = ("key", "val")


class RunningMean(kinds._Kind):
    """Every position weighs all before it alike: the mean of the
    values so far. Its cache is a state, the running sum (B, d), with
    no axis of positions and nothing to pad, transpose or roll."""
    leaves = ("sum",)

    def init(self, keys, dtype, p):
        d = self.cfg.d_model
        return {f"{p}_v_W": kinds._dense(next(keys), (d, d), dtype),
                f"{p}_out_W": kinds._dense(next(keys), (d, d), dtype)}

    def full(self, params, p, y, pos, attn_fn):
        sums = jnp.cumsum(y @ params[f"{p}_v_W"], axis=1)
        mean = sums / (pos + 1.0)[None, :, None]
        return mean @ params[f"{p}_out_W"], (sums[:, -1],)

    def step(self, params, p, y, t, caches, kv_q8):
        (name,) = self.names(p)
        total = caches[name] + y[:, 0] @ params[f"{p}_v_W"]
        out = (total / (t + 1.0)) @ params[f"{p}_out_W"]
        return out[:, None], ({**caches, name: total}, None)

    def empty(self, p, b, total, dtype, kv_q8=False):
        return {name: jnp.zeros((b, self.cfg.d_model), dtype)
                for name in self.names(p)}

    def padded(self, p, rows, total, dtype):
        return dict(zip(self.names(p), rows))

    def scanned(self, p, caches, p_len, total, kv_q8=False):
        return {name: caches[name] for name in self.names(p)}


def dense(vocab: int):
    cfg = tfm.TransformerConfig.llama_style(
        vocab=vocab, d_model=40, n_heads=4, n_kv_heads=2, n_layers=2,
        d_ff=56, max_seq=64)
    return cfg, lambda: tfm.init_transformer(jax.random.PRNGKey(vocab), cfg)


def latent():
    """`tests/test_latent_lm.py`'s model: latent attention, its indexer
    and the expert layers, with the seed's weights."""
    with open(os.path.join(ROOT, "perfbench", "tests", "data",
                           "tiny-dsv32.json")) as f:
        published = json.load(f)
    return dsv32_program_config(published), lambda: weights_dsv32.finish(
        published, weights.make_leaves(
            weights.seed_key(SEED), weights_dsv32.indexed(published),
            jnp.float32, via=jnp.bfloat16))


def hybrid():
    """`tests/test_hybrid_lm.py`'s model: a SambaY stack (state-space and
    window layers, a full-attention cache that a cross layer reads, a
    gated memory unit) whose window of 8 is shorter than the prompt, so
    that the buffers roll; with the seed's weights."""
    with open(os.path.join(ROOT, "perfbench", "tests", "data",
                           "tiny-phi4flash.json")) as f:
        published = json.load(f)
    return phi4flash_config(published), lambda: weights_phi4flash.finish(
        published, weights.make_leaves(
            weights.seed_key(SEED), weights_phi4flash.indexed(published),
            jnp.float32, via=jnp.bfloat16))


@pytest.mark.parametrize("model,kind", [
    (lambda: dense(59), None), (latent, None),
    (lambda: dense(61), Renamed), (lambda: dense(67), RunningMean),
    (hybrid, None),
], ids=["grouped-query", "latent", "renamed-leaves", "running-mean",
        "hybrid"])
def test_every_entry_serves_a_kind_as_the_full_forward_does(
        model, kind, monkeypatch):
    """`greedy_decode` both ways, `prefill` + `decode_caches` +
    `decode_from`, and the argmax of `transformer_apply` over the
    served row, token for token."""
    cfg, make_params = model()
    if kind is not None:
        monkeypatch.setattr(tfm, "attention_kind", lambda cfg, i: kind(cfg))
    params = make_params()
    if kind is RunningMean:
        assert "L1_v_W" in params and "L1_qkv_W" not in params
    prompt = jnp.asarray(weights.token_rows(SEED, 0, 2, 12, cfg.vocab))
    stepped = np.asarray(tfm.greedy_decode(params, prompt, 6, cfg=cfg))
    fast = np.asarray(tfm.greedy_decode(params, prompt, 6, cfg=cfg,
                                        use_prefill=True))
    assert np.array_equal(stepped, fast)
    full = tfm.transformer_apply(params, fast[:, :-1], cfg=cfg)
    assert np.array_equal(np.asarray(jnp.argmax(full[:, 11:], -1)),
                          fast[:, 12:])
    caches, last = tfm.prefill(params, prompt, cfg=cfg, total=18)
    if kind is not None:
        assert {n.split("_", 1)[1] for n in caches} == set(kind.leaves)
    if cfg.hybrid is not None:
        # the same caches and logits a chunk of 4 positions at a time; the
        # rolling buffers of 8 slots hold positions 4 .. 11
        assert caches["L1_k"].shape[2] == 8 and caches["L5_k"].shape[2] == 18
        chunked, last_chunked = tfm.prefill(params, prompt, cfg=cfg,
                                            total=18, chunk=4)
        assert set(chunked) == set(caches)
        for name in caches:
            np.testing.assert_allclose(chunked[name], caches[name],
                                       rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(last_chunked, last, rtol=1e-4, atol=1e-5)
    caches = tfm.decode_caches(caches, cfg=cfg, p_len=12, total=18)
    first = jnp.argmax(last, -1).astype(jnp.int32)
    tokens, caches = tfm.decode_from(params, caches, first, 12, 5, cfg=cfg)
    assert np.array_equal(np.concatenate(
        [np.asarray(first)[:, None], np.asarray(tokens)], axis=1),
        fast[:, 12:])


def test_the_lookup_is_the_one_place_that_reads_the_configuration():
    cfg, _ = dense(59)
    assert type(tfm.attention_kind(cfg, 0)) is kinds.GroupedQuery
    assert type(tfm.attention_kind(latent()[0], 2)) is kinds.Latent
    # a hybrid stack answers by layer
    assert [type(tfm.attention_kind(hybrid()[0], i)).__name__
            for i in range(8)] == [
        "StateSpace", "GroupedQuery", "StateSpace", "GroupedQuery",
        "StateSpace", "GroupedQuery", "GatedMemory", "Cross"]


def test_a_kind_refuses_the_forms_it_does_not_have():
    cfg, make_params = dense(59)
    prompt = jnp.zeros((1, 8), jnp.int32)
    with pytest.raises(ValueError, match="chunk is for"):
        tfm.prefill(make_params(), prompt, cfg=cfg, chunk=4)
    lcfg, _ = latent()
    with pytest.raises(ValueError, match="no int8 form"):
        tfm.greedy_decode({"tok_emb": jnp.zeros((lcfg.vocab, 4))}, prompt, 2,
                          cfg=lcfg, kv_q8=True)
    with pytest.raises(ValueError, match="no int8 form"):
        tfm.decode_caches({}, cfg=lcfg, p_len=8, total=8, kv_q8=True)
