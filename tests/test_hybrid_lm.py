"""A hybrid stack (SambaY: state-space and window layers, one
full-attention cache read by cross-attention layers, gated memory units,
differential attention) through the system's normal path, held to the
plain reference `perfbench/reference_phi4flash.py`, which shares no code
with the program. CPU, tiny widths, float32 unless a case says
otherwise, seeded.

Tolerances, each with its reason:

- `SAME` 2e-5 on logits of deviation 0.2: program and reference are two
  float32 computations of the same sums in another order (the paired
  128-wide layout against two softmaxes, an online softmax against a
  whole one, (B, N, E) against (E, N) states); they read 2e-6.
- `STATE` 1e-4 relative on a state, a cache row or a mixer's output: the
  same, for one layer.
- a bfloat16 state or softmax where the configuration says float32 reads
  1e-3 to 4e-3, and a planted fault 0.1 to 0.6: each fails `SAME` by 25x
  at least.
"""

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
from lua_mapreduce_tpu.ops import ssm
from perfbench import counts_phi4flash, model_phi4flash
from perfbench import reference_phi4flash as ref
from perfbench import weights, weights_phi4flash as wts

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SEED = 2 ** 31 + 7
SAME, STATE = 2e-5, 1e-4


def published(name="tests/data/tiny-phi4flash.json") -> dict:
    with open(os.path.join(ROOT, "perfbench", name)) as f:
        return json.load(f)


TINY = published()
CFG = model_phi4flash.program_config(TINY)
DIMS = ref.Dims.of(TINY)


@pytest.fixture(scope="module")
def params():
    """The seed's served bfloat16 values, held in float32, as the
    reference holds them."""
    return wts.finish(TINY, weights.make_leaves(
        weights.seed_key(SEED), wts.indexed(TINY), jnp.float32,
        via=jnp.bfloat16))


@pytest.fixture(scope="module")
def ids():
    return weights.token_rows(SEED, 0, 2, 40, TINY["vocab_size"])


def layer(params, i: int) -> dict:
    return {k[len(f"L{i}_"):]: v for k, v in params.items()
            if k.startswith(f"L{i}_")}


def close(a, b, tol=STATE):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    assert np.abs(a - b).max() <= tol * max(1.0, np.abs(b).max()), \
        np.abs(a - b).max()


# --------------------------------------------------------------------------
# the whole model against the reference
# --------------------------------------------------------------------------

def test_the_stack_is_the_published_pattern():
    real = model_phi4flash.program_config(published(
        "configs/phi-4-mini-flash-reasoning.serve.json"))
    k = real.hybrid.kinds
    assert [i for i, x in enumerate(k) if x == "ssm"] == list(
        range(0, 16, 2)) + [16]
    assert [i for i, x in enumerate(k) if x == "swa"] == list(range(1, 16, 2))
    assert [i for i, x in enumerate(k) if x == "full"] == [17]
    assert [i for i, x in enumerate(k) if x == "gmu"] == list(range(18, 32, 2))
    assert [i for i, x in enumerate(k) if x == "cross"] == list(
        range(19, 32, 2))
    assert (real.hybrid.kv_from, real.hybrid.memory_from,
            real.hybrid.cached_layers, real.hybrid.window) == (17, 16, 18, 512)
    assert real.positions == "none" and not real.rope and real.tied_head
    assert type(tfm.attention_kind(real, 16)) is kinds.StateSpace
    assert tfm.attention_kind(real, 16).hands
    assert not tfm.attention_kind(real, 14).hands
    assert tfm.attention_kind(real, 15) == kinds.GroupedQuery(
        real, window=512, differential=True, depth=15)
    assert tfm.attention_kind(real, 17) == kinds.GroupedQuery(
        real, window=0, differential=True, depth=17, shares=True)
    assert tfm.attention_kind(real, 19) == kinds.Cross(real, source=17,
                                                       depth=19)
    assert type(tfm.attention_kind(real, 18)) is kinds.GatedMemory


def test_the_programs_logits_are_the_references(params, ids):
    """`transformer_apply` over 40 positions (window 8, so five windows)
    against the reference's forward, logit by logit."""
    with jax.default_matmul_precision("highest"):
        full = np.asarray(tfm.transformer_apply(params, jnp.asarray(ids),
                                                cfg=CFG))
    for row in range(2):
        want = ref.forward(TINY, SEED, ids[row], 40, quiet=True)
        assert want.std() > 0.1
        assert np.abs(full[row] - want).max() < SAME


@pytest.mark.parametrize("fault", ref.PRECISION_FAULTS + ref.FAULTS)
def test_the_reference_with_a_fault_is_another_model(params, ids, fault):
    """Each planted fault (in the last 8 positions only, over the sound
    context) and each float32 value held in bfloat16 moves the logits by
    far more than the tolerance that holds the program."""
    with jax.default_matmul_precision("highest"):
        full = np.asarray(tfm.transformer_apply(params, jnp.asarray(ids[:1]),
                                                cfg=CFG))[0, -8:]
    wrong = ref.forward(TINY, SEED, ids[0], 8, fault=fault, quiet=True)
    assert np.abs(full - wrong).max() > 25 * SAME, fault


def test_a_bfloat16_state_in_the_program_fails_the_tolerance(
        params, ids, monkeypatch):
    monkeypatch.setattr(ssm, "STATE_DTYPE", jnp.bfloat16)
    with jax.default_matmul_precision("highest"):
        # the jitted entries cache on cfg: an equal cfg of another max_seq
        full = np.asarray(tfm.transformer_apply(
            params, jnp.asarray(ids[:1]),
            cfg=dataclasses.replace(CFG, max_seq=255)))[0]
    want = ref.forward(TINY, SEED, ids[0], 40, quiet=True)
    assert np.abs(full - want).max() > 25 * SAME


def test_the_served_path_is_the_references_forward(params, ids):
    """`prefill(chunk=)` -> `decode_caches` -> `decode_from`: the tokens a
    turn serves are the reference's argmax at every position, the window
    rolling three times over before the turn."""
    prompt = jnp.asarray(ids[:, :24])
    caches, last = tfm.prefill(params, prompt, cfg=CFG, total=36, chunk=6)
    caches = tfm.decode_caches(caches, cfg=CFG, p_len=24, total=36)
    first = jnp.argmax(last, -1).astype(jnp.int32)
    tokens, caches = tfm.decode_from(params, caches, first, 24, 11, cfg=CFG)
    served = np.concatenate([np.asarray(first)[:, None], np.asarray(tokens)],
                            axis=1)
    for row in range(2):
        seq = np.concatenate([ids[row, :24], served[row, :-1]])
        logits = ref.forward(TINY, SEED, seq, 12, quiet=True)
        assert np.array_equal(logits.argmax(-1), served[row])
        assert ref.logit_gaps(logits[None], served[row][None]).max() == 0.0


def test_a_turn_can_be_taken_back_and_gone_on_from(params, ids):
    """`decode_from` twice from one `start` serves the same tokens (the
    states and rolling buffers keep one snapshot: the benchmark's session
    makes every turn from the same position); a turn from where the last
    ended goes on as one longer turn would."""
    prompt = jnp.asarray(ids[:, :24])
    caches, last = tfm.prefill(params, prompt, cfg=CFG, total=40)
    caches = tfm.decode_caches(caches, cfg=CFG, p_len=24, total=40)
    assert {"L0_ssm0", "L0_conv0", "L0_at0", "L1_k0", "L1_at0"} <= set(caches)
    assert "L5_k0" not in caches        # the whole cache: nothing to keep
    first = jnp.argmax(last, -1).astype(jnp.int32)
    once, caches = tfm.decode_from(params, caches, first, 24, 6, cfg=CFG)
    other, caches = tfm.decode_from(params, caches, first + 1, 24, 6, cfg=CFG)
    again, caches = tfm.decode_from(params, caches, first, 24, 6, cfg=CFG)
    assert np.array_equal(once, again) and not np.array_equal(once, other)
    more, caches = tfm.decode_from(params, caches, again[:, -1], 30, 6,
                                   cfg=CFG)
    whole = np.asarray(tfm.greedy_decode(params, prompt, 13, cfg=CFG,
                                         use_prefill=True))[:, 24:]
    assert np.array_equal(np.concatenate([np.asarray(first)[:, None], again,
                                          more], axis=1), whole)


def test_prefill_runs_the_cross_decoder_on_the_last_position_only(
        params, ids, monkeypatch):
    """The layers after the last that caches something feed no cache, so
    a prefill (which returns the last position's logits) runs them for
    that position alone, whole or in chunks; `transformer_apply`, which
    returns every position's, runs them all. The logits agree."""
    seen = []
    for cls, name in ((kinds.Cross, "_over"), (kinds.GatedMemory, "_gate")):
        real = getattr(cls, name)

        def spy(self, params, p, y, *args, _real=real, **kw):
            seen.append((p, y.shape[1]))
            return _real(self, params, p, y, *args, **kw)
        monkeypatch.setattr(cls, name, spy)
    prompt = jnp.asarray(ids[:, :24])
    cfg = dataclasses.replace(CFG, max_seq=254)
    _, whole = tfm.prefill(params, prompt, cfg=cfg)
    _, chunked = tfm.prefill(params, prompt, cfg=cfg, chunk=8)
    assert sorted(seen) == sorted([("L6", 1), ("L7", 1)] * 2)
    del seen[:]
    full = tfm.transformer_apply(params, prompt, cfg=cfg)
    assert sorted(seen) == [("L6", 24), ("L7", 24)]
    close(whole, full[:, -1])
    close(chunked, full[:, -1])


# --------------------------------------------------------------------------
# each kind alone against the reference's layer
# --------------------------------------------------------------------------

def normed(params, i, x):
    return kinds._norm(params, f"L{i}_ln1", x, CFG)


@pytest.fixture(scope="module")
def stream(ids):
    """A (2, 40, d) float32 input of unit size."""
    return jax.random.normal(jax.random.PRNGKey(5),
                             (2, 40, TINY["hidden_size"]), jnp.float32)


def test_the_state_space_kind_gives_one_state_in_every_form(params, stream):
    """`full` over 40 positions, `chunk` five at a time and `step` one at
    a time end in one state and give one output: the reference's."""
    kind = tfm.attention_kind(CFG, 4)
    assert kind.hands and kind.leaves == ("conv", "ssm")
    handed = {}
    with jax.default_matmul_precision("highest"):
        out, (tail, h) = kind.full(params, "L4", stream, jnp.arange(40), None,
                                   handed)
        caches = kind.empty("L4", 2, 40, jnp.float32)
        assert [c.shape for c in caches.values()] == [(2, 3, 128), (2, 4, 128)]
        assert caches["L4_ssm"].dtype == jnp.float32
        outs = []
        for start in range(0, 40, 5):
            o, caches = kind.chunk(params, "L4", stream[:, start:start + 5],
                                   start + jnp.arange(5), caches, start, {})
            outs.append(o)
        stepped = kind.empty("L4", 2, 40, jnp.float32)
        for t in range(40):
            o1, (stepped, _) = kind.step(params, "L4", stream[:, t:t + 1],
                                         jnp.int32(t), stepped, False, {})
        for row in range(2):
            want, s = ref.state_space(layer(params, 4), stream[row],
                                      jnp.arange(40), DIMS, None, "", 0)
            close(out[row], want)
            close(handed["memory"][row], s)
    close(jnp.concatenate(outs, 1), out)
    close(o1, out[:, -1:])
    for got in (caches, stepped):
        close(got["L4_ssm"], h)
        close(got["L4_conv"], tail)


@pytest.mark.parametrize("i,window", [(1, 8), (5, 0)])
def test_paired_differential_attention_is_the_two_softmax_form(
        params, stream, i, window):
    """A pair held as one 128-wide row against `[q1|0]` and `[0|q2]`, over
    the cache layout, in every form (a sequence; chunks over a whole or a
    rolling cache; one position through `decode_attention`): the
    reference's two softmaxes, the subtraction and the norm."""
    kind = tfm.attention_kind(CFG, i)
    assert kind.differential and kind.window == window
    p, w = f"L{i}", layer(params, i)
    q_cols, kv_cols = DIMS.heads * DIMS.hd, DIMS.kv_heads * DIMS.hd
    pos = jnp.arange(40)
    with jax.default_matmul_precision("highest"):
        out, rows = kind.full(params, p, stream, pos, None, {})
        wants = []
        for row in range(2):
            qkv = stream[row] @ w["qkv_W"] + w["qkv_b"]
            a = ref.differential(
                w, qkv[:, :q_cols], qkv[:, q_cols:q_cols + kv_cols],
                qkv[:, q_cols + kv_cols:], pos, i,
                jnp.full((40,), window), DIMS, "", 0)
            wants.append(a @ w["out_W"] + w["out_b"])
        want = jnp.stack(wants)
        close(out, want)
        # chunks of 5 over the scan's caches: 8 slots that roll, or all 40
        caches = kind.empty(p, 2, 40, jnp.float32)
        assert caches[f"{p}_k"].shape == (2, 2, 8 if window else 40, 16)
        outs = []
        for start in range(0, 40, 5):
            o, caches = kind.chunk(params, p, stream[:, start:start + 5],
                                   start + jnp.arange(5), caches, start)
            outs.append(o)
        close(jnp.concatenate(outs, 1), want)
        # and what prefill hands out is what the chunks left
        handed_out = kind.padded(p, rows, 40, jnp.float32)
        for name in caches:
            close(caches[name], handed_out[name])
        # one position at a time
        stepped = kind.empty(p, 2, 40, jnp.float32)
        for t in range(40):
            o1, (stepped, _) = kind.step(params, p, stream[:, t:t + 1],
                                         jnp.int32(t), stepped, False)
            close(o1[:, 0], want[:, t])
        for name in caches:
            close(stepped[name], caches[name])


def test_the_cross_kind_reads_the_full_layers_cache(params, stream):
    """Queries of its own over layer 5's keys and values, in every form;
    nothing cached of its own."""
    full, cross = tfm.attention_kind(CFG, 5), tfm.attention_kind(CFG, 7)
    assert full.shares and cross.source == 5 and cross.leaves == ()
    assert cross.empty("L7", 2, 40, jnp.float32) == {}
    w5, w7 = layer(params, 5), layer(params, 7)
    q_cols, kv_cols = DIMS.heads * DIMS.hd, DIMS.kv_heads * DIMS.hd
    pos = jnp.arange(40)
    other = jnp.flip(stream, axis=1)        # the cross layer's own input
    with jax.default_matmul_precision("highest"):
        handed = {}
        _, rows = full.full(params, "L5", stream, pos, None, handed)
        out, none = cross.full(params, "L7", other, pos, None, handed)
        assert none == ()
        wants = []
        for row in range(2):
            kv = stream[row] @ w5["qkv_W"][:, q_cols:] + w5["qkv_b"][q_cols:]
            a = ref.differential(
                w7, other[row] @ w7["q_W"] + w7["q_b"], kv[:, :kv_cols],
                kv[:, kv_cols:], pos, 7, jnp.zeros((40,), jnp.int32), DIMS,
                "", 0)
            wants.append(a @ w7["out_W"] + w7["out_b"])
        want = jnp.stack(wants)
        close(out, want)
        caches = full.padded("L5", rows, 40, jnp.float32)
        o, same = cross.chunk(params, "L7", other[:, 30:35], pos[30:35],
                              caches, 30, {})
        assert same is caches
        close(o, want[:, 30:35])
        o, _ = cross.step(params, "L7", other[:, 33:34], jnp.int32(33),
                          caches, False, {})
        close(o, want[:, 33:34])
        # fewer positions than `pos` names: the last ones
        o, _ = cross.full(params, "L7", other[:, -1:], pos, None, handed)
        close(o, want[:, -1:])


def test_the_gated_unit_gates_the_memory_of_the_same_positions(params,
                                                               stream):
    kind = tfm.attention_kind(CFG, 6)
    memory = jax.random.normal(jax.random.PRNGKey(9), (2, 40, 128))
    with jax.default_matmul_precision("highest"):
        out, none = kind.full(params, "L6", stream, jnp.arange(40), None,
                              {"memory": memory})
        for row in range(2):
            close(out[row], ref.gated_memory(layer(params, 6), stream[row],
                                             memory[row], jnp.arange(40),
                                             None, "", 0))
        last, _ = kind.step(params, "L6", stream[:, -1:], jnp.int32(39), {},
                            False, {"memory": memory})
    assert none == () and kind.scanned("L6", {}, 24, 40) == {}
    close(last, out[:, -1:])


# --------------------------------------------------------------------------
# plain grouped-query attention: its window as a value, its chunk form
# --------------------------------------------------------------------------

@pytest.mark.parametrize("window", [0, 8])
def test_plain_grouped_query_attention_has_a_chunk_form(window):
    """Several positions of one cache a step, over the scan's (B, H_kv,
    S, D) layout, whole and rolling (ROADMAP B-I 5): what `full` gives
    with the reference attention, and the caches `step` leaves."""
    cfg = tfm.TransformerConfig.llama_style(
        vocab=61, d_model=32, n_heads=4, n_kv_heads=2, n_layers=1, d_ff=48,
        max_seq=64, window=window)
    kind = tfm.attention_kind(cfg, 0)
    assert kind == kinds.GroupedQuery(cfg, window=window)
    assert not kind.one_form and not kind.shares
    params = tfm.init_transformer(jax.random.PRNGKey(1), cfg)
    y = jax.random.normal(jax.random.PRNGKey(2), (2, 24, 32))
    pos = jnp.arange(24)
    want, _ = kind.full(params, "L0", y, pos, lambda q, k, v:
                        tfm.attention_reference(q, k, v, causal=True,
                                                window=window))
    caches = kind.empty("L0", 2, 24, jnp.float32)
    assert caches["L0_k"].shape == (2, 2, 8 if window else 24, 8)
    stepped = dict(caches)
    outs = []
    for start in range(0, 24, 6):
        o, caches = kind.chunk(params, "L0", y[:, start:start + 6],
                               start + jnp.arange(6), caches, start)
        outs.append(o)
    close(jnp.concatenate(outs, 1), want)
    for t in range(24):
        _, (stepped, _) = kind.step(params, "L0", y[:, t:t + 1], jnp.int32(t),
                                    stepped, False)
    for name in caches:
        close(caches[name], stepped[name])


def test_cache_attention_reads_slots_by_the_position_they_hold():
    """A rolling buffer's slots are in no order of position, and some
    hold none: the queries see what the positions say."""
    rng = np.random.default_rng(3)
    q = jnp.asarray(rng.normal(size=(1, 3, 2, 2, 8)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(1, 2, 10, 8)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(1, 2, 10, 8)), jnp.float32)
    k_pos = jnp.asarray([6, 7, 8, -1, -2, 1, 2, 3, 4, 5])
    q_pos = jnp.asarray([6, 7, 8])
    got = dec.cache_attention(q, k, v, q_pos, k_pos, window=4, scale=0.5)
    order = np.asarray([5, 6, 7, 8, 9, 0, 1, 2])        # positions 1..8
    s = jnp.einsum("bqhgd,bhnd->bqhgn", q, k[:, :, order]) * 0.5
    pos = np.arange(1, 9)
    seen = (pos[None] <= np.asarray(q_pos)[:, None]) & (
        np.asarray(q_pos)[:, None] - pos[None] < 4)
    p = jax.nn.softmax(jnp.where(seen[None, :, None, None], s, -jnp.inf), -1)
    close(got, jnp.einsum("bqhgn,bhnd->bqhgd", p, v[:, :, order]))
    # `live`: what lies in the slots from there on is weighed by nothing
    whole = dec.cache_attention(q, k, v, q_pos, jnp.arange(10))
    some = dec.cache_attention(q, k.at[:, :, 9:].set(1e4),
                               v.at[:, :, 9:].set(1e4), q_pos,
                               jnp.arange(10), live=jnp.int32(9))
    close(some, whole)


# --------------------------------------------------------------------------
# what is refused, and the benchmark's arithmetic
# --------------------------------------------------------------------------

@pytest.mark.parametrize("change,match", [
    (dict(rope=True), "positions"),
    (dict(positions="alibi"), "positions"),
    (dict(norm="rms"), "hybrid stack is written for"),
    (dict(window=8), "hybrid stack is written for"),
    (dict(remat=True), "hybrid stack is written for"),
    (dict(n_layers=7), "names each of the 7 layers"),
    (dict(n_heads=7, n_kv_heads=7), "differential"),
    (dict(hybrid=dataclasses.replace(CFG.hybrid, kv_from=4)), "kv_from"),
    (dict(hybrid=dataclasses.replace(CFG.hybrid, memory_from=6)),
     "memory_from"),
    (dict(hybrid=dataclasses.replace(CFG.hybrid, kinds=("ssm", "rnn") * 4)),
     "names each"),
])
def test_check_arch_names_the_combinations_that_exist(change, match):
    with pytest.raises(ValueError, match=match):
        tfm._check_arch(dataclasses.replace(CFG, **change))


def test_the_train_steps_and_the_sharded_forward_refuse_the_stack():
    import optax
    from jax.sharding import Mesh
    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1), ("dp", "sp"))
    for make in (lambda: tfm.make_sharded_apply(CFG, mesh),
                 lambda: tfm.make_train_step(CFG, mesh, optax.adam(1e-3))):
        with pytest.raises(ValueError, match="a hybrid stack"):
            make()
    with pytest.raises(ValueError, match="no int8 form"):
        tfm.decode_caches({}, cfg=CFG, p_len=8, total=8, kv_q8=True)
    plain = tfm.TransformerConfig.llama_style(
        vocab=61, d_model=32, n_heads=4, n_kv_heads=2, n_layers=1, d_ff=48,
        positions="none")
    with pytest.raises(ValueError, match="positions"):
        tfm._check_arch(plain)
    # no positional encoding is a scheme of its own: no table is made
    bare = dataclasses.replace(plain, rope=False)
    assert "pos_emb" not in tfm.init_transformer(jax.random.PRNGKey(0), bare)
    assert "pos_emb" in tfm.init_transformer(
        jax.random.PRNGKey(0), dataclasses.replace(bare, positions=None))


def test_the_configuration_holds_the_catalogs_numbers():
    """Every key of the catalog's `config` as published: nothing is
    reduced, which no other configuration of the benchmark can say."""
    cfg = published("configs/phi-4-mini-flash-reasoning.serve.json")
    source = {
        "embd_pdrop": 0, "hidden_act": "silu", "hidden_size": 2560,
        "intermediate_size": 10240, "layer_norm_eps": 1e-05,
        "max_position_embeddings": 262144, "mb_per_layer": 2,
        "model_type": "phi4flash", "num_attention_heads": 40,
        "num_hidden_layers": 32, "num_key_value_heads": 20, "resid_pdrop": 0,
        "sliding_window": 512, "tie_word_embeddings": True,
        "mlp_bias": False, "lm_head_bias": False, "vocab_size": 200064}
    assert {k: cfg[k] for k in source} == source
    assert cfg["reduced"] == {}
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    entry = [c for c in manifest["configs"]
             if c["name"] == "phi-4-mini-flash-reasoning.serve"][0]
    assert entry["reduced"] == [] and entry["source"] == cfg["source"]
    cell = [w for w in manifest["workloads"]
            if w["name"] == "phi4flash-reason-decode-16k-1chip"][0]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        entry["name"], "session-b32-c16384-n32", 1)
    assert sum(w["chips"] == 4 for w in manifest["workloads"]) == 1


def test_the_counts_are_the_issues_arithmetic():
    cfg = published("configs/phi-4-mini-flash-reasoning.serve.json")
    n = wts.n_params(cfg)
    assert n == 3_852_562_944                   # 7.705 GB in bfloat16
    held = wts.cache_bytes(cfg, 32, 16416)
    assert held == {"growing": 2_689_597_440, "rolling": 671_088_640,
                    "state": 103_219_200}
    # a step at position 16384: eight readers of the one cache and eight
    # windows, 22.1 GB; with the weights and the states 30.1 GB
    assert abs(counts_phi4flash.attn_bytes(cfg, 32, 16384, 1) / 1e9
               - 22.15) < 0.01
    assert abs(counts_phi4flash.step_bytes(cfg, 32, 16384, n) / 1e9
               - 30.06) < 0.01
    assert counts_phi4flash.keys_read(cfg, 100) == 8 * 101 + 8 * 101
    assert counts_phi4flash.keys_read(cfg, 16384) == 8 * 16385 + 8 * 512
    # the cross-decoder's layers cost a prompt token nothing in prefill:
    # 6.7 GFLOP of projections and MLPs a token with them, 3.9 without
    proj = counts_phi4flash.token_flops(cfg, 0)
    assert 6.6e9 < proj - 2 * 2560 * 200064 < 6.8e9
    assert (counts_phi4flash.turn_flops(cfg, 32, 16384, 32)
            == 32 * sum(counts_phi4flash.token_flops(cfg, p)
                        for p in range(16384, 16416)))
