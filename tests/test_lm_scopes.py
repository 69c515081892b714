"""The names the LM program gives its own device work (utils/profiling:
LM_SCOPES, LM_KERNELS, LM_PROGRAMS, LM_HOST_SPANS): they reach the
lowered program, the kernels carry their pinned names, and none of it
changes a number. CPU, tiny widths."""

import contextlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import Mesh

from lua_mapreduce_tpu import ops
from lua_mapreduce_tpu.models import transformer as tfm
from lua_mapreduce_tpu.ops import kda as kda_ops
from lua_mapreduce_tpu.parallel import ring_attention
from lua_mapreduce_tpu.utils import profiling

CFG = tfm.TransformerConfig.llama_style(
    vocab=64, d_model=32, n_heads=4, n_kv_heads=2, n_layers=2, d_ff=64,
    max_seq=128, window=16)
TRAIN_SCOPES = ("lm.loss", "lm.embed", "lm.attn", "lm.ffn", "lm.head",
                "lm.opt")
DECODE_SCOPES = ("lm.prefill", "lm.first_token", "lm.decode", "lm.embed",
                 "lm.attn", "lm.ffn", "lm.head")
# latent attention and the grouped expert layer: inside lm.attn / lm.ffn
LATENT = tfm.LatentAttention(
    q_rank=24, kv_rank=16, nope_dim=8, rope_dim=4, v_dim=8, index_heads=2,
    index_dim=8, index_top_k=6, rope_factor=40.0, rope_original=16,
    mscale_all_dim=1.0)
LATENT_CFG = tfm.TransformerConfig.llama_style(
    vocab=64, d_model=32, n_heads=4, n_layers=2, d_ff=48, max_seq=64,
    norm_eps=1e-6, tied_head=False, latent=LATENT, moe_experts=16,
    moe_router="grouped", moe_groups=4, moe_topk_groups=2, moe_top_k=3,
    moe_scale=2.5, moe_d_ff=16, moe_shared=1, moe_held=(4, 8),
    moe_first_dense=1)
SESSION_SCOPES = {"lm.mla": "lm.attn", "lm.indexer": "lm.attn",
                  "lm.sparse": "lm.attn", "lm.moe.route": "lm.ffn",
                  "lm.moe.experts": "lm.ffn", "lm.moe.shared": "lm.ffn"}
# latent attention that reads its cache whole: no query latent, no
# indexer, the qk-norm
WHOLE_CFG = tfm.TransformerConfig.llama_style(
    vocab=64, d_model=32, n_heads=4, n_layers=2, d_ff=48, max_seq=64,
    norm_eps=1e-6, tied_head=False, latent=tfm.LatentAttention(
        q_rank=0, kv_rank=16, nope_dim=8, rope_dim=4, v_dim=8,
        rope_factor=40.0, rope_original=16, mscale_all_dim=1.0,
        qk_norm=True))
WHOLE_SCOPES = {"lm.mla": "lm.attn", "lm.latent": "lm.attn"}
# a hybrid stack: every mixer under a scope of its own, inside lm.attn
HYBRID_CFG = tfm.TransformerConfig(
    vocab=64, d_model=32, n_heads=8, n_kv_heads=4, n_layers=8, d_ff=48,
    max_seq=64, positions="none", ffn="swiglu",
    hybrid=tfm.HybridStack.sambay(8, 4, ssm_state=4, ssm_rank=3))
HYBRID_SCOPES = {name: "lm.attn" for name in (
    "lm.ssm", "lm.gmu", "lm.swa", "lm.full", "lm.cross")}
# Kimi Delta Attention beside latent layers with no positions: the whole
# mixer under lm.kda, inside lm.attn
KDA_CFG = tfm.TransformerConfig(
    vocab=64, d_model=32, n_heads=4, n_layers=3, d_ff=48, max_seq=64,
    norm="rms", ffn="swiglu", positions="none", tied_head=False,
    latent=tfm.LatentAttention(q_rank=0, kv_rank=16, nope_dim=8,
                               rope_dim=4, v_dim=8),
    linear=tfm.LinearAttention(heads=2, head_dim=8),
    attn_pattern=("kda", "kda", "latent"), moe_experts=16,
    moe_router="grouped", moe_top_k=4, moe_scale=2.446, moe_d_ff=16,
    moe_shared=1, moe_held=(4, 4), moe_first_dense=1)
KDA_SCOPES = {"lm.kda": "lm.attn"}


def train_setup(shape, make=tfm.make_train_step, **kw):
    dp, sp = shape
    mesh = Mesh(np.array(jax.devices()[:dp * sp]).reshape(dp, sp),
                ("dp", "sp"))
    opt = optax.adam(1e-2)
    params = tfm.shard_params_moe(
        tfm.init_transformer(jax.random.PRNGKey(0), CFG), mesh)
    state = tfm.init_opt_state(opt, params, mesh)
    rows = np.arange(4 * 33, dtype=np.int32).reshape(4, 33) % CFG.vocab
    batch = tfm.shard_batch(mesh, rows[:, :-1], rows[:, 1:])
    return make(CFG, mesh, opt, **kw), params, state, batch


def scope_paths(lowered) -> set:
    """Every operation's scope path in the lowered module's locations."""
    return set(re.findall(r'loc\("([^"]*)"', lowered.as_text(debug_info=True)))


@pytest.fixture(scope="module")
def lowered_train():
    out = {}
    for shape in ((1, 1), (2, 2)):
        step, params, state, batch = train_setup(shape)
        out[shape] = step.lower(params, state, *batch)
    return out


@pytest.fixture(scope="module")
def lowered_decode():
    params = tfm.init_transformer(jax.random.PRNGKey(0), CFG)
    prompt = jnp.zeros((2, 8), jnp.int32)
    return tfm.greedy_decode.lower(params, prompt, 4, cfg=CFG,
                                   use_prefill=True)


@pytest.fixture(scope="module")
def lowered_session():
    """The session entry over the caches of a prefill, for the model
    with latent attention and the grouped expert layer."""
    params = tfm.init_transformer(jax.random.PRNGKey(0), LATENT_CFG)
    caches, last = tfm.prefill(params, jnp.zeros((2, 8), jnp.int32),
                               cfg=LATENT_CFG, total=12)
    caches = tfm.decode_caches(caches, cfg=LATENT_CFG, p_len=8, total=12)
    return tfm.decode_from.lower(params, caches, jnp.zeros((2,), jnp.int32),
                                 8, 4, cfg=LATENT_CFG, stats=True)


@pytest.mark.parametrize("shape", [(1, 1), (2, 2)])
@pytest.mark.parametrize("name", TRAIN_SCOPES)
def test_the_train_step_carries_the_scope(lowered_train, shape, name):
    paths = scope_paths(lowered_train[shape])
    assert any(name in p.split("/") or f"({name})" in p for p in paths), name


@pytest.mark.parametrize("shape", [(1, 1), (2, 2)])
def test_backward_is_told_from_forward_by_transpose(lowered_train, shape):
    paths = scope_paths(lowered_train[shape])
    for block in ("lm.attn", "lm.ffn", "lm.head"):
        assert any(f"jvp(lm.loss)/{block}" in p and "transpose(" not in p
                   for p in paths), block
        assert any(f"transpose(jvp(lm.loss))/{block}" in p
                   for p in paths), block
    # the optimizer's work is under neither
    assert not any("lm.opt" in p and "lm.loss" in p for p in paths)


def test_the_ring_exchange_is_scoped_on_the_mesh_only(lowered_train):
    ring = [p for p in scope_paths(lowered_train[2, 2]) if "lm.ring" in p]
    assert ring and all(p.endswith("lm.ring/ppermute") for p in ring)
    assert any("transpose(" in p for p in ring)      # and in the backward
    assert not any("lm.ring" in p for p in scope_paths(lowered_train[1, 1]))


@pytest.mark.parametrize("shape", [(1, 1), (2, 2)])
def test_the_train_program_is_named(lowered_train, shape):
    assert "lm_train_step" in profiling.LM_PROGRAMS
    text = lowered_train[shape].as_text()
    assert re.search(r"module @jit_lm_train_step\b", text)
    assert lowered_train[shape].compile().as_text().startswith(
        "HloModule jit_lm_train_step")


@pytest.mark.parametrize("make,kw", [
    (tfm.make_train_step, {"zero1": True}),
    (tfm.make_train_step_3d, {}),
    (tfm.make_train_step_pp, {"n_micro": 2})])
def test_the_other_builders_name_program_and_optimizer(make, kw):
    """ZeRO-1, 3-D and pipeline forms share the names (no cell runs them
    yet): the program, `lm.loss` and `lm.opt`."""
    cfg = tfm.TransformerConfig.llama_style(
        vocab=64, d_model=32, n_heads=4, n_layers=2, d_ff=64, max_seq=128)
    opt = optax.adam(1e-2)
    params = tfm.init_transformer(jax.random.PRNGKey(0), cfg)
    tok = jnp.zeros((4, 32), jnp.int32)
    if make is tfm.make_train_step_pp:
        mesh = Mesh(np.array(jax.devices()[:2]), ("pp",))
        params = tfm.shard_params_pp(params, mesh, cfg)
        state = opt.init(params)
    elif make is tfm.make_train_step_3d:
        mesh = Mesh(np.array(jax.devices()[:2]).reshape(1, 1, 2),
                    ("dp", "sp", "mp"))
        params = tfm.shard_params_3d(params, mesh, cfg)
        state = opt.init(params)
    else:
        from lua_mapreduce_tpu.parallel import zero1
        mesh = Mesh(np.array(jax.devices()[:2]).reshape(2, 1), ("dp", "sp"))
        state = zero1.init_state(opt, params, mesh)
    lowered = make(cfg, mesh, opt, **kw).lower(params, state, tok, tok)
    assert "module @jit_lm_train_step" in lowered.as_text()
    paths = scope_paths(lowered)
    for name in ("lm.opt", "jvp(lm.loss)", "transpose(jvp(lm.loss))",
                 "lm.attn", "lm.ffn", "lm.head", "lm.embed"):
        assert any(name in p for p in paths), name


@pytest.mark.parametrize("name", DECODE_SCOPES)
def test_the_decode_request_carries_the_scope(lowered_decode, name):
    assert "greedy_decode" in profiling.LM_PROGRAMS
    assert "module @jit_greedy_decode" in lowered_decode.as_text()
    assert any(name in p.split("/") for p in scope_paths(lowered_decode))


def test_decode_blocks_lie_under_their_phase(lowered_decode):
    # the scan's body is a function of its own in the lowered text; the
    # compiled program's `op_name` has the whole path
    paths = set(re.findall(r'op_name="([^"]*)"',
                           lowered_decode.compile().as_text()))
    for block in ("lm.embed", "lm.attn", "lm.ffn", "lm.head"):
        assert any(f"lm.prefill/{block}/" in p for p in paths), block
        assert any(p.startswith("jit(greedy_decode)/lm.decode/while/body/")
                   and f"/{block}/" in p for p in paths), block
    first = [p for p in paths if "lm.first_token" in p]
    assert first and not any("lm.prefill" in p or "lm.decode" in p
                             for p in first)


def nests(lowered, name: str, block: str) -> None:
    """``name`` lies inside ``block``'s scope, inside the scan of
    `lm.decode`, in the compiled session entry."""
    assert "decode_from" in profiling.LM_PROGRAMS
    assert "module @jit_decode_from" in lowered.as_text()
    paths = set(re.findall(r'op_name="([^"]*)"',
                           lowered.compile().as_text()))
    under = [p for p in paths if name in p.split("/")]
    assert under, name
    assert any(p.startswith("jit(decode_from)/lm.decode/while/body/")
               for p in under)
    for p in under:         # a reducer's own computation has a relative path
        parts = p.split("/")
        assert block in parts[:parts.index(name)], p
        assert not p.startswith("jit(") or parts[1:4] == [
            "lm.decode", "while", "body"], p


@pytest.mark.parametrize("name", sorted(SESSION_SCOPES))
def test_the_session_entry_nests_the_layers_scopes(lowered_session, name):
    """`lm.attn/lm.indexer`, `lm.ffn/lm.moe.experts`, ...: inside the
    block's scope, so that a reader of blocks still holds them, inside
    the scan of `lm.decode`."""
    nests(lowered_session, name, SESSION_SCOPES[name])


@pytest.mark.parametrize("name", sorted(WHOLE_SCOPES))
def test_attention_over_the_whole_cache_has_a_scope_of_its_own(
        lowered_whole, name):
    """`lm.attn/lm.latent` beside `lm.attn/lm.mla`; no indexer, so
    neither `lm.indexer` nor `lm.sparse`, and no `selected` counter."""
    nests(lowered_whole, name, WHOLE_SCOPES[name])
    paths = scope_paths(lowered_whole)
    assert not any("lm.indexer" in p or "lm.sparse" in p for p in paths)
    assert "selected" not in lowered_whole.out_info[2]


@pytest.mark.parametrize("name", sorted(HYBRID_SCOPES))
def test_a_hybrid_stacks_mixers_have_scopes_of_their_own(lowered_hybrid, name):
    """`lm.attn/lm.ssm`, `lm.attn/lm.cross`, ...: in the scan of the
    session entry, and in the prefill (chunked: every layer that caches
    something in the scan over the chunks, the cross-decoder after it)."""
    session, prefill = lowered_hybrid
    nests(session, name, HYBRID_SCOPES[name])
    under = [p for p in scope_paths(prefill) if name in p.split("/")]
    assert under and all("lm.attn" in p.split("/") for p in under), name


def test_the_hybrid_steps_attention_is_the_decode_kernels():
    """Window, full and cross attention of a decode step all go through
    `ops/decode.decode_attention`: on the chip `_decode_pallas`, no
    kernel of their own."""
    params = tfm.init_transformer(jax.random.PRNGKey(0), HYBRID_CFG)
    caches, _ = tfm.prefill(params, jnp.zeros((2, 8), jnp.int32),
                            cfg=HYBRID_CFG, total=12)
    caches = tfm.decode_caches(caches, cfg=HYBRID_CFG, p_len=8, total=12)
    real = ops.decode.decode_attention
    seen = []

    def interpreted(q, k, v, t, **kw):
        seen.append((k.shape, kw.get("roll", False)))
        return real(q, k, v, t, **dict(kw, backend="pallas_interpret"))

    import lua_mapreduce_tpu.models.attention_kinds as kinds
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(kinds, "decode_attention", interpreted)
        jaxpr = jax.make_jaxpr(lambda p, c: tfm.decode_from.__wrapped__(
            p, c, jnp.zeros((2,), jnp.int32), 8, 4, cfg=HYBRID_CFG))(
                params, caches)
    assert set(pallas_calls(jaxpr.jaxpr)) == {"_decode_pallas"}
    # two window layers on rolling buffers of 4 slots, the full layer and
    # the cross layer on the one cache of 12
    assert sorted(seen) == [((2, 2, 4, 8), True)] * 2 + [
        ((2, 2, 12, 8), False)] * 2


def test_the_session_entry_runs_no_prefill(lowered_session):
    paths = scope_paths(lowered_session)
    assert not any("lm.prefill" in p or "lm.first_token" in p for p in paths)
    for block in ("lm.embed", "lm.attn", "lm.ffn", "lm.head"):
        assert any(block in p.split("/") for p in paths), block


def test_every_scope_of_the_contract_is_used(lowered_train, lowered_decode,
                                             lowered_session, lowered_whole,
                                             lowered_hybrid, lowered_kda):
    found = (scope_paths(lowered_train[2, 2]) | scope_paths(lowered_decode)
             | scope_paths(lowered_session) | scope_paths(lowered_whole)
             | scope_paths(lowered_hybrid[0]) | scope_paths(lowered_kda[0]))
    for name in profiling.LM_SCOPES:
        assert any(name in p for p in found), name
    assert set(TRAIN_SCOPES + DECODE_SCOPES + ("lm.ring",)
               + tuple(SESSION_SCOPES) + tuple(WHOLE_SCOPES)
               + tuple(HYBRID_SCOPES)
               + tuple(KDA_SCOPES)) == set(profiling.LM_SCOPES)


def test_the_indexed_layers_add_no_kernel():
    """Latent attention over selected rows and the indexer are XLA's,
    and off the chip the expert layer is too: the session entry of that
    model holds no pallas_call here. The sixth pinned name is the kernel
    of latent attention over a whole cache (`ops/mla_decode.py`), the
    seventh the held experts of a decode step as one call on the chip
    (`ops/moe_held.py`), the eighth a KDA decode step
    (`ops/kda.py`), the ninth the put of a decode step's row into a
    whole cache that the sixth reads (`ops/mla_decode.py`)."""
    params = tfm.init_transformer(jax.random.PRNGKey(0), LATENT_CFG)
    caches, _ = tfm.prefill(params, jnp.zeros((2, 8), jnp.int32),
                            cfg=LATENT_CFG, total=12)
    jaxpr = jax.make_jaxpr(lambda p, c: tfm.decode_from.__wrapped__(
        p, c, jnp.zeros((2,), jnp.int32), 8, 4, cfg=LATENT_CFG))(
            params, caches)
    assert pallas_calls(jaxpr.jaxpr) == []
    assert len(profiling.LM_KERNELS) == 9


@pytest.fixture(scope="module")
def lowered_whole():
    """The session entry of the model whose latent attention has no
    indexer."""
    params = tfm.init_transformer(jax.random.PRNGKey(0), WHOLE_CFG)
    caches, _ = tfm.prefill(params, jnp.zeros((2, 8), jnp.int32),
                            cfg=WHOLE_CFG, total=12)
    caches = tfm.decode_caches(caches, cfg=WHOLE_CFG, p_len=8, total=12)
    return tfm.decode_from.lower(params, caches, jnp.zeros((2,), jnp.int32),
                                 8, 4, cfg=WHOLE_CFG, stats=True)


@pytest.fixture(scope="module")
def lowered_hybrid():
    """The hybrid stack's session entry and its chunked prefill."""
    params = tfm.init_transformer(jax.random.PRNGKey(0), HYBRID_CFG)
    ids = jnp.zeros((2, 8), jnp.int32)
    prefill = jax.jit(lambda p, i: tfm.prefill(p, i, cfg=HYBRID_CFG,
                                               total=12, chunk=4))
    caches, _ = prefill(params, ids)
    caches = tfm.decode_caches(caches, cfg=HYBRID_CFG, p_len=8, total=12)
    return (tfm.decode_from.lower(params, caches, jnp.zeros((2,), jnp.int32),
                                  8, 4, cfg=HYBRID_CFG),
            prefill.lower(params, ids))


@pytest.fixture(scope="module")
def lowered_kda():
    """The session entry of a stack with KDA and latent layers, and its
    chunked prefill."""
    params = tfm.init_transformer(jax.random.PRNGKey(0), KDA_CFG)
    ids = jnp.zeros((2, 8), jnp.int32)
    prefill = jax.jit(lambda p, i: tfm.prefill(p, i, cfg=KDA_CFG, total=12,
                                               chunk=4))
    caches, _ = prefill(params, ids)
    caches = tfm.decode_caches(caches, cfg=KDA_CFG, p_len=8, total=12)
    return (tfm.decode_from.lower(params, caches, jnp.zeros((2,), jnp.int32),
                                  8, 4, cfg=KDA_CFG),
            prefill.lower(params, ids))


@pytest.mark.parametrize("name", sorted(KDA_SCOPES))
def test_the_kda_mixer_has_a_scope_of_its_own(lowered_kda, name):
    """`lm.attn/lm.kda`: in the scan of the session entry and in the
    chunked prefill; the latent layer beside it keeps its own."""
    session, prefill = lowered_kda
    nests(session, name, KDA_SCOPES[name])
    nests(session, "lm.mla", "lm.attn")
    under = [p for p in scope_paths(prefill) if name in p.split("/")]
    assert under and all("lm.attn" in p.split("/") for p in under), name


def test_the_kda_step_is_the_kernel_inside_its_scope():
    """A decode step's KDA layers go through `ops/kda.kda_step`: on the
    chip `_kda_step_pallas`, one call a KDA layer, under `lm.kda`."""
    params = tfm.init_transformer(jax.random.PRNGKey(0), KDA_CFG)
    caches, _ = tfm.prefill(params, jnp.zeros((2, 8), jnp.int32),
                            cfg=KDA_CFG, total=12)
    caches = tfm.decode_caches(caches, cfg=KDA_CFG, p_len=8, total=12)
    real = kda_ops.kda_step
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(kda_ops, "kda_step",
                   lambda *a, **kw: real(*a, backend="pallas_interpret"))
        jaxpr = jax.make_jaxpr(lambda p, c: tfm.decode_from.__wrapped__(
            p, c, jnp.zeros((2,), jnp.int32), 8, 4, cfg=KDA_CFG))(
                params, caches)
    assert pallas_calls(jaxpr.jaxpr) == ["_kda_step_pallas"] * 2

    def enclosing(jaxpr, path=""):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                yield path
            for value in eqn.params.values():
                for sub in (value if isinstance(value, (list, tuple))
                            else [value]):
                    sub = getattr(sub, "jaxpr", sub)
                    if hasattr(sub, "eqns"):
                        yield from enclosing(
                            sub, f"{path}/{eqn.source_info.name_stack}")

    assert list(enclosing(jaxpr.jaxpr)) == ["/lm.decode/lm.attn/lm.kda"] * 2


def pallas_calls(jaxpr, out=None) -> list:
    out = [] if out is None else out
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            out.append(eqn.params["name"])
        for value in eqn.params.values():
            for sub in (value if isinstance(value, (list, tuple))
                        else [value]):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    pallas_calls(sub, out)
    return out


def kernel_sites():
    q = jnp.ones((1, 128, 2, 64), jnp.float32)

    def flash(q, k, v):
        return ops.flash_attention(q, k, v, causal=True,
                                   backend="pallas_interpret").sum()

    def decode(q, k, v):
        return ops.decode_attention(q, k, v, jnp.int32(5),
                                    backend="pallas_interpret")

    def q8(x, w):
        return ops.q8_matmul(x, *ops.quantize_q8(w),
                             backend="pallas_interpret")

    def mla(q, rows):
        return ops.mla_decode_attention(q, rows, jnp.int32(5), v_rank=64,
                                        scale=0.1,
                                        backend="pallas_interpret")

    def put(rows, row):
        return ops.mla_decode.latent_row_put(rows, row, jnp.int32(5),
                                             backend="pallas_interpret")

    def held(x, w):
        from lua_mapreduce_tpu.ops.moe_held import moe_held
        return moe_held(x, jnp.ones((8, 2)), jnp.ones((2,), jnp.int32), w, w,
                        w, backend="pallas_interpret")

    def kda_step(s, q, v):
        return kda_ops.kda_step(s, q, q, v, -q, q[..., 0],
                                backend="pallas_interpret")

    cache = jnp.ones((1, 2, 128, 64), jnp.float32)
    return {
        "_kda_step_pallas": (kda_step, (jnp.ones((2, 4, 16, 16)),
                                        jnp.ones((2, 4, 16)),
                                        jnp.ones((2, 4, 16)))),
        "_moe_held_pallas": (held, (jnp.ones((8, 128)),
                                    jnp.ones((2, 128, 128)))),
        "_mla_decode_pallas": (mla, (jnp.ones((1, 4, 96)),
                                     jnp.ones((1, 128, 96)))),
        "_latent_row_put": (put, (jnp.ones((2, 130, 96)),
                                  jnp.ones((2, 1, 96)))),
        "flash_pallas": (flash, (q, q, q)),
        "flash_bwd_pallas_dq": (jax.grad(flash, (0, 1, 2)), (q, q, q)),
        "flash_bwd_pallas_dkv": (jax.grad(flash, (0, 1, 2)), (q, q, q)),
        "_decode_pallas": (decode, (jnp.ones((1, 2, 2, 64)), cache, cache)),
        "q8_matmul_pallas": (q8, (jnp.ones((8, 128)), jnp.ones((128, 128)))),
    }


@pytest.mark.parametrize("name", profiling.LM_KERNELS)
def test_the_kernel_carries_its_pinned_name(name):
    fn, args = kernel_sites()[name]
    names = pallas_calls(jax.make_jaxpr(fn)(*args).jaxpr)
    assert name in names
    assert set(names) <= set(profiling.LM_KERNELS)


def test_a_scope_renames_no_kernel():
    """What `name=` is for: the enclosing scope and the transformation
    change the path, never the kernel's own name."""
    fn, args = kernel_sites()["flash_bwd_pallas_dq"]
    with profiling.scope("lm.attn"):
        names = pallas_calls(jax.make_jaxpr(fn)(*args).jaxpr)
    assert sorted(set(names)) == ["flash_bwd_pallas_dkv",
                                  "flash_bwd_pallas_dq", "flash_pallas"]


@pytest.mark.parametrize("shape", [(1, 1), (2, 2)])
def test_scopes_change_no_number(monkeypatch, shape):
    """The same step built with every scope a no-op returns bit-equal
    loss and parameters."""
    def run():
        step, params, state, batch = train_setup(shape)
        for _ in range(2):
            params, state, loss = step(params, state, *batch)
        return jax.device_get((loss, params))

    loss, params = run()
    for module in (tfm, ring_attention):
        monkeypatch.setattr(module, "scope",
                            lambda name: contextlib.nullcontext())
    step, p0, s0, batch = train_setup(shape)
    assert not any("lm." in p for p in scope_paths(step.lower(p0, s0, *batch)))
    bare_loss, bare_params = run()
    assert np.array_equal(loss, bare_loss)
    for k in params:
        assert np.array_equal(params[k], bare_params[k]), k


def test_scoped_decode_returns_the_same_tokens(monkeypatch):
    params = tfm.init_transformer(jax.random.PRNGKey(1), CFG)
    prompt = jnp.arange(16, dtype=jnp.int32).reshape(2, 8) % CFG.vocab
    scoped = np.asarray(tfm.greedy_decode(params, prompt, 24, cfg=CFG,
                                          use_prefill=True))
    stepped = np.asarray(tfm.greedy_decode(params, prompt, 24, cfg=CFG))
    assert np.array_equal(scoped, stepped)       # rolling window: 32 > 16
    monkeypatch.setattr(tfm, "scope", lambda name: contextlib.nullcontext())
    bare = tfm.greedy_decode.__wrapped__(params, prompt, 24, cfg=CFG,
                                         use_prefill=True)
    assert np.array_equal(scoped, np.asarray(bare))


def host_events(trace_dir) -> list:
    import glob
    import os

    from jax.profiler import ProfileData
    (path,) = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                     "*.xplane.pb"))
    return [ev.name for plane in ProfileData.from_file(path).planes
            if plane.name == "/host:CPU"
            for line in plane.lines for ev in line.events]


def test_shard_batch_writes_its_host_span(tmp_path):
    """`lm.shard_batch` lands on the profiler's host plane through
    `device_trace`, which opens the profiler without Python call stacks
    (as the benchmark does), beside JAX's own row of the program."""
    step, params, state, batch = train_setup((1, 1))
    params, state, _ = step(params, state, *batch)           # compiled
    mesh = batch[0].sharding.mesh
    rows = np.zeros((4, 32), np.int32)
    with profiling.device_trace(str(tmp_path)):
        batch = tfm.shard_batch(mesh, rows, rows)
        jax.block_until_ready(step(params, state, *batch))
    names = host_events(str(tmp_path))
    assert names.count("lm.shard_batch") == 1
    assert profiling.LM_HOST_SPANS == ("lm.shard_batch",)
    assert any(n.startswith("PjitFunction(lm_train_step)") for n in names)
    # python_tracer_level=0: no Python frames in the trace
    assert not any(n.startswith("$") for n in names)
