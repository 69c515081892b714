"""The default train step on a mesh (`make_train_step`): the gradients
reduce-scatter by rows, each device updates its rows of the weights and
of the optimizer state, which live split over the devices
(`parallel.mesh.rows_spec`), and the weights are gathered where the next
step takes them. On a (2, 2) mesh of host devices it must compute what
one device computes, keep weights and state in the layout
`shard_params_moe` and `init_opt_state` gave them, and leave alone what
it does not cover: a leaf of ragged rows, an expert model,
`zero1=True`."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from lua_mapreduce_tpu.models import transformer as tfm
from lua_mapreduce_tpu.parallel import zero1 as z1
from lua_mapreduce_tpu.parallel.mesh import opt_state_layout, rows_spec
from lua_mapreduce_tpu.train.precision import with_f32_master

CFG = dict(vocab=64, d_model=32, n_heads=4, n_kv_heads=2, n_layers=2,
           d_ff=64, max_seq=128, window=16)


def _mesh(shape):
    dp, sp = shape
    return Mesh(np.array(jax.devices("cpu")[:dp * sp]).reshape(dp, sp),
                ("dp", "sp"))


def _setup(shape, opt, cfg=None, dtype=jnp.float32):
    """Params, state and a batch on a (dp, sp) mesh, laid out as the
    launchers lay them out."""
    cfg = cfg or tfm.TransformerConfig.llama_style(**CFG)
    mesh = _mesh(shape)
    params = tfm.shard_params_moe(
        {k: v.astype(dtype) for k, v in tfm.init_transformer(
            jax.random.PRNGKey(0), cfg).items()}, mesh)
    # (float32 weights ARE their masters: a copy, or the step donates
    # one buffer twice)
    state = jax.tree.map(jnp.copy, tfm.init_opt_state(opt, params, mesh))
    ids = np.random.RandomState(7).randint(0, cfg.vocab, (4, 33))
    batch = tfm.shard_batch(mesh, ids[:, :-1].astype(np.int32),
                            ids[:, 1:].astype(np.int32))
    return cfg, mesh, params, state, batch


def _two_steps(shape, opt, cfg=None):
    cfg, mesh, params, state, batch = _setup(shape, opt, cfg)
    step = tfm.make_train_step(cfg, mesh, opt)
    layout = jax.tree.map(lambda x: x.sharding, (params, state))
    losses = []
    for _ in range(2):
        params, state, loss = step(params, state, *batch)
        losses.append(float(loss))
    return params, state, losses, layout, step


# Adam's epsilon at 1e-4: at 1e-8 an element whose gradient is near 0
# moves by lr x its sign, and a sum in another order may flip that sign
OPTIMIZERS = [
    pytest.param(lambda: with_f32_master(optax.adam(1e-2, eps=1e-4)),
                 id="adam-f32-master"),
    pytest.param(lambda: optax.chain(optax.clip_by_global_norm(1.0),
                                     optax.adam(1e-2, eps=1e-4)),
                 id="clip-then-adam"),
]


@pytest.mark.parametrize("make_opt", OPTIMIZERS)
def test_two_steps_on_a_2x2_mesh_are_the_one_device_steps(make_opt):
    """Parameters, optimizer state and losses after two steps of one
    global batch: a (2, 2) mesh against one device, float32, to within
    the order of a sum of four. `clip_by_global_norm` reads across each
    leaf: its norm must be the whole gradient's, not a quarter's."""
    one = _two_steps((1, 1), make_opt())
    four = _two_steps((2, 2), make_opt())
    np.testing.assert_allclose(four[2], one[2], rtol=1e-6)
    for (path, a), b in zip(
            jax.tree_util.tree_leaves_with_path((four[0], four[1])),
            jax.tree.leaves((one[0], one[1]))):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5,
                                   atol=1e-5,
                                   err_msg=jax.tree_util.keystr(path))
    # and it was a step: the weights moved
    assert not np.allclose(np.asarray(four[0]["tok_emb"]), np.asarray(
        _setup((1, 1), make_opt())[2]["tok_emb"]))


def test_the_step_returns_the_state_in_the_layout_it_was_given():
    """The weights `shard_params_moe` placed and the state
    `init_opt_state` made are split by rows, and two steps return them
    in that layout: one compiled program, not two."""
    params, state, _, layout, step = _two_steps(
        (2, 2), with_f32_master(optax.adam(1e-2)))
    assert jax.tree.map(lambda x: x.sharding, (params, state)) == layout
    assert opt_state_layout(state)[3] == 0          # no leaf whole
    assert opt_state_layout(params)[3] == 0
    assert step._cache_size() == 1


@pytest.mark.parametrize("shape,quarter", [((2, 2), True), ((1, 1), False)])
def test_opt_state_layout_reads_what_a_device_holds(shape, quarter):
    opt = with_f32_master(optax.adam(1e-2))
    cfg, mesh, params, state, _ = _setup(shape, opt, dtype=jnp.bfloat16)
    held, total, split, whole = opt_state_layout(state)
    # masters, m and v: three float32 leaves a parameter, and the count
    n = sum(v.size for v in params.values())
    assert total == 12 * n + 4
    assert split + whole == 3 * len(params)
    if quarter:
        assert (held, split, whole) == (3 * n + 4, 3 * len(params), 0)
    else:
        assert (held, split, whole) == (total, 0, 3 * len(params))


def test_the_train_cells_state_is_a_quarter_a_chip_on_the_2x2():
    """At the train cells' widths (Mistral-7B-v0.1, two layers, float32
    masters and Adam's moments over bfloat16 weights), from shapes alone:
    6.81 GB of state, 1.70 a chip on the 2x2, every leaf split."""
    import json
    import os

    from lua_mapreduce_tpu.parallel.mesh import rows_layout
    from perfbench.drivers.train import program_config
    with open(os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "perfbench", "configs",
            "mistral-7b-v0.1.train.json")) as f:
        cfg = program_config(json.load(f))
    params = jax.eval_shape(lambda: {
        k: v.astype(jnp.bfloat16) for k, v in tfm.init_transformer(
            jax.random.PRNGKey(0), cfg).items()})
    state = jax.eval_shape(with_f32_master(optax.adam(3e-4)).init, params)
    for shape, held_gb in (((2, 2), 1.70), ((1, 1), 6.81)):
        placed = jax.tree.map(
            lambda x, at: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=at),
            state, rows_layout(_mesh(shape), state))
        held, total, split, whole = opt_state_layout(placed)
        assert round(total / 1e9, 2) == 6.81
        assert round(held / 1e9, 2) == held_gb
        assert (split, whole) == ((48, 0) if shape == (2, 2) else (0, 48))
    assert len(params) == 16


def test_a_leaf_of_ragged_rows_keeps_its_sum_and_its_state_whole():
    """A vocabulary of 66 rows does not split in four: the embedding's
    gradient is all-reduced and its state kept whole on every device,
    while every other leaf is reduce-scattered; the numbers are still
    one device's."""
    cfg = tfm.TransformerConfig.llama_style(**{**CFG, "vocab": 66})
    opt = with_f32_master(optax.adam(1e-2, eps=1e-4))
    _, mesh, params, state, batch = _setup((2, 2), opt, cfg)
    assert rows_spec(mesh, params["tok_emb"].shape) == P()
    masters = state[0]
    assert masters["tok_emb"].sharding.spec == P()
    assert all(v.sharding.spec == P(("dp", "sp"))
               for k, v in masters.items() if k != "tok_emb")
    step = tfm.make_train_step(cfg, mesh, opt)
    (_, program), = [(i, e) for i, e in enumerate(
        jax.make_jaxpr(step)(params, state, *batch).jaxpr.eqns)
        if e.primitive.name == "jit"]
    body = [e for e in program.params["jaxpr"].jaxpr.eqns
            if e.primitive.name == "shard_map"][0].params["jaxpr"]
    summed = [e.invars[0].aval.shape for e in body.eqns
              if e.primitive.name in ("psum", "psum_invariant")
              and e.invars[0].aval.ndim]
    scattered = [e for e in body.eqns if e.primitive.name == "reduce_scatter"]
    assert summed == [params["tok_emb"].shape]
    assert len(scattered) == len(params) - 1
    one = _two_steps((1, 1), opt, cfg)
    four = _two_steps((2, 2), opt, cfg)
    for a, b in zip(jax.tree.leaves(four[:2]), jax.tree.leaves(one[:2])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5,
                                   atol=1e-5)


def _collectives_in(step, *args) -> set:
    text = step.lower(*args).as_text()
    return {op for op in ("reduce_scatter", "all_gather", "all_reduce")
            if f"stablehlo.{op}" in text}


def test_expert_models_and_zero1_keep_their_own_layouts():
    """An expert model (experts over dp) and `zero1=True` (flat chunks
    over dp) build what they built: no state split by rows, no
    reduce-scatter where the expert model all-reduces, and zero1's own
    exchange."""
    moe = tfm.TransformerConfig(vocab=64, d_model=32, n_heads=4,
                                n_layers=2, d_ff=64, max_seq=128,
                                moe_experts=4, moe_capacity=64)
    opt = optax.adam(1e-2)
    _, mesh, params, state, batch = _setup((2, 2), opt, moe)
    _, _, split, _ = opt_state_layout(state)
    experts = sum(k.endswith(("_moe_w1", "_moe_b1", "_moe_w2", "_moe_b2"))
                  for k in params)
    assert split == 2 * experts           # m and v of the expert leaves
    step = tfm.make_train_step(moe, mesh, opt)
    assert _collectives_in(step, params, state, *batch) == {"all_reduce"}

    cfg, mesh, params, _, batch = _setup((2, 2), opt)
    chunks = z1.init_state(opt, params, mesh, dp_axis="dp")
    assert all(x.sharding.spec == P("dp") for x in jax.tree.leaves(chunks)
               if x.ndim)
    step = tfm.make_train_step(cfg, mesh, opt, zero1=True)
    assert "reduce_scatter" in _collectives_in(step, params, chunks, *batch)
    out = step(params, chunks, *batch)
    assert jax.tree.map(lambda x: x.sharding.spec, out[1]) == \
        jax.tree.map(lambda x: x.sharding.spec, chunks)
