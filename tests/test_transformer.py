"""Transformer LM family: the sharded (dp × sp) forms must golden-diff
against the single-device oracle, and the sequence-parallel train step
must actually learn."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from lua_mapreduce_tpu.models import transformer as tfm
from lua_mapreduce_tpu.parallel.mesh import make_mesh


@pytest.fixture(scope="module")
def mesh():
    # 2 dp × 4 sp over the 8 virtual CPU devices
    return make_mesh(dp=2, mp=4, devices=jax.devices("cpu")[:8],
                     axis_names=("dp", "sp"))


@pytest.fixture(scope="module")
def cfg():
    return tfm.TransformerConfig.tiny()


@pytest.fixture(scope="module")
def params(cfg):
    return tfm.init_transformer(jax.random.PRNGKey(0), cfg)


def _tokens(cfg, b=4, l=64, seed=0):
    rng = np.random.RandomState(seed)
    return jnp.asarray(rng.randint(0, cfg.vocab, (b, l)), jnp.int32)


@pytest.mark.parametrize("attn", ["ring", "zigzag", "ulysses"])
def test_sharded_forward_matches_oracle(mesh, cfg, params, attn):
    tokens = _tokens(cfg)
    want = tfm.transformer_apply(params, tokens, cfg=cfg)
    fwd = tfm.make_sharded_apply(cfg, mesh, attn=attn)
    got = fwd(params, tokens)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.heavy
def test_grad_accum_matches_whole_tile(mesh, cfg):
    """make_train_step(grad_accum=2): identical loss/params to the
    un-accumulated step (mean of equal microbatch grads ≡ grad of the
    mean loss), with remat on — the two memory levers must compose."""
    rng = np.random.RandomState(6)
    b, l = 8, 64
    seq = rng.randint(0, cfg.vocab, (b, l + 1))
    tokens = jnp.asarray(seq[:, :-1], jnp.int32)
    targets = jnp.asarray(seq[:, 1:], jnp.int32)
    rcfg = tfm.TransformerConfig(**{**cfg.__dict__, "remat": True})
    params = tfm.init_transformer(jax.random.PRNGKey(8), rcfg)
    opt = optax.sgd(0.1)
    td = tfm.shard_batch(mesh, tokens, targets)

    outs = {}
    for accum in (1, 2):
        step = tfm.make_train_step(rcfg, mesh, opt, attn="ring",
                                   grad_accum=accum)
        p0 = jax.tree.map(jnp.copy, params)
        p, _, loss = step(p0, opt.init(p0), *td)
        outs[accum] = (float(loss), p)
    assert abs(outs[1][0] - outs[2][0]) < 2e-6
    for k in outs[1][1]:
        np.testing.assert_allclose(np.asarray(outs[1][1][k]),
                                   np.asarray(outs[2][1][k]),
                                   rtol=1e-5, atol=1e-6, err_msg=k)


@pytest.mark.heavy
def test_zigzag_step_is_dropin_for_ring(mesh, cfg):
    """attn='zigzag' must be loss- and grad-equivalent to the contiguous
    ring (the permutation is internal; the loss is a token mean)."""
    rng = np.random.RandomState(5)
    b, l = 4, 64
    seq = rng.randint(0, cfg.vocab, (b, l + 1))
    tokens = jnp.asarray(seq[:, :-1], jnp.int32)
    targets = jnp.asarray(seq[:, 1:], jnp.int32)
    params = tfm.init_transformer(jax.random.PRNGKey(3), cfg)
    opt = optax.sgd(0.1)
    tokens_d, targets_d = tfm.shard_batch(mesh, tokens, targets)

    outs = {}
    for attn in ("ring", "zigzag"):
        step = tfm.make_train_step(cfg, mesh, opt, attn=attn)
        # the step donates params/opt_state buffers — give each run its
        # own copies or the second run sees deleted arrays
        p0 = jax.tree.map(jnp.copy, params)
        p, _, loss = step(p0, opt.init(p0), tokens_d, targets_d)
        outs[attn] = (float(loss), p)
    assert abs(outs["ring"][0] - outs["zigzag"][0]) < 2e-5
    for k in outs["ring"][1]:
        np.testing.assert_allclose(np.asarray(outs["ring"][1][k]),
                                   np.asarray(outs["zigzag"][1][k]),
                                   rtol=2e-4, atol=2e-4)


@pytest.mark.heavy
def test_zigzag_pre_permuted_batch_matches_in_step_permutation(mesh, cfg):
    """zigzag_layout=True + shard_batch(schedule='zigzag'): identical
    loss/params to the default path that permutes inside the jitted
    step — the host-side pre-permutation is numerically invisible and
    removes the per-step cross-shard gather (VERDICT r2 item 8 /
    ADVICE r2)."""
    rng = np.random.RandomState(9)
    b, l = 4, 64
    seq = rng.randint(0, cfg.vocab, (b, l + 1))
    tokens = jnp.asarray(seq[:, :-1], jnp.int32)
    targets = jnp.asarray(seq[:, 1:], jnp.int32)
    params = tfm.init_transformer(jax.random.PRNGKey(3), cfg)
    opt = optax.sgd(0.1)

    step_in = tfm.make_train_step(cfg, mesh, opt, attn="zigzag")
    p0 = jax.tree.map(jnp.copy, params)
    p_in, _, loss_in = step_in(p0, opt.init(p0),
                               *tfm.shard_batch(mesh, tokens, targets))

    step_pre = tfm.make_train_step(cfg, mesh, opt, attn="zigzag",
                                   zigzag_layout=True)
    p0 = jax.tree.map(jnp.copy, params)
    p_pre, _, loss_pre = step_pre(
        p0, opt.init(p0),
        *tfm.shard_batch(mesh, tokens, targets, schedule="zigzag"))

    assert abs(float(loss_in) - float(loss_pre)) < 1e-6
    for k in p_in:
        np.testing.assert_allclose(np.asarray(p_in[k]),
                                   np.asarray(p_pre[k]),
                                   rtol=1e-6, atol=1e-7, err_msg=k)
    with pytest.raises(ValueError, match="requires attn"):
        tfm.make_train_step(cfg, mesh, opt, attn="ring",
                            zigzag_layout=True)


@pytest.mark.heavy
def test_train_step_learns_copy_task(mesh, cfg):
    """Sequence-parallel training on a deterministic pattern must reach
    low loss: sequences follow tok[t+1] = (tok[t] + 1) % vocab."""
    rng = np.random.RandomState(1)
    b, l = 8, 64
    start = rng.randint(0, cfg.vocab, (b, 1))
    seq = (start + np.arange(l + 1)) % cfg.vocab
    tokens = jnp.asarray(seq[:, :-1], jnp.int32)
    targets = jnp.asarray(seq[:, 1:], jnp.int32)

    params = tfm.init_transformer(jax.random.PRNGKey(2), cfg)
    opt = optax.adam(3e-3)
    opt_state = opt.init(params)
    step = tfm.make_train_step(cfg, mesh, opt, attn="ring")
    tokens_d, targets_d = tfm.shard_batch(mesh, tokens, targets)

    losses = []
    for _ in range(60):
        params, opt_state, loss = step(params, opt_state, tokens_d,
                                       targets_d)
        losses.append(float(loss))
    assert losses[-1] < 0.5, losses[::10]
    assert losses[-1] < losses[0] / 4


def test_grads_cover_every_param(mesh, cfg):
    """The fused pmean backward must deliver a gradient for every
    parameter name (the grad-shuffle key-space invariant)."""
    tokens = _tokens(cfg, seed=3)
    targets = _tokens(cfg, seed=4)
    # the step donates its param buffers — snapshot to host first
    params = tfm.init_transformer(jax.random.PRNGKey(5), cfg)
    before = {k: np.asarray(v).copy() for k, v in params.items()}
    opt = optax.sgd(0.1)
    step = tfm.make_train_step(cfg, mesh, opt, attn="ulysses")
    new_params, _, loss = step(params, opt.init(params),
                               *tfm.shard_batch(mesh, tokens, targets))
    assert np.isfinite(float(loss))
    moved = [k for k in before
             if not np.allclose(before[k], np.asarray(new_params[k]))]
    assert set(moved) == set(before), set(before) - set(moved)


def test_seq_exceeding_max_seq_raises(mesh, cfg, params):
    long_tokens = jnp.zeros((2, cfg.max_seq + 4), jnp.int32)
    with pytest.raises(ValueError, match="max_seq"):
        tfm.transformer_apply(params, long_tokens, cfg=cfg)
    fwd = tfm.make_sharded_apply(cfg, mesh, attn="ring")
    with pytest.raises(ValueError, match="max_seq"):
        fwd(params, jnp.zeros((2, cfg.max_seq + 8), jnp.int32))


def test_unknown_attn_rejected_at_factory_time(mesh, cfg):
    with pytest.raises(ValueError, match="unknown attn"):
        tfm.make_train_step(cfg, mesh, optax.sgd(0.1), attn="rign")
    with pytest.raises(ValueError, match="unknown attn"):
        tfm.make_sharded_apply(cfg, mesh, attn="flash")


class Test3D:
    """dp x sp x mp (tensor-parallel) form vs the 2-D and oracle paths."""

    @pytest.fixture(scope="class")
    def mesh3(self):
        return jax.sharding.Mesh(
            np.array(jax.devices("cpu")[:8]).reshape(2, 2, 2),
            ("dp", "sp", "mp"))

    @pytest.mark.heavy
    def test_one_step_matches_2d_path(self, mesh3, cfg):
        """Same data, same init: one SGD step through the 3-D tp form
        must produce the same params as the 2-D (dp, sp) form."""
        rng = np.random.RandomState(0)
        b, l = 4, 32
        seq = rng.randint(0, cfg.vocab, (b, l + 1))
        tokens = jnp.asarray(seq[:, :-1], jnp.int32)
        targets = jnp.asarray(seq[:, 1:], jnp.int32)

        mesh2 = make_mesh(dp=4, mp=2, devices=jax.devices("cpu")[:8],
                          axis_names=("dp", "sp"))
        opt = optax.sgd(0.1)
        params0 = tfm.init_transformer(jax.random.PRNGKey(7), cfg)

        step2 = tfm.make_train_step(cfg, mesh2, opt, attn="ring")
        p2 = jax.tree.map(lambda x: jnp.array(x, copy=True), params0)
        p2, _, loss2 = step2(p2, opt.init(p2),
                             *tfm.shard_batch(mesh2, tokens, targets))

        step3 = tfm.make_train_step_3d(cfg, mesh3, opt, attn="ring")
        p3 = tfm.shard_params_3d(params0, mesh3, cfg)
        p3, _, loss3 = step3(p3, opt.init(p3),
                             *tfm.shard_batch(mesh3, tokens, targets))
        p3 = tfm.unshard_params_3d(p3, cfg)

        np.testing.assert_allclose(float(loss3), float(loss2), rtol=1e-5)
        for k in p2:
            np.testing.assert_allclose(
                np.asarray(p3[k]), np.asarray(p2[k]), rtol=2e-4,
                atol=2e-4, err_msg=k)

    @pytest.mark.heavy
    def test_3d_zigzag_matches_3d_ring(self, mesh3, cfg):
        """attn='zigzag' on the 3-D mesh: loss/params equivalent to the
        contiguous 3-D ring (internal permutation, token-mean loss)."""
        rng = np.random.RandomState(4)
        b, l = 4, 32
        seq = rng.randint(0, cfg.vocab, (b, l + 1))
        tokens = jnp.asarray(seq[:, :-1], jnp.int32)
        targets = jnp.asarray(seq[:, 1:], jnp.int32)
        opt = optax.sgd(0.1)
        params0 = tfm.init_transformer(jax.random.PRNGKey(9), cfg)

        outs = {}
        for attn in ("ring", "zigzag"):
            step = tfm.make_train_step_3d(cfg, mesh3, opt, attn=attn)
            p = tfm.shard_params_3d(
                jax.tree.map(jnp.copy, params0), mesh3, cfg)
            p, _, loss = step(p, opt.init(p),
                              *tfm.shard_batch(mesh3, tokens, targets))
            outs[attn] = (float(loss), tfm.unshard_params_3d(p, cfg))
        assert abs(outs["ring"][0] - outs["zigzag"][0]) < 2e-5
        for k in outs["ring"][1]:
            np.testing.assert_allclose(
                np.asarray(outs["ring"][1][k]),
                np.asarray(outs["zigzag"][1][k]),
                rtol=2e-4, atol=2e-4, err_msg=k)

    @pytest.mark.heavy
    def test_3d_grad_accum_matches_whole_tile(self, mesh3, cfg):
        rng = np.random.RandomState(11)
        b, l = 4, 32
        seq = rng.randint(0, cfg.vocab, (b, l + 1))
        tokens = jnp.asarray(seq[:, :-1], jnp.int32)
        targets = jnp.asarray(seq[:, 1:], jnp.int32)
        opt = optax.sgd(0.1)
        params0 = tfm.init_transformer(jax.random.PRNGKey(12), cfg)

        outs = {}
        for accum in (1, 2):
            step = tfm.make_train_step_3d(cfg, mesh3, opt, attn="ring",
                                          grad_accum=accum)
            p = tfm.shard_params_3d(
                jax.tree.map(jnp.copy, params0), mesh3, cfg)
            p, _, loss = step(p, opt.init(p),
                              *tfm.shard_batch(mesh3, tokens, targets))
            outs[accum] = (float(loss), tfm.unshard_params_3d(p, cfg))
        assert abs(outs[1][0] - outs[2][0]) < 2e-6
        for k in outs[1][1]:
            np.testing.assert_allclose(
                np.asarray(outs[1][1][k]), np.asarray(outs[2][1][k]),
                rtol=1e-5, atol=1e-6, err_msg=k)

    @pytest.mark.heavy
    def test_3d_training_learns(self, mesh3, cfg):
        rng = np.random.RandomState(1)
        b, l = 8, 32
        start = rng.randint(0, cfg.vocab, (b, 1))
        seq = (start + np.arange(l + 1)) % cfg.vocab
        tokens = jnp.asarray(seq[:, :-1], jnp.int32)
        targets = jnp.asarray(seq[:, 1:], jnp.int32)
        opt = optax.adam(3e-3)
        params = tfm.shard_params_3d(
            tfm.init_transformer(jax.random.PRNGKey(2), cfg), mesh3, cfg)
        step = tfm.make_train_step_3d(cfg, mesh3, opt, attn="ring")
        st = opt.init(params)
        td = tfm.shard_batch(mesh3, tokens, targets)
        first = None
        for _ in range(50):
            params, st, loss = step(params, st, *td)
            if first is None:
                first = float(loss)
        assert float(loss) < first / 3, (first, float(loss))

    def test_rejects_indivisible_heads(self, mesh3):
        bad = tfm.TransformerConfig(vocab=32, d_model=32, n_heads=3,
                                    n_layers=1, d_ff=32, max_seq=64)
        with pytest.raises(ValueError, match="not divisible"):
            tfm.make_train_step_3d(bad, mesh3, optax.sgd(0.1))


class TestMoE:
    """Expert-parallel transformer: switch-MoE FFN with experts over dp."""

    @pytest.fixture(scope="class")
    def moe_cfg(self):
        return tfm.TransformerConfig(
            vocab=64, d_model=32, n_heads=4, n_layers=2, d_ff=64,
            max_seq=128, moe_experts=8, moe_capacity=256)

    def test_sharded_forward_matches_oracle(self):
        """Generous capacity (no drops) → routing is per-token, so the
        ep-sharded forward equals the single-device oracle exactly.
        Default-suite shape (ADVICE r5): shrunk from the class cfg so
        this end-to-end MoE golden diff runs on every `pytest tests/`,
        not only under --full."""
        cfg = tfm.TransformerConfig(
            vocab=32, d_model=16, n_heads=2, n_layers=1, d_ff=32,
            max_seq=64, moe_experts=4, moe_capacity=128)  # = b*l: no drops
        mesh2 = make_mesh(dp=4, mp=2, devices=jax.devices("cpu")[:8],
                          axis_names=("dp", "sp"))
        params = tfm.init_transformer(jax.random.PRNGKey(0), cfg)
        tokens = _tokens(cfg, b=4, l=32)    # b divisible by dp=4
        want = tfm.transformer_apply(params, tokens, cfg=cfg)
        fwd = tfm.make_sharded_apply(cfg, mesh2, attn="ring")
        got = fwd(tfm.shard_params_moe(params, mesh2), tokens)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=3e-4, atol=3e-4)

    @pytest.mark.heavy
    def test_moe_training_learns(self, moe_cfg):
        mesh2 = make_mesh(dp=4, mp=2, devices=jax.devices("cpu")[:8],
                          axis_names=("dp", "sp"))
        rng = np.random.RandomState(1)
        b, l = 8, 64
        start = rng.randint(0, moe_cfg.vocab, (b, 1))
        seq = (start + np.arange(l + 1)) % moe_cfg.vocab
        tokens = jnp.asarray(seq[:, :-1], jnp.int32)
        targets = jnp.asarray(seq[:, 1:], jnp.int32)
        opt = optax.adam(3e-3)
        params = tfm.shard_params_moe(
            tfm.init_transformer(jax.random.PRNGKey(2), moe_cfg), mesh2)
        step = tfm.make_train_step(moe_cfg, mesh2, opt, attn="ring")
        st = opt.init(params)
        td = tfm.shard_batch(mesh2, tokens, targets)
        first = None
        for _ in range(60):
            params, st, loss = step(params, st, *td)
            if first is None:
                first = float(loss)
        assert float(loss) < first / 3, (first, float(loss))

    def test_rejects_indivisible_experts(self, moe_cfg):
        mesh2 = make_mesh(dp=8, mp=1, devices=jax.devices("cpu")[:8],
                          axis_names=("dp", "sp"))
        bad = tfm.TransformerConfig(vocab=32, d_model=32, n_heads=4,
                                    n_layers=1, d_ff=32, max_seq=64,
                                    moe_experts=6, moe_capacity=16)
        with pytest.raises(ValueError, match="not divisible"):
            tfm.make_train_step(bad, mesh2, optax.sgd(0.1))

    def test_capacity_required_with_experts(self):
        nocap = tfm.TransformerConfig(vocab=32, d_model=32, n_heads=4,
                                      n_layers=1, d_ff=32, max_seq=64,
                                      moe_experts=4)
        with pytest.raises(ValueError, match="moe_capacity"):
            tfm.init_transformer(jax.random.PRNGKey(0), nocap)

    def test_moe_rejected_on_3d_path(self, moe_cfg):
        mesh3 = jax.sharding.Mesh(
            np.array(jax.devices("cpu")[:8]).reshape(2, 2, 2),
            ("dp", "sp", "mp"))
        with pytest.raises(ValueError, match="not supported"):
            tfm.make_train_step_3d(moe_cfg, mesh3, optax.sgd(0.1))


class TestPipeline:
    """Pipeline-parallel (GPipe) form: stages over pp, AD-transposed
    backward schedule."""

    @pytest.fixture(scope="class")
    def pp_cfg(self):
        return tfm.TransformerConfig(vocab=64, d_model=32, n_heads=4,
                                     n_layers=4, d_ff=64, max_seq=128)

    @pytest.fixture(scope="class")
    def pp_mesh(self):
        return jax.sharding.Mesh(
            np.array(jax.devices("cpu")[:4]), ("pp",))

    @pytest.mark.heavy
    def test_one_step_matches_single_device(self, pp_cfg, pp_mesh):
        """One SGD step through the 4-stage pipeline == the same step on
        one device (same data, same init) — forward AND backward."""
        rng = np.random.RandomState(0)
        b, l = 8, 32
        seq = rng.randint(0, pp_cfg.vocab, (b, l + 1))
        tokens = jnp.asarray(seq[:, :-1], jnp.int32)
        targets = jnp.asarray(seq[:, 1:], jnp.int32)
        params0 = tfm.init_transformer(jax.random.PRNGKey(3), pp_cfg)
        opt = optax.sgd(0.1)

        # single-device oracle step
        def loss_fn(p):
            logp = jax.nn.log_softmax(
                tfm.transformer_apply(p, tokens, cfg=pp_cfg), axis=-1)
            return -jnp.mean(jnp.take_along_axis(
                logp, targets[..., None], axis=-1))

        l_ref, g_ref = jax.value_and_grad(loss_fn)(params0)
        up, _ = opt.update(g_ref, opt.init(params0))
        p_ref = optax.apply_updates(params0, up)

        step = tfm.make_train_step_pp(pp_cfg, pp_mesh, opt, n_micro=4)
        pp = tfm.shard_params_pp(params0, pp_mesh, pp_cfg)
        pp, _, l_pp = step(pp, opt.init(pp), tokens, targets)
        got = tfm.unstack_params_pp(pp, pp_cfg)

        np.testing.assert_allclose(float(l_pp), float(l_ref), rtol=1e-5)
        for k in p_ref:
            np.testing.assert_allclose(np.asarray(got[k]),
                                       np.asarray(p_ref[k]), rtol=2e-4,
                                       atol=2e-4, err_msg=k)

    @pytest.mark.heavy
    def test_pipeline_training_learns(self, pp_cfg, pp_mesh):
        rng = np.random.RandomState(1)
        b, l = 8, 32
        start = rng.randint(0, pp_cfg.vocab, (b, 1))
        seq = (start + np.arange(l + 1)) % pp_cfg.vocab
        tokens = jnp.asarray(seq[:, :-1], jnp.int32)
        targets = jnp.asarray(seq[:, 1:], jnp.int32)
        opt = optax.adam(3e-3)
        params = tfm.shard_params_pp(
            tfm.init_transformer(jax.random.PRNGKey(4), pp_cfg),
            pp_mesh, pp_cfg)
        step = tfm.make_train_step_pp(pp_cfg, pp_mesh, opt, n_micro=4)
        st = opt.init(params)
        first = None
        for _ in range(50):
            params, st, loss = step(params, st, tokens, targets)
            if first is None:
                first = float(loss)
        assert float(loss) < first / 3, (first, float(loss))

    def test_validations(self, pp_cfg, pp_mesh):
        bad = tfm.TransformerConfig(vocab=32, d_model=32, n_heads=4,
                                    n_layers=3, d_ff=32, max_seq=64)
        with pytest.raises(ValueError, match="not divisible"):
            tfm.make_train_step_pp(bad, pp_mesh, optax.sgd(0.1),
                                   n_micro=2)
        moe = tfm.TransformerConfig(vocab=32, d_model=32, n_heads=4,
                                    n_layers=4, d_ff=32, max_seq=64,
                                    moe_experts=4, moe_capacity=8)
        with pytest.raises(ValueError, match="dense blocks only"):
            tfm.make_train_step_pp(moe, pp_mesh, optax.sgd(0.1),
                                   n_micro=2)


def test_remat_matches_non_remat_grads():
    """cfg.remat recomputes blocks in backward — loss and grads must be
    IDENTICAL to the saved-activation path (same math, less memory).
    Default-suite shape (ADVICE r5): shortened sequence — the oracle
    property is shape-independent, so this golden diff stays in every
    `pytest tests/` run."""
    import dataclasses

    cfg = tfm.TransformerConfig.tiny()
    cfg_r = dataclasses.replace(cfg, remat=True)
    params = tfm.init_transformer(jax.random.PRNGKey(0), cfg)
    rng = np.random.RandomState(0)
    seq = rng.randint(0, cfg.vocab, (2, 9))
    tok = jnp.asarray(seq[:, :-1], jnp.int32)
    tgt = jnp.asarray(seq[:, 1:], jnp.int32)

    import functools

    def loss(c):
        attn = functools.partial(tfm.attention_reference, causal=True)
        pos = jnp.arange(tok.shape[1])

        def f(p):
            return tfm.lm_loss_local(p, tok, tgt, c, attn, pos)
        return jax.value_and_grad(f)(params)

    l0, g0 = loss(cfg)
    l1, g1 = loss(cfg_r)
    assert np.allclose(float(l0), float(l1), rtol=1e-6)
    for k in g0:
        np.testing.assert_allclose(np.asarray(g0[k]), np.asarray(g1[k]),
                                   rtol=2e-5, atol=1e-6, err_msg=k)


@pytest.mark.heavy
def test_remat_composes_with_sequence_parallel(mesh):
    """remat under the sharded sp form: one train step runs and matches
    the non-remat step's loss (collectives re-executed in backward)."""
    import dataclasses

    cfg = tfm.TransformerConfig(vocab=32, d_model=16, n_heads=2,
                                n_layers=2, d_ff=32, max_seq=64)
    rng = np.random.RandomState(1)
    seq = rng.randint(0, cfg.vocab, (4, 17))
    tok = jnp.asarray(seq[:, :-1], jnp.int32)
    tgt = jnp.asarray(seq[:, 1:], jnp.int32)
    opt = optax.sgd(0.05)

    losses = {}
    for name, c in (("plain", cfg),
                    ("remat", dataclasses.replace(cfg, remat=True))):
        # fresh params per variant: the step donates its param buffers
        params = tfm.init_transformer(jax.random.PRNGKey(0), cfg)
        step = tfm.make_train_step(c, mesh, opt, attn="ring")
        _, _, loss = step(params, opt.init(params),
                          *tfm.shard_batch(mesh, tok, tgt))
        losses[name] = float(loss)
    assert np.allclose(losses["plain"], losses["remat"], rtol=1e-6)


def test_flops_per_token_accounting():
    """MFU numerator sanity: hand-counted matmul FLOPs for a small cfg."""
    from lua_mapreduce_tpu.models.transformer import (TransformerConfig,
                                                      flops_per_token)
    cfg = TransformerConfig(vocab=64, d_model=32, n_heads=4, n_layers=2,
                            d_ff=128, max_seq=64)
    d, dff, L = 32, 128, 16
    fwd = 2 * (8 * d * d + 4 * L * d * 0.5 + 4 * d * dff) + 2 * d * 64
    assert flops_per_token(cfg, L) == 3.0 * fwd
    # non-causal doubles only the attention term
    delta = flops_per_token(cfg, L, causal=False) - flops_per_token(cfg, L)
    assert delta == 3.0 * 2 * (2.0 * L * d)


class TestGreedyDecode:
    """KV-cached decode vs the no-cache oracle: identical tokens."""

    def test_matches_full_forward_rerun(self, cfg):
        # default-suite shape (ADVICE r5): fewer decode steps — each
        # naive-rerun prefix length is its own XLA compile, so the step
        # count, not the model, is the cost; the KV-cache-vs-oracle
        # golden diff itself is length-independent
        rng = np.random.RandomState(13)
        params = tfm.init_transformer(jax.random.PRNGKey(13), cfg)
        prompt = jnp.asarray(rng.randint(0, cfg.vocab, (2, 5)), jnp.int32)
        n_new = 4
        got = tfm.greedy_decode(params, prompt, n_new, cfg=cfg)
        assert got.shape == (2, 9)
        assert np.array_equal(np.asarray(got[:, :5]), np.asarray(prompt))

        # naive loop: re-run the FULL forward at every prefix
        toks = prompt
        for _ in range(n_new):
            logits = tfm.transformer_apply(params, toks, cfg=cfg)
            nxt = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
            toks = jnp.concatenate([toks, nxt[:, None]], axis=1)
        assert np.array_equal(np.asarray(got), np.asarray(toks))

    @pytest.mark.heavy
    def test_trained_model_continues_pattern(self, mesh, cfg):
        """Train on tok[t+1] = tok[t] + 1 (mod vocab), then decode: the
        continuation must follow the arithmetic pattern."""
        rng = np.random.RandomState(14)
        b, l = 8, 64
        start = rng.randint(0, cfg.vocab, (b, 1))
        seq = (start + np.arange(l + 1)) % cfg.vocab
        tokens = jnp.asarray(seq[:, :-1], jnp.int32)
        targets = jnp.asarray(seq[:, 1:], jnp.int32)
        params = tfm.init_transformer(jax.random.PRNGKey(2), cfg)
        opt = optax.adam(3e-3)
        opt_state = opt.init(params)
        step = tfm.make_train_step(cfg, mesh, opt, attn="ring")
        td = tfm.shard_batch(mesh, tokens, targets)
        for _ in range(60):
            params, opt_state, _ = step(params, opt_state, *td)

        prompt = jnp.asarray((np.arange(8) + 3) % cfg.vocab,
                             jnp.int32)[None, :]
        out = np.asarray(tfm.greedy_decode(params, prompt, 8, cfg=cfg))[0]
        want = (np.arange(16) + 3) % cfg.vocab
        # chance is 1/64 per token; ≥half right after 60 tiny-model
        # steps demonstrates the decode drives a LEARNED continuation
        acc = float(np.mean(out[8:] == want[8:]))
        assert acc >= 0.5, (out.tolist(), want.tolist())

    @pytest.mark.heavy
    def test_sampling(self, cfg):
        params = tfm.init_transformer(jax.random.PRNGKey(20), cfg)
        prompt = jnp.asarray([[1, 2, 3, 4]], jnp.int32)
        k = jax.random.PRNGKey(0)
        a = tfm.greedy_decode(params, prompt, 6, cfg=cfg,
                              temperature=1.0, key=k)
        b = tfm.greedy_decode(params, prompt, 6, cfg=cfg,
                              temperature=1.0, key=k)
        assert np.array_equal(np.asarray(a), np.asarray(b))  # per-key det.
        assert np.all(np.asarray(a) < cfg.vocab)
        c = tfm.greedy_decode(params, prompt, 6, cfg=cfg, temperature=1.0,
                              key=jax.random.PRNGKey(9), top_k=3)
        assert c.shape == (1, 10)
        # near-zero temperature concentrates on the argmax → greedy
        d = tfm.greedy_decode(params, prompt, 6, cfg=cfg,
                              temperature=1e-4, key=k)
        g = tfm.greedy_decode(params, prompt, 6, cfg=cfg)
        assert np.array_equal(np.asarray(d), np.asarray(g))
        with pytest.raises(ValueError, match="PRNG"):
            tfm.greedy_decode(params, prompt, 2, cfg=cfg, temperature=0.5)

    def test_prefill_matches_scan_decode(self, cfg):
        """use_prefill=True (batched prompt ingestion) produces the
        same tokens as the from-scratch position scan — greedy AND
        sampled (shared fold_in(key, t) stream)."""
        rng = np.random.RandomState(21)
        params = tfm.init_transformer(jax.random.PRNGKey(21), cfg)
        prompt = jnp.asarray(rng.randint(0, cfg.vocab, (3, 7)), jnp.int32)
        a = tfm.greedy_decode(params, prompt, 6, cfg=cfg)
        b = tfm.greedy_decode(params, prompt, 6, cfg=cfg,
                              use_prefill=True)
        assert np.array_equal(np.asarray(a), np.asarray(b))
        k = jax.random.PRNGKey(3)
        c = tfm.greedy_decode(params, prompt, 6, cfg=cfg,
                              temperature=0.9, key=k)
        d = tfm.greedy_decode(params, prompt, 6, cfg=cfg,
                              temperature=0.9, key=k, use_prefill=True)
        assert np.array_equal(np.asarray(c), np.asarray(d))
        # n_new edge cases
        assert tfm.greedy_decode(params, prompt, 0, cfg=cfg,
                                 use_prefill=True).shape == (3, 7)
        e = tfm.greedy_decode(params, prompt, 1, cfg=cfg,
                              use_prefill=True)
        assert np.array_equal(np.asarray(e), np.asarray(
            tfm.greedy_decode(params, prompt, 1, cfg=cfg)))

    @pytest.mark.heavy
    def test_prefill_sharded_matches_single_device(self, mesh, cfg):
        """Sequence-parallel prefill (ring + zigzag over the mesh)
        yields the same caches/logits — and therefore tokens — as the
        single-device prefill."""
        rng = np.random.RandomState(22)
        params = tfm.init_transformer(jax.random.PRNGKey(22), cfg)
        # zigzag needs p_len % (2*sp) == 0
        prompt = jnp.asarray(rng.randint(0, cfg.vocab, (2, 16)),
                             jnp.int32)
        want_c, want_l = tfm.prefill(params, prompt, cfg=cfg, total=20)
        for attn in ("ring", "zigzag"):
            got_c, got_l = tfm.prefill(params, prompt, cfg=cfg,
                                       total=20, mesh=mesh, attn=attn)
            np.testing.assert_allclose(np.asarray(got_l),
                                       np.asarray(want_l),
                                       rtol=2e-4, atol=2e-4,
                                       err_msg=attn)
            for name in want_c:
                np.testing.assert_allclose(
                    np.asarray(got_c[name]), np.asarray(want_c[name]),
                    rtol=2e-4, atol=2e-4, err_msg=f"{attn}:{name}")
        out = tfm.greedy_decode(params, prompt, 4, cfg=cfg,
                                use_prefill=True, mesh=mesh, attn="ring")
        ref = tfm.greedy_decode(params, prompt, 4, cfg=cfg)
        assert np.array_equal(np.asarray(out), np.asarray(ref))

    @pytest.mark.heavy
    def test_prefill_moe_sharded_rejected(self, mesh):
        moe_cfg = tfm.TransformerConfig(vocab=16, d_model=16, n_heads=2,
                                        n_layers=1, d_ff=32, max_seq=32,
                                        moe_experts=2, moe_capacity=64)
        params = tfm.init_transformer(jax.random.PRNGKey(0), moe_cfg)
        prompt = jnp.zeros((1, 8), jnp.int32)
        with pytest.raises(ValueError, match="dense"):
            tfm.prefill(params, prompt, cfg=moe_cfg, mesh=mesh)
        # single-device MoE prefill works (whole-prompt routing group)
        caches, logits = tfm.prefill(params, prompt, cfg=moe_cfg)
        assert logits.shape == (1, 16)
        assert caches["L0_k"].shape == (1, 8, 2, 8)
        # explicit total=0 must hit the guard, not silently mean p_len
        with pytest.raises(ValueError, match="shorter than the prompt"):
            tfm.prefill(params, prompt, cfg=moe_cfg, total=0)

    def test_moe_capacity_required(self):
        """A capacity-less MoE config must fail loudly at decode time
        just as it does at init/train time (the decode MoE path itself
        is golden-diffed in tests/test_moe.py)."""
        moe_cfg = tfm.TransformerConfig(vocab=16, d_model=16, n_heads=2,
                                        n_layers=1, d_ff=32, max_seq=32,
                                        moe_experts=2, moe_capacity=0)
        ok_cfg = dataclasses.replace(moe_cfg, moe_capacity=8)
        params = tfm.init_transformer(jax.random.PRNGKey(0), ok_cfg)
        with pytest.raises(ValueError, match="moe_capacity"):
            tfm.greedy_decode(params, jnp.zeros((1, 4), jnp.int32), 2,
                              cfg=moe_cfg)


def test_moe_with_grad_accum_rejected(mesh):
    moe_cfg = tfm.TransformerConfig(vocab=16, d_model=16, n_heads=4,
                                    n_layers=1, d_ff=32, max_seq=64,
                                    moe_experts=2, moe_capacity=8)
    with pytest.raises(ValueError, match="grad_accum"):
        tfm.make_train_step(moe_cfg, mesh, optax.sgd(0.1), grad_accum=2)


def test_empty_prompt_rejected(cfg):
    params = tfm.init_transformer(jax.random.PRNGKey(0), cfg)
    with pytest.raises(ValueError, match="at least one token"):
        tfm.greedy_decode(params, jnp.zeros((1, 0), jnp.int32), 4, cfg=cfg)


# -- the gradients' barrier (make_train_step's replicated path) -------------

BARRIER_CASES = [((1, 1), 1), ((1, 1), 2), ((2, 2), 1), ((2, 2), 2)]


def _barrier_setup(shape, dtype=jnp.float32, rows=4, cfg=None):
    """A llama-style step on a (dp, sp) CPU mesh as the benchmark builds
    it: float32 masters under Adam, state laid out on the mesh."""
    from jax.sharding import Mesh

    from lua_mapreduce_tpu.train.precision import with_f32_master
    dp, sp = shape
    cfg = cfg or tfm.TransformerConfig.llama_style(
        vocab=64, d_model=32, n_heads=4, n_kv_heads=2, n_layers=2, d_ff=64,
        max_seq=128, window=16)
    mesh = Mesh(np.array(jax.devices("cpu")[:dp * sp]).reshape(dp, sp),
                ("dp", "sp"))
    opt = with_f32_master(optax.adam(1e-2))
    params = tfm.init_transformer(jax.random.PRNGKey(0), cfg)
    params = tfm.shard_params_moe(
        {k: v.astype(dtype) for k, v in params.items()}, mesh)
    state = tfm.init_opt_state(opt, params, mesh)
    ids = np.random.RandomState(7).randint(0, cfg.vocab, (rows, 33))
    batch = tfm.shard_batch(mesh, ids[:, :-1].astype(np.int32),
                            ids[:, 1:].astype(np.int32))
    return cfg, mesh, opt, params, state, batch


def _eqns_named(jaxpr, name):
    return [(i, e) for i, e in enumerate(jaxpr.eqns)
            if e.primitive.name == name]


@pytest.mark.parametrize("shape,accum", BARRIER_CASES)
def test_every_gradient_leaf_meets_one_barrier(shape, accum):
    """One `optimization_barrier` a gradient leaf, inside the shard_map,
    on what the exchange returns (on a mesh of four, this device's
    quarter of the rows), behind the whole of `lm.loss` (behind the scan
    where microbatches accumulate) and ahead of `lm.opt`; none anywhere
    else."""
    from jax.sharding import PartitionSpec as P

    from lua_mapreduce_tpu.parallel.mesh import rows_spec
    cfg, mesh, opt, params, state, batch = _barrier_setup(shape)
    step = tfm.make_train_step(cfg, mesh, opt, grad_accum=accum)
    (_, program), = _eqns_named(
        jax.make_jaxpr(step)(params, state, *batch).jaxpr, "jit")
    outer = program.params["jaxpr"].jaxpr
    (at, mapped), = _eqns_named(outer, "shard_map")
    assert not _eqns_named(outer, "optimization_barrier")
    opt_at = [i for i, e in enumerate(outer.eqns)
              if "lm.opt" in str(e.source_info.name_stack)]
    assert opt_at and at < min(opt_at)

    body = mapped.params["jaxpr"]
    barriers = _eqns_named(body, "optimization_barrier")
    assert len(barriers) == len(params)
    assert all(len(e.invars) == 1 for _, e in barriers)
    shapes = sorted((e.invars[0].aval.shape, str(e.invars[0].aval.dtype))
                    for _, e in barriers)
    rows = lambda s: s if rows_spec(mesh, s) == P() else (  # noqa: E731
        s[0] // mesh.size, *s[1:])
    assert shapes == sorted((rows(v.shape), str(v.dtype))
                            for v in params.values())
    loss_at = [i for i, e in enumerate(body.eqns)
               if "lm.loss" in str(e.source_info.name_stack)
               or e.primitive.name == "scan"]
    assert max(loss_at) < min(i for i, _ in barriers)
    assert bool(_eqns_named(body, "scan")) == (accum > 1)


def _stamped(tree, axes):
    """The OLD way, kept in this file alone: until PR 29 the package ran
    every gradient leaf through `lax.pmean` over each data axis
    (`utils/jax_compat.stamp_replicated`), an identity on a value that
    is already the same on every device, to tell the vma checker so."""
    from jax import lax

    def stamp(x):
        for a in axes:
            x = lax.pmean(x, a)
        return x

    return jax.tree.map(stamp, tree)


def _stamped_accum(loss_of, p, arrays, accum, axes):
    """`accum_value_and_grad` as it was with that stamp: on the scan's
    initial carry and on every microbatch's loss and gradients."""
    from jax import lax
    micro = tuple(a.reshape(accum, a.shape[0] // accum, *a.shape[1:])
                  for a in arrays)

    def body(carry, mb):
        l, g = _stamped(jax.value_and_grad(loss_of)(p, *mb), axes)
        return (carry[0] + l.astype(jnp.float32), jax.tree.map(
            lambda acc, x: acc + x.astype(jnp.float32), carry[1], g)), None

    l0, g0 = _stamped((jnp.float32(0.0), jax.tree.map(
        lambda x: jnp.zeros_like(x, dtype=jnp.float32), p)), axes)
    (loss_s, g_s), _ = lax.scan(body, (l0.astype(jnp.float32), g0), micro)
    return loss_s / accum, jax.tree.map(
        lambda g, x: (g / accum).astype(x.dtype), g_s, p)


def _by_hand_step(cfg, mesh, opt, accum, stamped=False, scatter=None):
    """`make_train_step` without the barrier: the optimizer applied by
    hand to `value_and_grad`'s result. Returns the gradients too.
    `scatter` (by default what the package does: a dense model on more
    than one device) reduce-scatters the gradients by rows and updates
    each device's rows, else the sum is the transpose of the loss's mean
    and the update replicated. `stamped` builds the replicated step the
    old way (see `_stamped`)."""
    import functools

    from jax import lax, shard_map
    from jax.sharding import PartitionSpec as P

    from lua_mapreduce_tpu.parallel.mesh import rows_layout, rows_spec
    from lua_mapreduce_tpu.train.accum import accum_value_and_grad
    axes, n_sp = ("dp", "sp"), mesh.shape["sp"]
    if scatter is None:
        scatter = not stamped and tfm._splits_rows(
            mesh, bool(cfg.moe_experts))
    attn = tfm._attn_shard_fn("ring", "sp", n_sp, cfg)
    suffix = tfm.param_specs_moe("dp") if cfg.moe_experts else {}
    block = functools.partial(
        tfm._block, moe_axis="dp" if cfg.moe_experts else None)

    def unsharded(k):       # the data axes leaf k is NOT sharded over
        return tuple(a for a in axes
                     if a not in tuple(tfm._spec_for(k, suffix)))

    def exchange(g):
        if rows_spec(mesh, g.shape) == P():
            return lax.psum(g, axes)
        return lax.psum_scatter(g, axes, scatter_dimension=0, tiled=True)

    def shard(p, tok, tgt):
        pos = tfm._shard_pos("ring", "sp", n_sp, tok.shape[1])
        if scatter:
            p = lax.pcast(p, axes, to="varying")

        def loss_of(p, tok, tgt):
            local = tfm.lm_loss_local(p, tok, tgt, cfg, attn, pos,
                                      block=block)
            if scatter:
                return lax.psum(local / mesh.size, axes)
            return lax.pmean(lax.pmean(local, "sp"), "dp")

        if accum == 1:
            loss, grads = jax.value_and_grad(loss_of)(p, tok, tgt)
        elif stamped:
            loss, grads = _stamped_accum(loss_of, p, (tok, tgt), accum,
                                         axes)
        else:
            loss, grads = accum_value_and_grad(
                loss_of, p, (tok, tgt), accum)
        if stamped:
            grads = {k: _stamped(g, unsharded(k))
                     for k, g in grads.items()}
        if scatter:
            grads = {k: exchange(g) for k, g in grads.items()}
        return loss, grads

    @jax.jit
    def by_hand(p, s, tok, tgt):
        specs = {k: tfm._spec_for(k, suffix) for k in p}
        grad_specs = {k: rows_spec(mesh, v.shape)
                      for k, v in p.items()} if scatter else specs
        loss, grads = shard_map(
            shard, mesh=mesh, in_specs=(specs, P(*axes), P(*axes)),
            out_specs=(P(), grad_specs))(p, tok, tgt)
        updates, s = opt.update(grads, s, p)
        p = optax.apply_updates(p, updates)
        if scatter:
            p = lax.with_sharding_constraint(p, rows_layout(mesh, p))
            s = lax.with_sharding_constraint(s, rows_layout(mesh, s))
        return p, s, loss, grads

    return by_hand


def _to_the_types(fn, *args):
    """Run `fn` compiled so that no value keeps more precision than its
    type states. Left to itself the CPU's compiler may skip a rounding
    to bfloat16 where nothing stands between the gradient and the
    float32 update that reads it (a barrier, or the old stamp's
    all-reduce, is such a thing), and two builds of one step then differ
    in a last bit that says nothing about either."""
    return fn.lower(*args).compile(
        compiler_options={"xla_allow_excess_precision": False})(*args)


def _assert_same_bits(got, want, but=lambda path: False):
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(got),
                            jax.tree.leaves(want)):
        assert g.dtype == w.dtype
        if not but(jax.tree_util.keystr(path)):
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


@pytest.mark.parametrize("shape,accum", BARRIER_CASES)
def test_the_barrier_changes_no_bit_of_a_step(shape, accum):
    """Parameters, optimizer state and loss of one step equal those of a
    step whose gradients never met a barrier: the optimizer applied by
    hand to `value_and_grad`'s result. bfloat16 weights, as the cells.

    One case leaves one leaf out since PR 29: on the (1, 1) mesh at
    `grad_accum=1` nothing at all stands between the by-hand step's
    gradients and its update (the old stamp did, and the exchange or
    the scan does in the other cases), and the CPU's compiler adds the
    tied embedding's two gradients, the head's and the lookup's, into
    the float32 update without rounding their sum to bfloat16 first. Its
    master and moments then differ in a last bit; the bfloat16 weight
    itself does not. Compiled to the types they agree as well."""
    cfg, mesh, opt, params, state, batch = _barrier_setup(
        shape, jnp.bfloat16)
    by_hand = _by_hand_step(cfg, mesh, opt, accum)
    assert "optimization_barrier" not in str(
        jax.make_jaxpr(by_hand)(params, state, *batch))
    want = by_hand(params, state, *batch)[:3]
    step = tfm.make_train_step(cfg, mesh, opt, grad_accum=accum)
    fresh = lambda: (  # noqa: E731     (the step donates its first two)
        *jax.tree.map(jnp.copy, (params, state)), *batch)
    got = step(*fresh())
    if (shape, accum) == ((1, 1), 1):
        _assert_same_bits(
            got, want,
            but=lambda path: path.startswith("[1]") and "tok_emb" in path)
        _assert_same_bits(
            _to_the_types(step, *fresh()),
            _to_the_types(by_hand, params, state, *batch)[:3])
    else:
        _assert_same_bits(got, want)
    # and it was a step: finite, and the working weights moved
    assert np.isfinite(float(got[2]))
    assert any(not np.array_equal(np.asarray(v), np.asarray(params[k]))
               for k, v in got[0].items())


# -- no replication stamp (PR 29): the step is the stamped one --------------

def _moe_cfg():
    return tfm.TransformerConfig(
        vocab=64, d_model=32, n_heads=4, n_layers=2, d_ff=64, max_seq=128,
        moe_experts=4, moe_capacity=64)


STAMP_CASES = [
    pytest.param((2, 2), 1, None, id="2x2"),
    pytest.param((4, 1), 1, None, id="4x1"),
    pytest.param((2, 2), 1, _moe_cfg, id="2x2-experts-over-dp"),
    pytest.param((4, 1), 1, _moe_cfg, id="4x1-experts-over-dp"),
    pytest.param((2, 2), 2, None, id="2x2-accum2"),
    pytest.param((4, 1), 2, None, id="4x1-accum2"),
]


def _stamp_setup(shape, accum, make_cfg):
    cfg, mesh, opt, params, state, batch = _barrier_setup(
        shape, jnp.bfloat16, rows=8, cfg=make_cfg and make_cfg())
    old = _by_hand_step(cfg, mesh, opt, accum, stamped=True)
    step = tfm.make_train_step(cfg, mesh, opt, grad_accum=accum)
    return cfg, mesh, opt, old, step, (params, state, *batch)


def _eqns_within(jaxpr):
    for e in jaxpr.eqns:
        yield e
        for v in e.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else [v]):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _eqns_within(sub)


def _sums(fn, *args):
    """Whether `fn`'s one shard_map is checked, and how many sums over
    mesh axes it holds at any depth: {(axes, of an array): count}. A
    `pmean` is traced as a `psum_invariant` and a division."""
    import collections
    (_, program), = _eqns_named(jax.make_jaxpr(fn)(*args).jaxpr, "jit")
    (_, mapped), = _eqns_named(program.params["jaxpr"].jaxpr, "shard_map")
    found = collections.Counter(
        (tuple(e.params["axes"]), e.invars[0].aval.ndim > 0)
        for e in _eqns_within(mapped.params["jaxpr"])
        if e.primitive.name in ("psum", "psum_invariant"))
    return mapped.params["check_vma"], found


def _scatters(fn, *args):
    """{axes: count} of the reduce-scatters in `fn`'s one shard_map."""
    import collections
    (_, program), = _eqns_named(jax.make_jaxpr(fn)(*args).jaxpr, "jit")
    (_, mapped), = _eqns_named(program.params["jaxpr"].jaxpr, "shard_map")
    return collections.Counter(
        tuple(e.params["axis_name"])
        for e in _eqns_within(mapped.params["jaxpr"])
        if e.primitive.name == "reduce_scatter")


@pytest.mark.parametrize("shape,accum,make_cfg", STAMP_CASES)
def test_the_step_traces_checked_and_with_no_mean_of_a_gradient(
        shape, accum, make_cfg):
    """The package's shard_map is traced with the vma check ON. A dense
    model on a mesh sums no gradient whole and means nothing: each leaf
    is reduce-scattered once, over both axes at once, and the loss (this
    device's share of the mean) is summed once. An expert model's holds
    the stamped step's sums less the stamps, `out_specs` as they were:
    one `pmean` a data axis that a leaf is not sharded over, three times
    where microbatches accumulate (the carry's start, every microbatch,
    the result), and there twice on the loss as well."""
    cfg, mesh, opt, old, step, args = _stamp_setup(shape, accum, make_cfg)
    checked, sums = _sums(step, *args)
    assert checked is True
    old_checked, old_sums = _sums(old, *args)
    assert old_checked is True
    if not cfg.moe_experts:
        assert sums == {(("dp", "sp"), False): 1}
        assert _scatters(step, *args) == {("dp", "sp"): len(args[0])}
        return
    assert not _scatters(step, *args)
    suffix = tfm.param_specs_moe("dp") if cfg.moe_experts else {}
    over = lambda a: sum(  # noqa: E731
        a not in tuple(tfm._spec_for(k, suffix)) for k in args[0])
    # four expert leaves a layer are sharded over dp: no stamp there
    experts = 4 * cfg.n_layers * bool(cfg.moe_experts)
    assert over("dp") == len(args[0]) - experts
    times, on_loss = (3, 2) if accum > 1 else (1, 0)
    stamps = {((a,), True): times * over(a) for a in ("dp", "sp")}
    stamps.update({((a,), False): on_loss
                   for a in ("dp", "sp") if on_loss})
    assert dict(old_sums - sums) == stamps
    assert not sums - old_sums


@pytest.mark.parametrize("shape,accum,make_cfg", STAMP_CASES)
def test_the_step_without_the_stamp_is_the_stamped_one(
        shape, accum, make_cfg):
    """One step's gradients, parameters, optimizer state and loss against
    those of the step built the old way, a `pmean` over each data axis on
    every gradient leaf: bit for bit at `grad_accum=1`, the replicated
    step by hand as the new way; and the package's step bit for bit
    against its own form by hand (for a dense model, the reduce-scatter:
    `tests/test_row_split.py` holds it to the one-device step).

    With microbatches the accumulated GRADIENTS are compared, to one
    bfloat16 ulp of the leaf's largest element. The old stamp's
    all-reduce stood between each microbatch's bfloat16 gradient and the
    float32 accumulator; without it the CPU's compiler may add a
    gradient it has not yet rounded (see `_to_the_types`; here the tied
    embedding's, the sum of the head's and the lookup's), so the float32
    sums differ by what that rounding would have taken off, and after
    their own rounding by at most one step at the size of the terms: a
    small element, where the microbatches cancel, by many of ITS ulps.
    Adam divides by the root of a tiny second moment and turns that into
    a visible difference of an updated leaf, which is why the comparison
    stops at the gradients there. Compiled to the types, every bit of
    the whole step agrees."""
    cfg, mesh, opt, old, step, args = _stamp_setup(shape, accum, make_cfg)
    new = _by_hand_step(cfg, mesh, opt, accum, scatter=False)
    want = old(*args)
    got = new(*args)
    if accum == 1:
        _assert_same_bits(got, want)
        _assert_same_bits(step(*jax.tree.map(jnp.copy, args[:2]),
                               *args[2:]),
                          _by_hand_step(cfg, mesh, opt, accum)(*args)[:3])
    else:
        for k, w in want[3].items():
            w = np.asarray(w.astype(jnp.float32))
            g = np.asarray(got[3][k].astype(jnp.float32))
            ulp = 2.0 ** (np.floor(np.log2(np.abs(w).max())) - 7)
            assert np.abs(g - w).max() <= ulp, k
        _assert_same_bits(_to_the_types(new, *args),
                          _to_the_types(old, *args))
    assert any(np.asarray(g).any() for g in want[3].values())
