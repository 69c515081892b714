"""Mixture-of-experts layer: expert-parallel all_to_all routing vs the
single-device oracle, capacity semantics, and trainability."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax import shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

from lua_mapreduce_tpu.parallel import moe
from lua_mapreduce_tpu.parallel.mesh import make_mesh

D, FF, E, CAP = 16, 32, 8, 4


@pytest.fixture(scope="module")
def mesh():
    return make_mesh(dp=8, mp=1, devices=jax.devices("cpu")[:8],
                     axis_names=("ep", "unused"))


@pytest.fixture(scope="module")
def params():
    return moe.init_moe(jax.random.PRNGKey(0), D, FF, E)


def _tokens(seed, t=32):
    return jnp.asarray(np.random.RandomState(seed).randn(t, D),
                       jnp.float32)


def test_reference_routes_and_combines(params):
    x = _tokens(0)
    out, aux = moe.moe_ffn_reference(params, x, capacity=CAP)
    assert out.shape == x.shape
    assert np.isfinite(np.asarray(out)).all()
    assert 0.5 < float(aux) < float(E)      # balanced-ish random router


def test_capacity_drops_overflow_tokens(params):
    """Force every token to one expert: only the first CAP tokens get
    output; the rest are dropped (zero contribution)."""
    p = dict(params)
    bias = jnp.zeros((D, E)).at[:, 3].set(100.0)
    p["moe_router_W"] = bias
    # positive tokens → positive feature sum → every token scores
    # expert 3 highest (a linear router has no bias term)
    x = jnp.abs(_tokens(1, t=16))
    out, _ = moe.moe_ffn_reference(p, x, capacity=CAP)
    norms = np.linalg.norm(np.asarray(out), axis=-1)
    assert (norms[:CAP] > 1e-6).all()
    np.testing.assert_allclose(norms[CAP:], 0.0, atol=1e-6)


def test_shard_matches_per_tile_reference(mesh, params):
    """ep-sharded MoE ≡ the oracle applied per device tile (same
    per-tile capacity semantics)."""
    n_ep = 8
    t_local = 16
    x = _tokens(2, t=n_ep * t_local)            # (128, D), tile = 16

    want = jnp.concatenate([
        moe.moe_ffn_reference(params, x[i * t_local:(i + 1) * t_local],
                              capacity=CAP)[0]
        for i in range(n_ep)])

    def body(params, x):
        out, aux = moe.moe_ffn_shard(params, x, capacity=CAP,
                                     ep_axis="ep")
        return out, aux

    specs = {k: (P("ep") if k.startswith("moe_w") or
                 k.startswith("moe_b") else P())
             for k in params}
    sharded = {k: jax.device_put(v, NamedSharding(mesh, specs[k]))
               for k, v in params.items()}
    fn = jax.jit(shard_map(
        body, mesh=mesh, in_specs=(specs, P("ep")),
        out_specs=(P("ep"), P())))
    got, aux = fn(sharded, jax.device_put(
        x, NamedSharding(mesh, P("ep"))))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    assert np.isfinite(float(aux))


def _expert_specs(params):
    return {k: (P("ep") if k.startswith("moe_w") or
                k.startswith("moe_b") else P())
            for k in params}


def _grad_fn(mesh, specs, capacity, stamped=False):
    """Loss and gradients of a small ep-sharded regression. Replicated
    leaves' gradients (the router's) are psum'd across ep by the
    transpose machinery and typed as unvarying there; the experts' stay
    varying over ep, as `specs` says: the vma check passes as it is.
    `stamped` is the OLD way, kept in this file alone: until PR 29 every
    replicated leaf's gradient went through one more `pmean` over ep, an
    identity, to tell the checker so."""
    def body(params, x, y):
        out, aux = moe.moe_ffn_shard(params, x, capacity=capacity,
                                     ep_axis="ep")
        mse = jnp.mean((out - y) ** 2)
        return jax.lax.pmean(mse, "ep") + 0.01 * aux

    def vag(p, x, y):
        l, g = jax.value_and_grad(lambda p: body(p, x, y))(p)
        if stamped:
            g = {k: v if "ep" in tuple(specs[k])
                 else jax.lax.pmean(v, "ep") for k, v in g.items()}
        return l, g

    return jax.jit(shard_map(
        vag, mesh=mesh, in_specs=(specs, P("ep"), P("ep")),
        out_specs=(P(), specs)))


def _regression(mesh, specs, params):
    rng = np.random.RandomState(3)
    x = jnp.asarray(rng.randn(128, D), jnp.float32)
    y = jnp.asarray(np.sin(2 * np.asarray(x)), jnp.float32)
    sharded = {k: jax.device_put(v, NamedSharding(mesh, specs[k]))
               for k, v in params.items()}
    return (x, sharded, jax.device_put(x, NamedSharding(mesh, P("ep"))),
            jax.device_put(y, NamedSharding(mesh, P("ep"))))


@pytest.mark.parametrize("capacity", [4, 32])
def test_unstamped_gradients_are_the_stamped_ones(mesh, capacity):
    """With experts over the axis the batch is split on, the loss and the
    experts' gradients equal, bit for bit, those of the old stamped
    function (tokens dropped at capacity 4, none at 32). The router's,
    the one stamped leaf, agrees to the float32 ulps that the stamp
    itself cost: its mean over eight devices is a running sum of eight
    equal values (3x, 5x, 6x and 7x need not be float32 numbers) and a
    division by 8, and so not quite the identity it was held to be."""
    params = moe.init_moe(jax.random.PRNGKey(1), D, FF, E)
    specs = _expert_specs(params)
    _, sharded, xd, yd = _regression(mesh, specs, params)
    want = _grad_fn(mesh, specs, capacity, stamped=True)(sharded, xd, yd)
    got = _grad_fn(mesh, specs, capacity)(sharded, xd, yd)
    assert float(got[0]) == float(want[0])
    assert sorted(got[1]) == sorted(want[1])
    for k, w in want[1].items():
        if "ep" in tuple(specs[k]):
            np.testing.assert_array_equal(np.asarray(got[1][k]),
                                          np.asarray(w))
        else:
            np.testing.assert_array_max_ulp(np.asarray(got[1][k]),
                                            np.asarray(w), maxulp=4)
    assert all(np.asarray(g).any() for g in want[1].values())
    # sharded leaf by leaf as `specs` says: an expert's gradient stays
    # on its device, the router's is whole on every one
    for k, g in got[1].items():
        assert g.sharding.spec == specs[k], k


def test_moe_trains_and_uses_multiple_experts(mesh):
    """A small ep-sharded regression task must reduce loss AND keep the
    router spread across experts (aux loss regularizer working)."""
    params = moe.init_moe(jax.random.PRNGKey(1), D, FF, E)
    specs = _expert_specs(params)
    grad_fn = _grad_fn(mesh, specs, 32)
    x, sharded, xd, yd = _regression(mesh, specs, params)

    opt = optax.adam(1e-2)
    st = opt.init(sharded)
    first = None
    for _ in range(60):
        loss, g = grad_fn(sharded, xd, yd)
        up, st = opt.update(g, st)
        sharded = optax.apply_updates(sharded, up)
        if first is None:
            first = float(loss)
    assert float(loss) < first * 0.6, (first, float(loss))
    # router still uses several experts after training
    gates = np.asarray(jax.nn.softmax(
        x @ np.asarray(sharded["moe_router_W"]), axis=-1))
    used = (np.bincount(gates.argmax(-1), minlength=E) > 0).sum()
    assert used >= 3, f"router collapsed to {used} experts"


class TestMoEDecode:
    """KV-cached decode on switch-MoE configs (VERDICT r2 item 5):
    capacity-bounded routing at one position per step."""

    def _cfgs(self, n_experts=4, capacity=64):
        from lua_mapreduce_tpu.models.transformer import TransformerConfig
        moe = TransformerConfig(vocab=32, d_model=16, n_heads=2,
                                n_layers=2, d_ff=24, max_seq=32,
                                moe_experts=n_experts,
                                moe_capacity=capacity)
        dense = TransformerConfig(vocab=32, d_model=16, n_heads=2,
                                  n_layers=2, d_ff=24, max_seq=32)
        return moe, dense

    def test_decode_golden_vs_dense_on_identical_experts(self):
        """MoE decode ≡ dense decode when every expert IS the dense FFN.

        Construction: zero router → uniform gates (1/E each, argmax
        breaks the tie to expert 0); every expert's first layer equals
        the dense ff1 and its second layer is the dense ff2 scaled by E,
        so combine-weight 1/E times the expert output reproduces the
        dense FFN exactly (E a power of two → the scaling is exact in
        f32). Token-exact golden diff between the two decode paths."""
        from lua_mapreduce_tpu.models import transformer as tfm
        moe_cfg, dense_cfg = self._cfgs()
        e = moe_cfg.moe_experts
        dense_params = tfm.init_transformer(jax.random.PRNGKey(7),
                                            dense_cfg)
        # non-FFN params copied VERBATIM (same-seed init would not do:
        # the two configs consume different numbers of PRNG splits, so
        # their attention weights diverge); FFN params constructed
        moe_params = {k: v for k, v in dense_params.items()
                      if "_ff" not in k}
        for i in range(moe_cfg.n_layers):
            p = f"L{i}"
            moe_params[f"{p}_moe_router_W"] = jnp.zeros(
                (moe_cfg.d_model, e))
            moe_params[f"{p}_moe_w1"] = jnp.tile(
                dense_params[f"{p}_ff1_W"][None], (e, 1, 1))
            moe_params[f"{p}_moe_b1"] = jnp.tile(
                dense_params[f"{p}_ff1_b"][None], (e, 1))
            moe_params[f"{p}_moe_w2"] = jnp.tile(
                e * dense_params[f"{p}_ff2_W"][None], (e, 1, 1))
            moe_params[f"{p}_moe_b2"] = jnp.tile(
                e * dense_params[f"{p}_ff2_b"][None], (e, 1))

        prompt = jnp.asarray(
            np.random.RandomState(3).randint(0, 32, (3, 5)), jnp.int32)
        got = tfm.greedy_decode(moe_params, prompt, 6, cfg=moe_cfg)
        want = tfm.greedy_decode(dense_params, prompt, 6, cfg=dense_cfg)
        assert np.array_equal(np.asarray(got), np.asarray(want))

    @pytest.mark.heavy
    def test_decode_matches_full_forward_rerun(self):
        """Random-router MoE decode vs re-running the FULL MoE forward
        at every prefix: token-exact when no bucket overflows (capacity
        ≥ every per-group worst case, so drop decisions are empty in
        both the per-step and the whole-tile routing groups)."""
        from lua_mapreduce_tpu.models import transformer as tfm
        moe_cfg, _ = self._cfgs(capacity=3 * 32)   # ≥ B*L: no drops
        params = tfm.init_transformer(jax.random.PRNGKey(11), moe_cfg)
        prompt = jnp.asarray(
            np.random.RandomState(5).randint(0, 32, (3, 4)), jnp.int32)
        n_new = 6
        got = tfm.greedy_decode(params, prompt, n_new, cfg=moe_cfg)
        toks = prompt
        for _ in range(n_new):
            logits = tfm.transformer_apply(params, toks, cfg=moe_cfg)
            nxt = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
            toks = jnp.concatenate([toks, nxt[:, None]], axis=1)
        assert np.array_equal(np.asarray(got), np.asarray(toks))

    @pytest.mark.heavy
    def test_prefill_decode_matches_scan_when_no_overflow(self):
        """MoE + use_prefill: token-exact vs the scan decode when no
        routing bucket overflows (capacity >= every group's worst
        case); under overflow the two grouping schemes drop different
        tokens by design (documented caveat)."""
        from lua_mapreduce_tpu.models import transformer as tfm
        moe_cfg, _ = self._cfgs(capacity=3 * 32)       # >= B*P: no drops
        params = tfm.init_transformer(jax.random.PRNGKey(2), moe_cfg)
        prompt = jnp.asarray(
            np.random.RandomState(6).randint(0, 32, (3, 6)), jnp.int32)
        a = tfm.greedy_decode(params, prompt, 5, cfg=moe_cfg)
        b = tfm.greedy_decode(params, prompt, 5, cfg=moe_cfg,
                              use_prefill=True)
        assert np.array_equal(np.asarray(a), np.asarray(b))

    def test_decode_sampling_moe(self):
        """Temperature sampling works on the MoE path and is
        deterministic per key."""
        from lua_mapreduce_tpu.models import transformer as tfm
        moe_cfg, _ = self._cfgs()
        params = tfm.init_transformer(jax.random.PRNGKey(1), moe_cfg)
        prompt = jnp.asarray([[1, 2, 3]], jnp.int32)
        k = jax.random.PRNGKey(4)
        a = tfm.greedy_decode(params, prompt, 5, cfg=moe_cfg,
                              temperature=0.8, key=k)
        b = tfm.greedy_decode(params, prompt, 5, cfg=moe_cfg,
                              temperature=0.8, key=k)
        assert np.array_equal(np.asarray(a), np.asarray(b))
        assert np.all(np.asarray(a) < moe_cfg.vocab)


class TestTopK:
    @pytest.mark.heavy
    def test_top2_matches_dense_composition_with_big_capacity(self,
                                                              params):
        """With capacity >= T nothing drops, so top-2 routing must equal
        the dense oracle: run every expert on every token, take each
        token's two highest-gated experts, renormalize their gates, and
        mix."""
        x = _tokens(3)
        t = x.shape[0]
        out, _ = moe.moe_ffn_reference(params, x, capacity=t, top_k=2)

        w = {k[len("moe_"):]: v for k, v in params.items()}
        gates = jax.nn.softmax(x @ w["router_W"], axis=-1)      # (T, E)
        h = jax.nn.gelu(jnp.einsum("td,edf->tef", x, w["w1"])
                        + w["b1"][None])
        ye = jnp.einsum("tef,efd->ted", h, w["w2"]) + w["b2"][None]
        top2 = jnp.argsort(gates, axis=-1)[:, -2:]              # (T, 2)
        g2 = jnp.take_along_axis(gates, top2, axis=-1)
        g2 = g2 / g2.sum(axis=-1, keepdims=True)
        want = jnp.einsum(
            "tk,tkd->td", g2,
            jnp.take_along_axis(ye, top2[:, :, None], axis=1))
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   rtol=2e-4, atol=2e-5)

    def test_top2_capacity_accounts_across_both_choices(self, params):
        """Slots are shared between first and second choices: forcing
        every token's top-2 onto the same two experts fills each bucket
        once, not twice."""
        p = dict(params)
        bias = jnp.zeros((D, E)).at[:, 2].set(100.0).at[:, 5].set(99.0)
        p["moe_router_W"] = bias
        x = jnp.abs(_tokens(4, t=16))
        out, _ = moe.moe_ffn_reference(p, x, capacity=CAP, top_k=2)
        norms = np.linalg.norm(np.asarray(out), axis=-1)
        # experts 2 and 5 each keep their first CAP tokens (the same
        # first CAP tokens — routing is token-ordered), rest dropped
        assert (norms[:CAP] > 1e-6).all()
        np.testing.assert_allclose(norms[CAP:], 0.0, atol=1e-6)

    def test_top2_shard_matches_reference(self, mesh, params):
        """The golden-diff extends to top-k: per-tile reference routing
        equals the ep-sharded form."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        n = mesh.shape["ep"]
        x = _tokens(5, t=8 * n)
        tiles = x.reshape(n, -1, D)
        want = jnp.concatenate([
            moe.moe_ffn_reference(params, tiles[i], capacity=CAP,
                                  top_k=2)[0] for i in range(n)])

        def body(xt, pr):
            out, aux = moe.moe_ffn_shard(pr, xt, capacity=CAP,
                                         ep_axis="ep", top_k=2)
            return out

        shard_p = {k: (NamedSharding(mesh, P("ep"))
                       if k != "moe_router_W"
                       else NamedSharding(mesh, P()))
                   for k in params}
        pr = {k: jax.device_put(v, shard_p[k])
              for k, v in params.items()}
        got = jax.jit(shard_map(
            body, mesh=mesh,
            in_specs=(P("ep"), {k: (P("ep") if k != "moe_router_W"
                                    else P()) for k in params}),
            out_specs=P("ep")))(x, pr)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-4, atol=2e-5)

    def test_top_k_validation(self):
        from lua_mapreduce_tpu.models.transformer import (
            TransformerConfig, init_transformer)

        cfg = TransformerConfig(vocab=32, d_model=16, n_heads=2,
                                n_layers=1, d_ff=32, max_seq=16,
                                moe_experts=4, moe_capacity=8,
                                moe_top_k=5)
        with pytest.raises(ValueError, match="moe_top_k"):
            init_transformer(jax.random.PRNGKey(0), cfg)

    @pytest.mark.heavy
    def test_top2_transformer_trains(self):
        """A top-2 MoE transformer learns the stride task through the
        full sharded train step — moe_top_k threads end to end."""
        from lua_mapreduce_tpu.models import transformer as tfm

        cfg = tfm.TransformerConfig(vocab=16, d_model=32, n_heads=2,
                                    n_layers=2, d_ff=64, max_seq=64,
                                    moe_experts=4, moe_capacity=128,
                                    moe_top_k=2)
        mesh2 = make_mesh(dp=4, mp=2, devices=jax.devices("cpu")[:8],
                          axis_names=("dp", "sp"))
        rng = np.random.RandomState(1)
        b, l = 8, 64
        start = rng.randint(0, cfg.vocab, (b, 1))
        seq = (start + np.arange(l + 1)) % cfg.vocab
        tokens = jnp.asarray(seq[:, :-1], jnp.int32)
        targets = jnp.asarray(seq[:, 1:], jnp.int32)
        opt = optax.adam(3e-3)
        from lua_mapreduce_tpu.models.transformer import shard_params_moe
        params = shard_params_moe(
            tfm.init_transformer(jax.random.PRNGKey(2), cfg), mesh2)
        step = tfm.make_train_step(cfg, mesh2, opt, attn="ring")
        st = opt.init(params)
        td = tfm.shard_batch(mesh2, tokens, targets)
        first = None
        for _ in range(60):
            params, st, loss = step(params, st, *td)
            if first is None:
                first = float(loss)
        assert float(loss) < first / 3, (first, float(loss))


class TestSortedRouting:
    """The sort+gather routing (the default impl) must be EXACTLY the
    one-hot einsum oracle's semantics — same top-k choices, same
    first-C-in-token-order capacity fill (round-major for k>1), same
    pre-drop renormalization, same aux — on outputs AND gradients
    (DESIGN §14: the einsum form's dispatch/combine contractions are
    8× the expert FFN's FLOPs; the sorted form removes them, so it
    must be a pure reformulation, not an approximation)."""

    @pytest.mark.parametrize("top_k", [1, 2])
    @pytest.mark.parametrize("cap", [CAP, 64])
    def test_outputs_match_einsum_oracle(self, params, top_k, cap):
        x = _tokens(7, t=48)
        want, aux_w = moe.moe_ffn_reference(params, x, capacity=cap,
                                            top_k=top_k, impl="einsum")
        got, aux_g = moe.moe_ffn_reference(params, x, capacity=cap,
                                           top_k=top_k, impl="sorted")
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(float(aux_g), float(aux_w), rtol=1e-5)

    @pytest.mark.parametrize("top_k", [1, 2])
    def test_outputs_match_under_heavy_overflow(self, params, top_k):
        """Collapse the router onto one expert so most tokens drop —
        the fill order (round-major, then token order) must agree."""
        p = dict(params)
        p["moe_router_W"] = jnp.zeros((D, E)).at[:, 3].set(100.0)
        x = jnp.abs(_tokens(8, t=24))
        want, _ = moe.moe_ffn_reference(p, x, capacity=CAP,
                                        top_k=top_k, impl="einsum")
        got, _ = moe.moe_ffn_reference(p, x, capacity=CAP,
                                       top_k=top_k, impl="sorted")
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-6)

    @pytest.mark.parametrize("top_k", [1, 2])
    def test_grads_match_einsum_oracle(self, params, top_k):
        x = _tokens(9, t=32)

        def loss(params, x, impl):
            out, aux = moe.moe_ffn_reference(params, x, capacity=CAP,
                                             top_k=top_k, impl=impl)
            return jnp.sum(out ** 2) + 0.01 * aux

        gw_p, gw_x = jax.grad(loss, argnums=(0, 1))(params, x, "einsum")
        gs_p, gs_x = jax.grad(loss, argnums=(0, 1))(params, x, "sorted")
        np.testing.assert_allclose(np.asarray(gs_x), np.asarray(gw_x),
                                   rtol=2e-4, atol=1e-5)
        for k in gw_p:
            np.testing.assert_allclose(
                np.asarray(gs_p[k]), np.asarray(gw_p[k]),
                rtol=2e-4, atol=1e-5, err_msg=k)

    def test_shard_sorted_matches_einsum_shard(self, mesh, params):
        """Both impls inside shard_map over the ep axis: identical
        outputs — the all_to_all operates on identical (E, C, d)
        buckets regardless of how they were built."""
        n_ep, t_local = 8, 16
        x = _tokens(10, t=n_ep * t_local)
        specs = {k: (P("ep") if k.startswith("moe_w") or
                     k.startswith("moe_b") else P())
                 for k in params}
        sharded = {k: jax.device_put(v, NamedSharding(mesh, specs[k]))
                   for k, v in params.items()}
        xs = jax.device_put(x, NamedSharding(mesh, P("ep")))

        def run(impl):
            def body(params, x):
                return moe.moe_ffn_shard(params, x, capacity=CAP,
                                         ep_axis="ep", impl=impl)
            fn = jax.jit(shard_map(
                body, mesh=mesh, in_specs=(specs, P("ep")),
                out_specs=(P("ep"), P())), static_argnums=())
            return fn(sharded, xs)

        want, _ = run("einsum")
        got, _ = run("sorted")
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)
