"""Flash-attention kernel vs the XLA oracle (interpreter mode on CPU —
the kernel-path test discipline of tests/test_ops.py)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from lua_mapreduce_tpu.ops.attention import flash_attention


def _qkv(seed, b=2, l=96, h=3, d=32, dtype=jnp.float32):
    rng = np.random.RandomState(seed)
    mk = lambda: jnp.asarray(rng.randn(b, l, h, d), dtype) * 0.5
    return mk(), mk(), mk()


@pytest.mark.parametrize("causal", [False, True], ids=["full", "causal"])
def test_kernel_matches_oracle(causal):
    q, k, v = _qkv(0)
    want = flash_attention(q, k, v, causal=causal, backend="xla")
    got = flash_attention(q, k, v, causal=causal,
                          backend="pallas_interpret",
                          block_q=32, block_k=128)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_ragged_length_padding():
    """L not a multiple of any block size: padded tail must not leak."""
    q, k, v = _qkv(1, l=70)
    want = flash_attention(q, k, v, causal=True, backend="xla")
    got = flash_attention(q, k, v, causal=True,
                          backend="pallas_interpret",
                          block_q=16, block_k=128)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_mixed_dtypes_promoted():
    """bf16 q with f32 k/v must work on BOTH backends (the kernel dots
    run in operand dtype, so promotion happens at the public boundary)."""
    q, k, v = _qkv(5)
    want = flash_attention(q, k, v, causal=True, backend="xla")
    got_x = flash_attention(q.astype(jnp.bfloat16), k, v, causal=True,
                            backend="xla")
    got_p = flash_attention(q.astype(jnp.bfloat16), k, v, causal=True,
                            backend="pallas_interpret")
    for got in (got_x, got_p):
        assert got.dtype == jnp.float32
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want), rtol=0.02, atol=0.02)


def test_bfloat16():
    q, k, v = _qkv(2, dtype=jnp.bfloat16)
    want = flash_attention(q.astype(jnp.float32), k.astype(jnp.float32),
                           v.astype(jnp.float32), causal=True,
                           backend="xla")
    got = flash_attention(q, k, v, causal=True,
                          backend="pallas_interpret")
    assert got.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want), rtol=0.1, atol=0.05)


def test_gradients_flow():
    q, k, v = _qkv(3, l=32)

    def loss(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=True,
                                       backend="pallas_interpret",
                                       block_q=16, block_k=128) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=True,
                                       backend="xla") ** 2)

    g = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4)


def test_shape_mismatch_rejected():
    q, k, v = _qkv(4)
    with pytest.raises(ValueError, match="shapes differ"):
        flash_attention(q, k[:, :64], v)


class TestFusedBackward:
    """The Pallas backward (FlashAttention-2 shape): dq/dk/dv come from
    two fused kernels re-materializing p from the saved logsumexp —
    never from re-running the XLA composition. Parity with the XLA VJP
    across the geometries that exercise every masking/padding branch."""

    # l=96 runs the single-kv-block path; l=200 forces n_kv=2 (block_k
    # clamps to >=128), exercising the dq kernel's cross-kv-block
    # accumulation and the dkv kernel's per-kv-tile scratch re-init —
    # the geometry real training uses (code-review r3)
    @pytest.mark.parametrize("l", [96, 200], ids=["1kv", "2kv"])
    @pytest.mark.parametrize("causal", [False, True],
                             ids=["full", "causal"])
    def test_grads_match_xla_vjp(self, causal, l):
        q, k, v = _qkv(6, l=l)

        def loss(backend):
            def f(q, k, v):
                out = flash_attention(q, k, v, causal=causal,
                                      backend=backend,
                                      block_q=32, block_k=128)
                # non-uniform cotangent: catches dq/dk/dv mixups a
                # sum() cotangent of ones would let cancel out
                w = jnp.arange(out.size).reshape(out.shape) % 7
                return jnp.sum(out * w.astype(out.dtype))
            return f

        g = jax.grad(loss("pallas_interpret"), argnums=(0, 1, 2))(q, k, v)
        g_ref = jax.grad(loss("xla"), argnums=(0, 1, 2))(q, k, v)
        for name, a, b in zip("qkv", g, g_ref):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-4, atol=1e-4,
                                       err_msg=f"d{name}")

    def test_ragged_length_grads(self):
        """Padded tail rows/cols must contribute ZERO gradient."""
        q, k, v = _qkv(7, l=70)

        def loss(backend):
            return lambda q, k, v: jnp.sum(flash_attention(
                q, k, v, causal=True, backend=backend,
                block_q=16, block_k=128) ** 2)

        g = jax.grad(loss("pallas_interpret"), argnums=(0, 1, 2))(q, k, v)
        g_ref = jax.grad(loss("xla"), argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g, g_ref):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-4, atol=1e-4)

    @pytest.mark.heavy
    def test_bf16_grads(self):
        """bf16 operands: backward dots run in bf16 (MXU-native) with
        f32 accumulation — grads close to the f32 XLA VJP."""
        q, k, v = _qkv(8, l=64, dtype=jnp.bfloat16)

        def loss_p(q, k, v):
            return jnp.sum(flash_attention(
                q, k, v, causal=True,
                backend="pallas_interpret").astype(jnp.float32) ** 2)

        def loss_x(q, k, v):
            return jnp.sum(flash_attention(
                q.astype(jnp.float32), k.astype(jnp.float32),
                v.astype(jnp.float32), causal=True, backend="xla") ** 2)

        g = jax.grad(loss_p, argnums=(0, 1, 2))(q, k, v)
        g_ref = jax.grad(loss_x, argnums=(0, 1, 2))(
            q.astype(jnp.float32), k.astype(jnp.float32),
            v.astype(jnp.float32))
        for a, b in zip(g, g_ref):
            assert a.dtype == jnp.bfloat16
            np.testing.assert_allclose(np.asarray(a, np.float32),
                                       np.asarray(b), rtol=0.1, atol=0.1)

    @pytest.mark.parametrize("causal", [False, True],
                             ids=["full", "causal"])
    def test_return_lse_parity_and_grads(self, causal):
        """return_lse=True: out AND lse agree between backends, and
        gradients flow correctly through BOTH outputs (the lse
        cotangent folds into the backward's delta term)."""
        q, k, v = _qkv(10, l=200)

        def loss(backend):
            def f(q, k, v):
                out, lse = flash_attention(q, k, v, causal=causal,
                                           backend=backend,
                                           return_lse=True)
                return jnp.sum(out ** 2) + jnp.sum(jnp.sin(lse))
            return f

        op, lp = flash_attention(q, k, v, causal=causal,
                                 backend="pallas_interpret",
                                 return_lse=True)
        ox, lx = flash_attention(q, k, v, causal=causal, backend="xla",
                                 return_lse=True)
        assert lp.shape == (q.shape[0], q.shape[1], q.shape[2])
        np.testing.assert_allclose(np.asarray(op), np.asarray(ox),
                                   rtol=2e-5, atol=2e-5)
        np.testing.assert_allclose(np.asarray(lp), np.asarray(lx),
                                   rtol=1e-5, atol=1e-5)
        g = jax.grad(loss("pallas_interpret"), argnums=(0, 1, 2))(q, k, v)
        g_ref = jax.grad(loss("xla"), argnums=(0, 1, 2))(q, k, v)
        for name, a, b in zip("qkv", g, g_ref):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-4, atol=1e-4,
                                       err_msg=f"d{name}")

    def test_saved_lse_is_correct(self):
        """The forward's saved logsumexp equals the oracle's row-wise
        logsumexp of the masked scores (the quantity the backward
        trusts to re-materialize p)."""
        from lua_mapreduce_tpu.ops.attention import _flash_pallas
        b, l, h, d = 2, 64, 2, 32
        rng = np.random.RandomState(9)
        q, k, v = (jnp.asarray(rng.randn(b, l, h, d), jnp.float32) * 0.5
                   for _ in range(3))
        _, lse = _flash_pallas(q, k, v, causal=True, interpret=True,
                               with_lse=True)
        s = np.einsum("blhd,bmhd->bhlm", np.asarray(q), np.asarray(k),
                      dtype=np.float64) / np.sqrt(d)
        mask = np.tril(np.ones((l, l), bool))
        s = np.where(mask, s, -np.inf)
        want = np.log(np.sum(np.exp(s), axis=-1))      # (b, h, l)
        got = np.asarray(lse).reshape(b, h, l)
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


# (block_q, block_k, causal, window, q_offset, n_q, n_kv) — awkward
# geometries, incl. the banded ring's far hop (q_offset > window +
# block_k: whole kv blocks, here every one, have no live q block)
_GEOMS = [
    (128, 128, True, 0, 0, 8, 8),
    (64, 128, True, 50, 128, 6, 3),
    (128, 128, True, 50, 512, 4, 4),
    (64, 128, True, 1, 0, 8, 4),         # window=1 off-by-one case
    (128, 256, True, 300, 1024, 8, 4),
    (8, 128, True, 17, 40, 5, 2),
    (64, 128, False, 0, 0, 4, 2),
]


@pytest.mark.parametrize("geom", _GEOMS, ids=[
    "causal", "window-offset", "far-hop", "window1", "wide-kv",
    "tiny-q", "full-ragged"])
def test_tile_table_walks_every_live_tile_once(geom):
    """The tile schedule's invariants, against the ELEMENT mask: (a) a
    tile's class is what _tile_mask says of its entries (dead: none
    visible, interior: all — a tile wrongly called interior would fold
    masked entries unmasked); (b) the forward/dq walk and the dkv walk
    (GQA group 3) visit every live tile exactly once a group member and
    no dead one; (c) every index is in range — an out-of-range block
    index becomes a wild DMA offset on hardware while interpret mode
    silently wraps; (d) every outer block has exactly one run, _FIRST
    on its first step and _LAST on its last, a wholly dead block one
    step of class 0 that still writes its zeros."""
    from lua_mapreduce_tpu.ops import attention as A

    bq, bk, causal, window, qo, n_q, n_kv = geom
    seq_len = n_kv * bk - (5 if not causal else 0)
    cls = A._class_grid(n_q, n_kv, block_q=bq, block_k=bk, causal=causal,
                        window=window, seq_len=seq_len, q_offset=qo)
    valid = np.broadcast_to(np.asarray(A._tile_mask(
        np.arange(n_q * bq)[:, None], np.arange(n_kv * bk)[None, :],
        causal, window, seq_len, qo)), (n_q * bq, n_kv * bk))
    tiles = valid.reshape(n_q, bq, n_kv, bk).transpose(0, 2, 1, 3)
    want = np.where(tiles.all((2, 3)), A._INTERIOR,
                    np.where(tiles.any((2, 3)), A._EDGE, 0))
    np.testing.assert_array_equal(cls, want)

    for grid, group in ((cls, 1), (cls.T, 3)):
        n_outer, n_inner = grid.shape
        table = A._tile_table(grid, group)
        assert table.dtype == np.int32 and (table >= 0).all()
        outer, inner, flags = A._step(table, np.arange(table.size))
        assert (0 <= outer).all() and (outer < n_outer).all()
        assert (0 <= inner).all() and (inner < group * n_inner).all()
        # one run an outer block, in order, flagged at its two ends
        assert (np.diff(outer) >= 0).all()
        assert sorted(set(outer)) == list(range(n_outer))
        first = np.r_[True, np.diff(outer) > 0]
        last = np.r_[np.diff(outer) > 0, True]
        np.testing.assert_array_equal(flags & A._FIRST != 0, first)
        np.testing.assert_array_equal(flags & A._LAST != 0, last)
        kind = flags & (A._INTERIOR | A._EDGE)
        np.testing.assert_array_equal(kind, np.where(
            kind > 0, grid[outer, inner % n_inner], 0))
        visited = sorted(zip(outer[kind > 0], inner[kind > 0]))
        live = sorted((o, g * n_inner + i) for o in range(n_outer)
                      for g in range(group) for i in range(n_inner)
                      if grid[o, i])
        assert visited == live
        # a step of class 0 is only ever a dead block's whole run
        assert ((kind > 0) | (first & last)).all()


@pytest.mark.parametrize("geom,every", [
    # (n_q, n_kv, causal, window, q_offset) at 128 x 128 blocks
    ((1, 1, True, 0, 0), "FIRST LAST EDGE"),        # a prefill of one tile
    ((4, 4, True, 0, 0), ""),                       # a diagonal: all dynamic
    ((2, 2, True, 512, 256), "INTERIOR"),           # the ring's hop 1
    ((2, 1, False, 0, 0), "FIRST LAST INTERIOR"),   # full, one kv block
], ids=["one-tile", "causal", "ring-hop1", "full"])
def test_table_bits_every_step_has_need_no_branch(geom, every):
    """What the kernels specialise a step's bodies on: a flag every
    step of the walk has runs its body with no branch."""
    from lua_mapreduce_tpu.ops import attention as A
    n_q, n_kv, causal, window, qo = geom
    cls = A._class_grid(n_q, n_kv, block_q=128, block_k=128, causal=causal,
                        window=window, seq_len=n_kv * 128, q_offset=qo)
    some, got = A._table_bits(A._tile_table(cls), False)
    want = sum(getattr(A, "_" + name) for name in every.split())
    assert got == want and some & got == got


@pytest.mark.parametrize("l_q,q_offset,want", [
    (4096, 0, (28, 28, 8)),          # mistral7b-train-1chip: a row
    (2048, 0, (6, 6, 4)),            # the 2x2 ring's hop 0 (diagonal)
    (2048, 2048, (0, 16, 0)),        # its hop 1: all interior
], ids=["1chip", "ring-hop0", "ring-hop1"])
def test_tile_classes_at_the_train_cells(l_q, q_offset, want):
    from lua_mapreduce_tpu.ops.attention import tile_classes
    assert tile_classes(l_q, l_q, 512, 512, True, 4096, q_offset) == want


@pytest.fixture
def masked_everywhere(monkeypatch):
    """The parent's fold as the oracle: every tile of the whole grid
    visited and masked (a dead tile, wholly masked, folds nothing: the
    parent skipped it). Classes are read when a call is traced, so the
    traces made under the other schedule are dropped both ways."""
    from lua_mapreduce_tpu.ops import attention as A

    def on():
        monkeypatch.setattr(
            A, "_class_grid",
            lambda n_q, n_kv, **kw: np.full((n_q, n_kv), A._EDGE))
        jax.clear_caches()
    yield on
    monkeypatch.undo()
    jax.clear_caches()


_BITWISE = {
    # name: (l, h, h_kv, blocks, kwargs)
    "causal": (512, 2, 2, (128, 128), dict(causal=True)),
    "causal-bf16": (512, 2, 2, (128, 128), dict(causal=True)),
    "window-inside": (512, 2, 2, (128, 128), dict(causal=True,
                                                  window=200)),
    "window-at-L": (512, 2, 2, (128, 128), dict(causal=True, window=512)),
    "window-beyond": (512, 2, 2, (128, 128), dict(causal=True,
                                                  window=4096)),
    "ring-hop1": (256, 2, 2, (128, 128), dict(causal=True, window=512,
                                              q_offset=256)),
    "ring-hop1-edge": (256, 2, 2, (64, 128), dict(causal=True,
                                                  window=300,
                                                  q_offset=256)),
    "ring-far-dead": (256, 2, 2, (128, 128), dict(causal=True,
                                                  window=300,
                                                  q_offset=1024)),
    "gqa4": (384, 4, 1, (128, 128), dict(causal=True)),
    "ragged": (300, 2, 2, (64, 128), dict(causal=True)),
    "full": (256, 2, 2, (128, 128), dict(causal=False)),
    "full-ragged": (300, 2, 1, (64, 128), dict(causal=False)),
}


@pytest.mark.parametrize("case", sorted(_BITWISE))
def test_tile_schedule_equals_masked_fold_bit_for_bit(case,
                                                      masked_everywhere):
    """o, lse, dq, dk, dv of the tile schedule (dead tiles not visited,
    interior tiles folded with no mask) equal, to the last bit, the
    fold that visits and masks every tile at the same blocks."""
    l, h, hkv, (bq, bk), kw = _BITWISE[case]
    dtype = jnp.bfloat16 if case.endswith("bf16") else jnp.float32
    # head width 64: XLA's CPU backend, which runs interpret mode,
    # contracts dot·scale − m into one fused multiply-add where no
    # select stands between them; at a power-of-two scale the product
    # is exact, so both bodies round alike here as they do on the chip
    rng = np.random.RandomState(11)
    q = jnp.asarray(rng.randn(1, l, h, 64), dtype)
    k, v = (jnp.asarray(rng.randn(1, l, hkv, 64), dtype)
            for _ in range(2))

    def run():
        def f(q, k, v):
            return flash_attention(q, k, v, backend="pallas_interpret",
                                   block_q=bq, block_k=bk,
                                   return_lse=True, **kw)

        def loss(q, k, v):
            o, lse = f(q, k, v)
            w = jnp.arange(o.size).reshape(o.shape) % 7
            # a wholly dead row's lse is -1e30: keep it out of sin
            return (jnp.sum(o * w.astype(o.dtype))
                    + jnp.sum(jnp.sin(jnp.maximum(lse, -50.0))))
        return (*f(q, k, v), *jax.grad(loss, argnums=(0, 1, 2))(q, k, v))

    got = run()
    masked_everywhere()
    want = run()
    for name, a, b in zip(("o", "lse", "dq", "dk", "dv"), got, want):
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32),
                                      err_msg=name)
    # and the oracle is no tautology: the answers are the XLA oracle's
    o_x, lse_x = flash_attention(q, k, v, backend="xla", return_lse=True,
                                 **kw)
    tol = 0.05 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(o_x),
                               rtol=tol, atol=tol)
