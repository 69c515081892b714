"""ops/decode.py — fused decode attention.

The XLA path is the exact composition the decode scan ran in-line
before the op existed, so the long-standing token-exactness pins
(decode vs full-forward oracle, prefill vs scan) transitively cover
it; THIS file pins the Pallas kernel against that XLA path over the
(shape, position, roll) matrix in interpret mode, and the kernel's
Mosaic lowering lives in tests/test_tpu_lowering.py with the rest.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from lua_mapreduce_tpu.ops.decode import (_TILE_BYTES, _VMEM_LIMIT, _tiles,
                                          _vmem_bytes, decode_attention)


def _args(b, hkv, g, d, s_len, seed=0, dtype=jnp.float32):
    rng = np.random.RandomState(seed)
    q = jnp.asarray(rng.randn(b, hkv, g, d), dtype)
    k = jnp.asarray(rng.randn(b, hkv, s_len, d), dtype)
    v = jnp.asarray(rng.randn(b, hkv, s_len, d), dtype)
    return q, k, v


class TestDecodeParity:
    @pytest.mark.parametrize("shape", [(2, 4, 1, 64, 256),   # MHA g=1
                                       (2, 2, 4, 64, 384),   # GQA
                                       (1, 1, 8, 128, 512),
                                       # ragged: s_len % block_s != 0
                                       # exercises the ceil-divided
                                       # grid's masked final block
                                       (2, 2, 2, 64, 300),
                                       (1, 2, 1, 64, 1000),
                                       # several rows a step (ops/decode
                                       # _tiles): 6 rows, 8 does not
                                       # divide them; two chunks, so
                                       # t = 0 and 5 leave one dead
                                       (3, 2, 4, 64, 1024),
                                       # 7 rows a step AND a ragged
                                       # second chunk (640 = 512 + 128)
                                       (1, 7, 2, 64, 640),
                                       # 17 rows: prime and past the
                                       # most a step takes, one a step
                                       (1, 17, 1, 64, 256)])
    def test_kernel_matches_xla(self, shape):
        b, hkv, g, d, s_len = shape
        q, k, v = _args(*shape)
        for t in [0, 5, s_len // 2, s_len - 1]:
            for roll in (False, True):
                ref = decode_attention(q, k, v, jnp.int32(t), roll=roll,
                                       backend="xla")
                got = decode_attention(q, k, v, jnp.int32(t), roll=roll,
                                       backend="pallas_interpret")
                np.testing.assert_allclose(
                    np.asarray(got), np.asarray(ref), rtol=2e-5,
                    atol=2e-5, err_msg=f"t={t} roll={roll}")

    @pytest.mark.parametrize("shape", [(2, 2, 2, 64, 300),   # ragged
                                       # the two decode cells' shapes
                                       # cut in batch: 8 rows a step,
                                       # one chunk a row and eight
                                       (2, 8, 4, 128, 512),
                                       (1, 8, 4, 128, 4096)])
    def test_kernel_matches_xla_bf16(self, shape):
        """The real serving dtype: bf16 caches. ``t`` in the first
        chunk of a row of several leaves the others dead (their DMAs
        elided, their compute skipped)."""
        s_len = shape[-1]
        q, k, v = _args(*shape, seed=9, dtype=jnp.bfloat16)
        for t in [0, 5, s_len // 2, s_len - 1]:
            for roll in (False, True):
                ref = decode_attention(q, k, v, jnp.int32(t), roll=roll,
                                       backend="xla")
                got = decode_attention(q, k, v, jnp.int32(t), roll=roll,
                                       backend="pallas_interpret")
                np.testing.assert_allclose(
                    np.asarray(got), np.asarray(ref), rtol=2e-2,
                    atol=2e-2, err_msg=f"t={t} roll={roll}")

    def test_rolling_full_cache_all_slots_visible(self):
        """t ≥ S in rolling mode: every slot holds a live position —
        the containment-is-the-mask rule."""
        q, k, v = _args(1, 2, 2, 64, 128, seed=3)
        ref = decode_attention(q, k, v, jnp.int32(500), roll=True,
                               backend="xla")
        got = decode_attention(q, k, v, jnp.int32(500), roll=True,
                               backend="pallas_interpret")
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)

    def test_t_zero_attends_only_first_slot(self):
        """Degenerate start: exactly one visible slot; the online
        softmax must not divide by a zero denominator."""
        q, k, v = _args(1, 2, 1, 64, 256, seed=4)
        got = decode_attention(q, k, v, jnp.int32(0),
                               backend="pallas_interpret")
        # one visible slot → output IS that slot's v row
        np.testing.assert_allclose(np.asarray(got),
                                   np.asarray(v[:, :, 0:1, :]),
                                   rtol=1e-5, atol=1e-5)

    def test_inside_scan_traced_t(self):
        """The real call shape: ``t`` is a traced scan counter, the
        caches ride the carry."""
        q, k, v = _args(1, 2, 1, 64, 128, seed=5)

        def body(c, t):
            return c, decode_attention(q, k, v, t,
                                       backend="pallas_interpret")

        _, outs = jax.lax.scan(body, 0, jnp.arange(4))
        for i in range(4):
            ref = decode_attention(q, k, v, jnp.int32(i), backend="xla")
            np.testing.assert_allclose(np.asarray(outs[i]),
                                       np.asarray(ref),
                                       rtol=2e-5, atol=2e-5)

    @pytest.mark.parametrize("shape", [(2, 4, 1, 64, 256),
                                       (2, 2, 2, 64, 300),   # ragged
                                       # 16 rows a step at the cells'
                                       # head width; 7 with a ragged
                                       # second chunk
                                       (2, 8, 4, 128, 512),
                                       (1, 7, 2, 64, 640)])
    def test_q8_kernel_matches_xla(self, shape):
        """int8-cache path: the kernel's factored-out scales must
        reproduce the XLA q8 composition (same rounding points), live
        range, roll, and ragged tail included."""
        from lua_mapreduce_tpu.ops.decode import quantize_kv

        b, hkv, g, d, s_len = shape
        q, k, v = _args(*shape, seed=7)
        q = q.astype(jnp.bfloat16)
        kq, ks = quantize_kv(k)
        vq, vs = quantize_kv(v)
        for t in [0, 5, s_len // 2, s_len - 1]:
            for roll in (False, True):
                ref = decode_attention(q, kq, vq, jnp.int32(t),
                                       roll=roll, k_scale=ks,
                                       v_scale=vs, backend="xla")
                got = decode_attention(q, kq, vq, jnp.int32(t),
                                       roll=roll, k_scale=ks,
                                       v_scale=vs,
                                       backend="pallas_interpret")
                np.testing.assert_allclose(
                    np.asarray(got), np.asarray(ref), rtol=5e-3,
                    atol=5e-3, err_msg=f"t={t} roll={roll}")

    def test_q8_close_to_full_precision(self):
        """Quantization noise at d=64 stays under ~2% of the full-
        precision result — the accuracy budget kv_q8 serving spends."""
        from lua_mapreduce_tpu.ops.decode import quantize_kv

        q, k, v = _args(1, 2, 2, 64, 256, seed=8)
        kq, ks = quantize_kv(k)
        vq, vs = quantize_kv(v)
        full = decode_attention(q, k, v, jnp.int32(255), backend="xla")
        q8 = decode_attention(q, kq, vq, jnp.int32(255), k_scale=ks,
                              v_scale=vs, backend="xla")
        rel = float(jnp.abs(full - q8).max() / jnp.abs(full).max())
        assert rel < 0.02, rel

    def test_scales_must_come_together(self):
        from lua_mapreduce_tpu.ops.decode import quantize_kv

        q, k, v = _args(1, 1, 1, 64, 128)
        kq, ks = quantize_kv(k)
        with pytest.raises(ValueError, match="together"):
            decode_attention(q, kq, v, jnp.int32(0), k_scale=ks)

    def test_bad_backend_rejected(self):
        q, k, v = _args(1, 1, 1, 64, 128)
        with pytest.raises(ValueError, match="unknown backend"):
            decode_attention(q, k, v, jnp.int32(0), backend="cuda")


# (rows, s_len, d, itemsize, g, q8) -> (rows a step, chunk length)
CHAT = (32 * 8, 512, 128, 2, 4, False)
LONGCTX = (8 * 8, 4096, 128, 2, 4, False)


def _q8(call):
    return call[:3] + (1, call[4], True)


class TestTiles:
    """``_tiles`` alone: a plain function of static shapes."""

    @pytest.mark.parametrize("call", [CHAT, LONGCTX, _q8(CHAT),
                                      _q8(LONGCTX)])
    def test_the_cells_shapes_get_a_megabyte_a_tile(self, call):
        rows, s_len, d, itemsize, g, q8 = call
        r, block_s = _tiles(*call)
        assert r * block_s * d * itemsize == _TILE_BYTES == 1 << 20
        assert block_s == 512           # the elision's granularity
        assert rows % r == 0

    @pytest.mark.parametrize("itemsize,q8", [(2, False), (4, False),
                                             (1, True)])
    @pytest.mark.parametrize("rows,s_len", [(256, 512), (64, 4096),
                                            (6, 1024), (7, 640), (17, 256),
                                            (1, 32768), (34, 32768),
                                            (4, 300), (1024, 128)])
    def test_divides_the_rows_and_fits_vmem(self, rows, s_len, itemsize,
                                            q8):
        for d, g in [(64, 1), (64, 8), (128, 4), (128, 1), (256, 8)]:
            r, block_s = _tiles(rows, s_len, d, itemsize, g, q8)
            assert rows % r == 0 and block_s % 128 == 0
            # the padded count, scale tiles and all, under the limit
            # the call hands Mosaic
            assert _vmem_bytes(r, block_s, d, itemsize, g, q8) \
                < _VMEM_LIMIT
            # a chunk never longer than the row rounded up, nor, once
            # lengthened, than an eighth of it
            assert block_s <= max(512, s_len // 8)
            assert block_s <= -(-s_len // 128) * 128

    def test_vmem_count_pads_as_mosaic_lays_out(self):
        # 8 rows x 512 x 128 bf16: two 1 MiB tiles, double-buffered,
        # are 4 MiB of the count; head width 64 pads to 128 lanes
        base = _vmem_bytes(8, 512, 128, 2, 4, False)
        assert 4 << 20 < base < 6 << 20
        assert _vmem_bytes(8, 512, 64, 2, 4, False) == base
        # int8 caches bring the bf16 copies of both tiles (1 MiB each)
        # and two double-buffered (r, 1, block_s) float32 scale tiles,
        # one sublane of eight used (128 KiB each)
        assert _vmem_bytes(8, 512, 128, 1, 4, True) \
            - _vmem_bytes(8, 512, 128, 1, 4, False) == (2 << 20) + (512 << 10)

    def test_rows_it_cannot_group_keep_the_old_tiling(self):
        # 17 is prime and more than a step takes: one row a step, and
        # a short row leaves the chunk at the 512 it was
        assert _tiles(17, 512, 128, 2, 4, False) == (1, 512)
        assert _tiles(17, 4096, 128, 2, 4, False) == (1, 512)
        # a long row makes up for it with the chunk, to an eighth of
        # the row (4096 of 32768: 1 MiB at head width 128)
        assert _tiles(17, 32768, 128, 2, 4, False) == (1, 4096)
        assert _tiles(1, 32768, 128, 2, 4, False) == (1, 4096)
        # rows that do group never lengthen the chunk past the target
        assert _tiles(64, 32768, 128, 2, 4, False) == (8, 512)
        # the chunk asked for is cut to a short cache's own length
        assert _tiles(8, 300, 64, 2, 4, False) == (8, 384)
        assert _tiles(8, 100, 64, 2, 4, False) == (8, 128)
