"""The held experts of a decode step as one grouped call
(`ops/moe_held.py`): the kernel, interpreted on the CPU, against the
conditionals it replaced."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from lua_mapreduce_tpu import ops
from lua_mapreduce_tpu.ops.moe_held import (MAX_TOKENS, _moe_held_pallas,
                                            _moe_held_xla, _tiles, _touched,
                                            _vmem_bytes, _VMEM_BUDGET,
                                            moe_held)

D, F = 128, 256             # two tiles of 128 an expert


def layer(tokens: int, count: int, dtype, d: int = D, f: int = F):
    ks = jax.random.split(jax.random.PRNGKey(tokens * 100 + count), 4)
    draw = lambda k, shape, scale: (  # noqa: E731
        scale * jax.random.normal(k, shape)).astype(dtype)
    return (draw(ks[0], (tokens, d), 1.0), draw(ks[1], (count, d, f), d ** -.5),
            draw(ks[2], (count, d, f), d ** -.5),
            draw(ks[3], (count, f, d), f ** -.5))


def routing(tokens: int, count: int, touched: str):
    """(combine, load) as `moe_ffn_held` computes them: every token gives
    two of the touched experts a weight (one where one is touched)."""
    experts = {"none": [], "one": [1], "all": list(range(count)),
               "last": [count - 1]}[touched]
    combine = np.zeros((tokens, count), np.float32)
    for t in range(tokens):
        for j in range(min(2, len(experts))):
            e = experts[(3 * t + j * (1 + t % 2)) % len(experts)]
            combine[t, e] += 0.2 + 0.05 * ((t + j) % 7)
    if touched == "all":        # each at least once, whatever the tokens
        combine[np.arange(count) % tokens, np.arange(count)] += 0.1
    load = (combine > 0).sum(0).astype(np.int32)
    return jnp.asarray(combine), jnp.asarray(load)


# compiled once a shape, whatever the routing (a case costs its routing)
conditionals = jax.jit(_moe_held_xla)


@jax.jit
def largest_term(x, combine, wg, wu, wd):
    """The largest |combine[:, e] * swiglu_e(x)| over experts and tokens."""
    h = jax.nn.silu(jnp.einsum("td,edf->etf", x, wg)) * jnp.einsum(
        "td,edf->etf", x, wu)
    y = jnp.einsum("etf,efd->etd", h, wd)
    return jnp.max(jnp.abs(combine.T[:, :, None] * y))


@pytest.mark.parametrize("touched", ["none", "one", "all", "last"])
@pytest.mark.parametrize("count", [4, 16, 32])
@pytest.mark.parametrize("tokens", [5, 8, 16])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_the_kernel_gives_what_the_conditionals_give(dtype, tokens, count,
                                                     touched):
    x, wg, wu, wd = layer(tokens, count, dtype)
    combine, load = routing(tokens, count, touched)
    want = conditionals(x, combine, load, wg, wu, wd)
    got = moe_held(x, combine, load, wg, wu, wd, backend="pallas_interpret")
    assert got.shape == (tokens, D) and got.dtype == jnp.float32
    if touched == "none":
        assert not np.asarray(got).any()
        return
    assert np.abs(np.asarray(want)).max() > 0.05
    if dtype == jnp.float32:
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)
        return
    # bfloat16: the conditionals round an expert's hidden values more
    # than once on the CPU and its output before it is weighed, so they
    # are held, as the kernel is, to the conditionals over the same
    # values in float32: the kernel within one ulp (of bfloat16's eight
    # bits) of the largest term, and no farther than they are
    wide = [a.astype(jnp.float32) for a in (x, wg, wu, wd)]
    exact = np.asarray(conditionals(wide[0], combine, load, *wide[1:]))
    largest = float(largest_term(wide[0], combine, *wide[1:]))
    ulp = 2.0 ** (np.floor(np.log2(largest)) - 7)
    off = np.abs(np.asarray(got) - exact).max()
    assert off <= ulp
    assert off <= np.abs(np.asarray(want) - exact).max()


@pytest.mark.parametrize("tile_f", [128, 256])
def test_a_tile_width_changes_no_number_but_the_order_of_the_sum(tile_f):
    x, wg, wu, wd = layer(8, 4, jnp.float32)
    combine, load = routing(8, 4, "all")
    want = _moe_held_xla(x, combine, load, wg, wu, wd)
    got = _moe_held_pallas(x, combine, load, wg, wu, wd, tile_f=tile_f,
                           interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5,
                               atol=1e-5)


@pytest.mark.parametrize("load,ids,n", [
    ([0, 0, 0, 0], [0, 0, 0, 0], 0),
    ([0, 3, 0, 1], [1, 3, 0, 0], 2),
    ([2, 1, 1, 5], [0, 1, 2, 3], 4),
    ([0, 0, 0, 7], [3, 0, 0, 0], 1),
])
def test_the_touched_experts_come_first_in_their_own_order(load, ids, n):
    got_ids, got_n = _touched(jnp.asarray(load, jnp.int32))
    assert got_ids.dtype == jnp.int32 and got_n.shape == (1,)
    assert int(got_n[0]) == n
    assert list(np.asarray(got_ids))[:n] == ids[:n]
    # the places past the list are never read but by an empty walk's
    # guard: they name an expert that exists
    assert list(np.asarray(got_ids)) == ids


def test_what_the_kernel_does_not_take_takes_the_conditionals(monkeypatch):
    """`auto` on the chip: the kernel up to `MAX_TOKENS` tokens and for
    widths that are whole lanes, the conditionals past that, the
    conditionals on every other platform."""
    from lua_mapreduce_tpu.ops import moe_held as module
    x, wg, wu, wd = layer(MAX_TOKENS + 8, 4, jnp.float32)
    combine, load = routing(MAX_TOKENS + 8, 4, "all")
    called = []
    monkeypatch.setattr(module, "_moe_held_pallas",
                        lambda *a, **kw: called.append(kw) or a[0])
    moe_held(x, combine, load, wg, wu, wd)
    assert called == []                         # the CPU
    monkeypatch.setattr(ops, "default_backend", lambda op=None: "pallas")
    moe_held(x, combine, load, wg, wu, wd)
    assert called == []                         # too many tokens
    few = (x[:MAX_TOKENS], combine[:MAX_TOKENS], load)
    moe_held(*few, wg[:, :, :200], wu[:, :, :200], wd[:, :200])
    moe_held(few[0][:, :100], *few[1:], wg[:, :100], wu[:, :100],
             wd[:, :, :100])
    assert called == []                         # no whole lanes
    moe_held(*few, wg, wu, wd)
    assert called == [{"interpret": False}]


@pytest.mark.parametrize("shape,tile_f", [
    ((16, 4096, 2048, 32), 128),        # the sarvam-105b cell: a megabyte
    ((8, 7168, 2048, 16), 128),         # the DeepSeek-V3.2-Exp cell
    ((64, 4096, 2048, 32), 128),        # the most tokens a call takes
    ((8, 1024, 1536, 4), 512),          # the widest divisor in a megabyte
    ((8, 1024, 384, 4), 384),
])
def test_a_tile_is_chosen_by_its_bytes(shape, tile_f):
    t, d, f, count = shape
    assert _tiles(t, d, f, count, 2) == tile_f
    assert _vmem_bytes(t, d, tile_f, count, 2) <= _VMEM_BUDGET
