"""TPU-engine tests on a virtual 8-device CPU mesh.

Covers the SPMD MapReduce executor (keyed psum shape, bucketed all_to_all
shuffle shape), the collectives wrappers, and the dual-path golden
equivalence demanded by SURVEY.md §7 ("the golden-diff harness must run
against both" the traceable and host engines).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map

from lua_mapreduce_tpu.engine.contract import TaskSpec
from lua_mapreduce_tpu.engine.local import LocalExecutor
from lua_mapreduce_tpu.parallel import (ArrayTaskSpec, TpuExecutor, host_mesh)
from lua_mapreduce_tpu.parallel import collectives

VOCAB = 64
NUM_P = 16      # partitions; mesh dp=8 → 2 partitions per device


@pytest.fixture(scope="module")
def mesh():
    return host_mesh(8)


def test_keyed_sum_matches_global(mesh):
    x = np.arange(8 * 4 * 3, dtype=np.float32).reshape(8 * 4, 3)
    spec = ArrayTaskSpec(
        mapfn=lambda shard: {"s": jnp.sum(shard, axis=0),
                             "sq": jnp.sum(shard ** 2, axis=0)})
    ex = TpuExecutor(spec, mesh)
    out = ex.run_keyed(x)
    np.testing.assert_allclose(out["s"], x.sum(axis=0), rtol=1e-6)
    np.testing.assert_allclose(out["sq"], (x ** 2).sum(axis=0), rtol=1e-6)


def test_keyed_mean_and_max(mesh):
    x = np.random.RandomState(0).randn(16, 5).astype(np.float32)
    mean = TpuExecutor(ArrayTaskSpec(
        mapfn=lambda s: jnp.mean(s, axis=0), reduce_op="mean"), mesh)
    np.testing.assert_allclose(mean.run_keyed(x), x.mean(axis=0), rtol=1e-5)
    mx = TpuExecutor(ArrayTaskSpec(
        mapfn=lambda s: jnp.max(s, axis=0), reduce_op="max"), mesh)
    np.testing.assert_allclose(mx.run_keyed(x), x.max(axis=0))


def test_combiner_is_local_prereduction(mesh):
    """combinerfn runs per device before the collective — same contract as
    the map-side combiner (job.lua:92-96)."""
    x = np.ones((8, 4), dtype=np.float32)
    spec = ArrayTaskSpec(
        mapfn=lambda s: s,                       # [1, 4] per device shard
        combinerfn=lambda t: jnp.sum(t, axis=0)) # local fold → [4]
    out = TpuExecutor(spec, mesh).run_keyed(x)
    np.testing.assert_allclose(out, np.full(4, 8.0))


def _token_ids(texts):
    """Feature-hash words into VOCAB bins (static key space for the
    traceable path)."""
    ids = []
    for t in texts:
        for w in t.split():
            ids.append(hash_word(w))
    return np.array(ids, dtype=np.int32)


def hash_word(w: str) -> int:
    h = 2166136261
    for b in w.encode():
        h = ((h ^ b) * 16777619) & 0xFFFFFFFF
    return h % VOCAB


def test_bucketed_shuffle_matches_host_engine(mesh):
    """The dual-path golden test: hash-bucketed wordcount through (a) the
    jitted all_to_all shuffle and (b) the host engine, byte-identical."""
    rng = np.random.RandomState(7)
    words = [f"w{i}" for i in range(200)]
    texts = [" ".join(rng.choice(words, size=50)) for _ in range(32)]

    ids = _token_ids(texts)
    pad = (-len(ids)) % 8
    ids = np.concatenate([ids, np.full(pad, -1, np.int32)])  # -1 = no token

    bins_per_p = VOCAB // NUM_P

    spec = ArrayTaskSpec(
        mapfn=lambda shard: jnp.zeros(VOCAB, jnp.int32).at[shard].add(
            jnp.where(shard >= 0, 1, 0)),
        partitionfn=lambda counts: counts.reshape(NUM_P, bins_per_p),
        num_partitions=NUM_P,
    )
    ex = TpuExecutor(spec, mesh)
    sharded = ex.run_bucketed(ids)               # [NUM_P, bins_per_p] sharded
    tpu_counts = np.asarray(sharded).reshape(-1)

    # host engine, same logical task: keys = bin index, values = 1
    import examples.wordcount  # noqa: F401  (package import side effects none)

    def taskfn(emit):
        for i, t in enumerate(texts):
            emit(i, t)

    def mapfn(key, text, emit):
        for w in text.split():
            emit(hash_word(w), 1)

    def partitionfn(key):
        return key // bins_per_p

    def reducefn(key, values):
        return sum(values)

    host = LocalExecutor(TaskSpec(taskfn=taskfn, mapfn=mapfn,
                                  partitionfn=partitionfn, reducefn=reducefn,
                                  storage="mem:tpu-golden"))
    host.run()
    host_counts = np.zeros(VOCAB, np.int64)
    for k, vs in host.results():
        host_counts[k] = vs[0]

    np.testing.assert_array_equal(tpu_counts, host_counts)
    # and both match straight-line numpy
    golden = np.bincount(_token_ids(texts), minlength=VOCAB)
    np.testing.assert_array_equal(tpu_counts, golden)


def test_bucketed_partition_divisibility_enforced(mesh):
    spec = ArrayTaskSpec(mapfn=lambda s: s,
                         partitionfn=lambda x: x.reshape(6, -1),
                         num_partitions=6)
    with pytest.raises(ValueError, match="multiple"):
        TpuExecutor(spec, mesh).run_bucketed(np.zeros((8, 6), np.float32))


def test_collectives_tree_ops(mesh):
    from jax.sharding import PartitionSpec as P

    x = np.arange(16, dtype=np.float32).reshape(8, 2)

    def body(t):
        return collectives.psum_tree({"a": t}, "dp")["a"]

    f = jax.jit(shard_map(body, mesh=mesh, in_specs=(P("dp"),),
                              out_specs=P()))
    # each shard is [1, 2]; psum keeps the local shape → global [1, 2]
    np.testing.assert_allclose(f(x), x.sum(axis=0, keepdims=True))

    # reduce_scatter: each device keeps its slice of the cross-device sum
    x2 = np.arange(64, dtype=np.float32).reshape(8, 8)

    def body_rs(t):
        return collectives.reduce_scatter_tree(t.reshape(8), "dp")

    f2 = jax.jit(shard_map(body_rs, mesh=mesh, in_specs=(P("dp"),),
                               out_specs=P("dp")))
    np.testing.assert_allclose(np.asarray(f2(x2)).reshape(-1), x2.sum(axis=0))


def test_ppermute_ring_rotates(mesh):
    from jax.sharding import PartitionSpec as P

    x = np.arange(8, dtype=np.float32).reshape(8, 1)

    def body(t):
        return collectives.ppermute_ring(t, "dp", mesh_size=8, shift=1)

    f = jax.jit(shard_map(body, mesh=mesh, in_specs=(P("dp"),),
                              out_specs=P("dp")))
    out = np.asarray(f(x)).reshape(-1)
    # device i's value moved to device i+1 → output is rolled by one
    np.testing.assert_array_equal(out, np.roll(np.arange(8), 1))


def test_run_loop_scan_harness(mesh):
    spec = ArrayTaskSpec(mapfn=lambda s: jnp.sum(s))
    ex = TpuExecutor(spec, mesh)

    def step(state):
        return state + 1.0, state

    final, trace = ex.run_loop(jnp.float32(0), step, n_steps=5)
    assert final == 5.0
    np.testing.assert_allclose(np.asarray(trace), np.arange(5.0))


def test_differentiable_keyed_grads_match_oracle(mesh):
    """Grads flow through the keyed MapReduce primitive — map AND
    cross-device reduction — and equal the single-device oracle."""
    from lua_mapreduce_tpu.parallel.tpu_engine import differentiable_keyed

    rng = np.random.RandomState(0)
    w = jnp.asarray(rng.rand(4, 3), jnp.float32)
    x = jnp.asarray(rng.rand(16, 4), jnp.float32)
    y = jnp.asarray(rng.rand(16, 3), jnp.float32)

    def mapfn(params, shard):
        xs, ys = shard
        pred = xs @ params
        return {"sq": jnp.mean((pred - ys) ** 2)}

    f = differentiable_keyed(mapfn, mesh, axis="dp", reduce_op="mean")

    def loss(params):
        return f(params, (x, y))["sq"]

    def oracle(params):
        return jnp.mean((x @ params - y) ** 2)

    lv, g = jax.value_and_grad(loss)(w)
    ov, og = jax.value_and_grad(oracle)(w)
    np.testing.assert_allclose(float(lv), float(ov), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(g), np.asarray(og), rtol=1e-5)

    # composes under jit too (traced once, no host round trips)
    jitted = jax.jit(jax.grad(loss))
    np.testing.assert_allclose(np.asarray(jitted(w)), np.asarray(og),
                               rtol=1e-5)
