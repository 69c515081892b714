"""Training harness tests: the TPU-native DP trainer and the MapReduce-
packaged digits example (the APRIL-ANN workload, SURVEY.md §3.5), plus the
grad-equivalence and checkpoint-resume guarantees."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from lua_mapreduce_tpu.coord.jobstore import MemJobStore
from lua_mapreduce_tpu.coord.persistent_table import PersistentTable
from lua_mapreduce_tpu.engine.contract import TaskSpec
from lua_mapreduce_tpu.engine.local import LocalExecutor
from lua_mapreduce_tpu.models.mlp import accuracy, init_mlp, nll_loss
from lua_mapreduce_tpu.parallel.mesh import host_mesh
from lua_mapreduce_tpu.store.memfs import MemStore
from lua_mapreduce_tpu.train import checkpoint as ckpt
from lua_mapreduce_tpu.train.data import make_digits
from lua_mapreduce_tpu.train.harness import DataParallelTrainer, TrainConfig


@pytest.fixture(scope="module")
def mesh():
    return host_mesh(8)


@pytest.fixture(scope="module")
def digits():
    return make_digits(seed=0)


def test_grad_accum_matches_big_batch(mesh, digits):
    """grad_accum=4 (microbatch scan, one optimizer update) must produce
    the same step as the whole batch at once — mean of equal-size
    microbatch grads ≡ grad of the mean loss."""
    x, y = digits[0][:128], digits[1][:128]
    params = init_mlp(jax.random.PRNGKey(7))

    losses, stepped = {}, {}
    for accum in (1, 4):
        tr = DataParallelTrainer(nll_loss, params, mesh,
                                 TrainConfig(grad_accum=accum))
        losses[accum] = tr.step(x, y)
        stepped[accum] = jax.tree.map(np.asarray, tr.params)
    assert abs(losses[1] - losses[4]) < 1e-6
    for k in stepped[1]:
        np.testing.assert_allclose(stepped[1][k], stepped[4][k],
                                   rtol=1e-5, atol=1e-6, err_msg=k)

    with pytest.raises(ValueError, match="grad_accum"):
        DataParallelTrainer(nll_loss, params, mesh,
                            TrainConfig(grad_accum=3)).step(x, y)


def test_dp_step_equals_single_device_step(mesh, digits):
    """pmean of per-shard grads == full-batch grad: one mesh step must
    match one plain optax step bit-for-bit (up to float assoc)."""
    x, y = digits[0][:128], digits[1][:128]
    params = init_mlp(jax.random.PRNGKey(42))

    tr = DataParallelTrainer(nll_loss, params, mesh, TrainConfig())
    tr.step(x, y)

    opt = optax.chain(optax.add_decayed_weights(TrainConfig.weight_decay),
                      optax.sgd(TrainConfig.learning_rate,
                                momentum=TrainConfig.momentum))
    state = opt.init(params)
    grads = jax.grad(nll_loss)(params, jnp.asarray(x), jnp.asarray(y))
    updates, _ = opt.update(grads, state, params)
    expected = optax.apply_updates(params, updates)

    for k in params:
        np.testing.assert_allclose(np.asarray(tr.params[k]),
                                   np.asarray(expected[k]),
                                   rtol=2e-5, atol=2e-6)


def _stamped_trainer_step(tr, accum):
    """The trainer's step as it was until PR 29, kept in this file alone:
    every gradient leaf (and, where microbatches accumulate, the scan's
    carry and every microbatch's loss and gradients) went through one
    more `pmean` over each mesh axis, an identity on a value that is
    already the same on every device, to tell the vma checker so."""
    from jax import lax, shard_map
    from jax.sharding import PartitionSpec as P
    axes = tuple(tr.mesh.axis_names)

    def stamped(tree, over=axes):
        def stamp(v):
            for a in over:
                v = lax.pmean(v, a)
            return v
        return jax.tree.map(stamp, tree)

    def shard_step(params, x, y):
        def global_loss(p, xm, ym):
            return lax.pmean(tr.loss_fn(p, xm, ym), tr.axis)

        if accum == 1:
            loss, grads = jax.value_and_grad(global_loss)(params, x, y)
        else:
            def body(carry, mb):
                l, g = stamped(jax.value_and_grad(global_loss)(params, *mb))
                return (carry[0] + l, jax.tree.map(jnp.add, carry[1], g)), None

            micro = tuple(a.reshape(accum, -1, *a.shape[1:]) for a in (x, y))
            (loss, grads), _ = lax.scan(body, stamped(
                (jnp.float32(0.0), jax.tree.map(jnp.zeros_like, params))),
                micro)
            loss, grads = jax.tree.map(lambda v: v / accum, (loss, grads))
        return loss, stamped(grads, (tr.axis,))

    @jax.jit
    def step(params, opt_state, x, y):
        loss, grads = shard_map(
            shard_step, mesh=tr.mesh, in_specs=(P(), P(tr.axis), P(tr.axis)),
            out_specs=(P(), P()))(params, x, y)
        updates, opt_state = tr.optimizer.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss

    return step


@pytest.mark.parametrize("devices,accum", [(2, 1), (2, 4), (8, 1), (8, 4)])
def test_trainer_step_without_the_stamp_is_the_stamped_one(
        digits, devices, accum):
    """One step's parameters, optimizer state and loss against those of
    the step built the old way, to float32 rounding (the loss, which met
    no stamp without microbatches, to the bit). Not every bit, for two
    reasons that are the old step's: on eight devices its mean was a
    running sum of eight equal values (3x, 5x, 6x and 7x need not be
    float32 numbers) and a division by 8, not quite the identity it was
    held to be; and on any mesh the CPU's compiler fuses the update
    another way when no all-reduce stands right before it (2 devices,
    where the mean is exact: 16 of 32768 updated weights, one ulp)."""
    mesh = host_mesh(devices)
    x, y = digits[0][:128], digits[1][:128]
    tr = DataParallelTrainer(nll_loss, init_mlp(jax.random.PRNGKey(7)),
                             mesh, TrainConfig(grad_accum=accum))
    xs, ys = tr._shard_batch(x, y)
    want = _stamped_trainer_step(tr, accum)(tr.params, tr.opt_state, xs, ys)
    loss = tr.step(x, y)
    got = (tr.params, tr.opt_state, loss)
    assert jax.tree.structure(got[:2]) == jax.tree.structure(want[:2])
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=1e-6, atol=1e-8)
    if accum == 1:
        assert loss == float(want[2])
    assert np.isfinite(loss)


def test_fit_learns_and_checkpoints(mesh, digits):
    x_tr, y_tr, x_va, y_va = digits
    params = init_mlp(jax.random.PRNGKey(0))
    tr = DataParallelTrainer(nll_loss, params, mesh,
                             TrainConfig(max_epochs=6, patience=6))
    store = MemStore()
    conf = PersistentTable("conf", MemJobStore())
    out = tr.fit(x_tr, y_tr, x_va, y_va, checkpoint_store=store, conf=conf)
    assert out["best_val"] < 0.5
    assert float(accuracy(tr.params, x_va, y_va)) > 0.9
    assert store.exists("model.ckpt")
    assert conf["epoch"] >= 1 and conf["best_val"] == out["best_val"]

    # checkpoint round-trips exactly
    loaded = ckpt.load_pytree(store, "model.ckpt", params)
    best = out["best_epoch"]
    assert best >= 1
    for k in params:
        assert loaded[k].shape == np.asarray(params[k]).shape


def test_fit_resumes_from_conf(mesh, digits):
    """Restart parity (SURVEY.md §5 checkpoint/resume): a second fit() with
    the same conf+store continues from the recorded epoch."""
    x_tr, y_tr, x_va, y_va = digits
    store = MemStore()
    jobstore = MemJobStore()
    conf = PersistentTable("conf", jobstore)
    tr = DataParallelTrainer(nll_loss, init_mlp(jax.random.PRNGKey(0)), mesh,
                             TrainConfig(max_epochs=3, patience=10))
    tr.fit(x_tr, y_tr, x_va, y_va, checkpoint_store=store, conf=conf)
    assert conf["epoch"] == 3

    # the resume checkpoint must hold LAST-epoch params AND optimizer
    # state — resuming from the best-only file would rewind training and
    # zero the momentum buffers
    assert store.exists("model.ckpt.resume")
    saved_params, saved_opt = ckpt.load_pytree(
        store, "model.ckpt.resume", (tr.params, tr.opt_state))
    for k in tr.params:
        np.testing.assert_array_equal(np.asarray(saved_params[k]),
                                      np.asarray(tr.params[k]))
    momentum = [np.asarray(x) for x in jax.tree.leaves(saved_opt)]
    assert any(np.any(m != 0) for m in momentum)

    tr2 = DataParallelTrainer(nll_loss, init_mlp(jax.random.PRNGKey(9)), mesh,
                              TrainConfig(max_epochs=5, patience=10))
    conf2 = PersistentTable("conf", jobstore)
    out2 = tr2.fit(x_tr, y_tr, x_va, y_va, checkpoint_store=store,
                   conf=conf2)
    # resumed at epoch 4, ran 4 and 5 only
    assert [h["epoch"] for h in out2["history"]] == [4, 5]


SMALL = {"sizes": (64, 32, 10), "n_shards": 4, "bunch": 64,
         "max_steps": 30, "patience": 30}


@pytest.mark.heavy
def test_mapreduce_digits_example_learns():
    """The six-function DP-SGD loop (APRIL-ANN analog) on the host engine:
    loops until convergence/max and the validation loss drops."""
    import examples.digits.mr_train as mr
    model_store = "mem:digits-e2e"
    spec = TaskSpec(taskfn="examples.digits.mr_train",
                    mapfn="examples.digits.mr_train",
                    partitionfn="examples.digits.mr_train",
                    reducefn="examples.digits.mr_train",
                    finalfn="examples.digits.mr_train",
                    init_args={**SMALL, "model_store": model_store},
                    storage="mem:digits-e2e-shuffle")
    stats = LocalExecutor(spec, max_iterations=100).run()
    meta = mr.read_meta(model_store)
    assert meta["step"] == len(stats.iterations)
    assert meta["step"] >= 5
    # untrained small MLP starts near ln(10) ≈ 2.3 val NLL; must improve a lot
    assert meta["best_val"] < 1.0
    assert meta["finished"]


def test_mapreduce_step_matches_direct_math(tmp_path):
    """Exact parity: one MapReduce iteration == the same update computed
    directly (grad sum over shards, 1/sqrt(count) smoothing, momentum SGD)."""
    import examples.digits.mr_train as mr
    from lua_mapreduce_tpu.store.router import get_storage_from

    model_store = "mem:digits-parity"
    args = {"sizes": (32, 16, 10), "n_shards": 2, "bunch": 16,
            "max_steps": 1, "patience": 99, "model_store": model_store,
            "seed": 3}
    spec = TaskSpec(taskfn="examples.digits.mr_train",
                    mapfn="examples.digits.mr_train",
                    partitionfn="examples.digits.mr_train",
                    reducefn="examples.digits.mr_train",
                    finalfn="examples.digits.mr_train",
                    init_args=args, storage="mem:digits-parity-shuffle")
    # snapshot initial state before running
    store = get_storage_from(model_store)
    state0 = mr._load_state(store)
    data = make_digits(seed=3, dim=32)

    LocalExecutor(spec, max_iterations=2).run()
    state1 = mr._load_state(store)

    # recompute expected update
    x_tr, y_tr = data[0], data[1]
    grads_sum = {k: np.zeros_like(np.asarray(v))
                 for k, v in state0["params"].items()}
    for shard in range(2):
        rng = np.random.RandomState(1000 + 0 + shard)   # step=0
        idx = rng.randint(0, len(x_tr), 16)
        g = jax.grad(nll_loss)(state0["params"], jnp.asarray(x_tr[idx]),
                               jnp.asarray(y_tr[idx]))
        for k in grads_sum:
            grads_sum[k] += np.asarray(g[k])
    for k, p in state0["params"].items():
        smoothed = grads_sum[k] / np.sqrt(2) + 1e-5 * np.asarray(p)
        vel = -0.05 * smoothed                          # momentum starts at 0
        np.testing.assert_allclose(np.asarray(state1["params"][k]),
                                   np.asarray(p) + vel, rtol=1e-5, atol=1e-6)


def test_checkpoint_roundtrip_all_backends(tmp_path):
    from lua_mapreduce_tpu.store.objectfs import ObjectStore
    from lua_mapreduce_tpu.store.sharedfs import SharedStore

    tree = {"W": np.arange(12, dtype=np.float32).reshape(3, 4),
            "nested": {"b": np.array([1.5, -2.5], dtype=np.float64),
                       "i": np.array([1, 2, 3], dtype=np.int32)}}
    for store in (MemStore(), SharedStore(str(tmp_path / "s")),
                  ObjectStore(str(tmp_path / "o"))):
        ckpt.save_pytree(store, "t.ckpt", tree)
        out = ckpt.load_pytree(store, "t.ckpt", tree)
        for va, vb in zip(jax.tree.leaves(tree), jax.tree.leaves(out)):
            np.testing.assert_array_equal(va, vb)
            assert np.asarray(va).dtype == np.asarray(vb).dtype


def test_profile_dir_captures_trace(mesh, digits, tmp_path):
    """TrainConfig.profile_dir traces the second epoch (SURVEY §5
    tracing, hot-path half) — the trace directory must be non-empty."""
    import os

    from lua_mapreduce_tpu.models.mlp import init_mlp, nll_loss

    x_tr, y_tr, _, _ = digits
    pdir = str(tmp_path / "trace")
    tr = DataParallelTrainer(
        nll_loss, init_mlp(jax.random.PRNGKey(0)), mesh,
        TrainConfig(batch_size=64, profile_dir=pdir))
    rng = np.random.RandomState(0)
    tr.run_epoch(x_tr[:256], y_tr[:256], rng)
    assert not os.path.exists(pdir) or not os.listdir(pdir)
    tr.run_epoch(x_tr[:256], y_tr[:256], rng)
    found = [os.path.join(r, f) for r, _, fs in os.walk(pdir) for f in fs]
    assert found, "second epoch should have written a profiler trace"


@pytest.mark.heavy
def test_digits_sheet_accuracy_both_paths_agree():
    """The APRIL-ANN capability end to end WITH ACCURACY (VERDICT r3
    item 5): train on the checked-in full-size digits sheet (the
    reference's exact 16x16/800-200 contract) through both the
    TPU-native trainer and the six-function MapReduce loop; both must
    clear the validation-accuracy bar and agree. Smaller budgets than
    the committed artifact (benchmarks/results/digits_e2e.json) — same
    code path."""
    from benchmarks.digits_e2e import run

    out = run(native_steps=150, mr_steps=30, target=0.9)
    assert out["tpu_native_path"]["val_accuracy"] >= 0.9, out
    assert out["mapreduce_path"]["val_accuracy"] >= 0.9, out
    assert out["agree_within"] <= 0.05, out


class TestAsyncCheckpoint:
    def test_background_save_round_trips(self):
        from lua_mapreduce_tpu.store.memfs import MemStore

        store = MemStore()
        tree = {"w": jnp.arange(12.0).reshape(3, 4),
                "b": jnp.ones((4,), jnp.bfloat16)}
        ac = ckpt.AsyncCheckpoint()
        ac.submit(store, "a.ckpt", tree)
        ac.wait()
        got = ckpt.load_pytree(store, "a.ckpt", tree)
        np.testing.assert_array_equal(np.asarray(got["w"], np.float32),
                                      np.asarray(tree["w"], np.float32))
        assert got["b"].dtype == jnp.bfloat16

    def test_snapshot_is_taken_at_submit_time(self):
        """The write must capture the tree AS SUBMITTED even if the
        caller's arrays are replaced (donated/overwritten) before the
        background write finishes."""
        from lua_mapreduce_tpu.store.memfs import MemStore

        store = MemStore()
        ac = ckpt.AsyncCheckpoint()
        tree = {"x": jnp.zeros((256, 256))}
        ac.submit(store, "s.ckpt", tree)
        tree["x"] = jnp.ones((256, 256))       # caller moves on
        ac.wait()
        got = ckpt.load_pytree(store, "s.ckpt", tree)
        assert float(np.asarray(got["x"]).max()) == 0.0

    def test_wait_reraises_background_failure(self):
        class BrokenStore:
            def builder(self):
                raise IOError("disk gone")

        ac = ckpt.AsyncCheckpoint()
        ac.submit(BrokenStore(), "x.ckpt", {"a": jnp.zeros(3)})
        with pytest.raises(RuntimeError, match="async checkpoint"):
            ac.wait()
        ac.wait()          # error is consumed; idle wait is clean

    def test_serializes_overlapping_submits(self):
        from lua_mapreduce_tpu.store.memfs import MemStore

        store = MemStore()
        ac = ckpt.AsyncCheckpoint()
        for i in range(5):
            ac.submit(store, "r.ckpt", {"i": jnp.full((64,), float(i))})
        ac.wait()
        got = ckpt.load_pytree(store, "r.ckpt", {"i": jnp.zeros(64)})
        assert float(np.asarray(got["i"])[0]) == 4.0
