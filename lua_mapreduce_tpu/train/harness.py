"""Data-parallel training: the TPU-native hot path.

The reference's training loop costs one full MapReduce cycle per optimizer
step — taskfn → 4 map jobs → shuffle files → 10 reduce jobs → finalfn —
with every transition a MongoDB round trip (SURVEY.md §3.5). Here the same
dataflow (shard grads → all-reduce → optimizer step → loop) is ONE jitted
SPMD program per step, and whole epochs run inside ``lax.scan`` with zero
coordination-store traffic (the BASELINE.md north star). The coordinator
only sees checkpoints and the early-stopping verdict — exactly the split
SURVEY.md §7 prescribes ("iteration control moves into the jitted loop").

Mapping to the reference example:
    map    = per-device grad on its batch shard        (common.lua:85-104)
    reduce = pmean over the dp axis                    (common.lua:112-137)
    final  = optax update + validation + early stop    (common.lua:144-202)
    state  = persistent_table + checkpoint file        (common.lua:57-77)
"""

from __future__ import annotations

import contextlib
import dataclasses
import time
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax import lax, shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

from lua_mapreduce_tpu.parallel import zero1 as _z1
from lua_mapreduce_tpu.train import checkpoint as ckpt
from lua_mapreduce_tpu.train.accum import accum_value_and_grad


@dataclasses.dataclass
class TrainConfig:
    """Hyperparameters (structure = the reference example's,
    examples/APRIL-ANN/init.lua:16-20: lr/momentum/weight-decay, max 40
    epochs, bunch of 128; early stopping via holdout validation). The
    reference's lr=0.4/momentum=0.1 are tuned to its APRIL-ANN loss
    scaling and diverge on plain mean-NLL; these defaults are stable."""
    learning_rate: float = 0.05
    momentum: float = 0.9
    weight_decay: float = 1e-5      # init.lua weight_decay
    batch_size: int = 128           # "bunch_size" init.lua:127-141
    max_epochs: int = 40            # init.lua max epochs
    patience: int = 10              # train_holdout_validation analog
    seed: int = 1234
    # gradient accumulation: >1 splits each per-device batch tile into
    # this many microbatches folded in a lax.scan before ONE optimizer
    # update — same numbers as the big batch (mean of microbatch grads ≡
    # grad of the mean loss), activation memory ÷ grad_accum. The
    # standard lever when the target batch doesn't fit HBM.
    grad_accum: int = 1
    # ZeRO-1: shard the optimizer state over the dp axis
    # (parallel/zero1.py) — gradients reduce-scatter, each rank updates
    # its 1/n_dp chunk, chunks all-gather back. Same wire traffic as
    # the all-reduce, optimizer memory / n_dp. Elementwise optimizers
    # only.
    zero1: bool = False
    # device-side tracing (the SURVEY §5 tracing subsystem's hot-path
    # half — JobTimes covers the host engine): when set, the SECOND
    # run_epoch call (the first is compile-skewed) is captured with
    # jax.profiler.trace into this directory, viewable in XProf
    profile_dir: Optional[str] = None


class DataParallelTrainer:
    """SPMD trainer over a mesh's ``dp`` axis.

    ``loss_fn(params, x, y) -> scalar`` must be JAX-traceable. Parameters
    are replicated; batches are sharded on the leading axis; gradients are
    ``pmean``'d over ICI inside the jitted step.
    """

    def __init__(self, loss_fn: Callable, params: Any, mesh,
                 config: Optional[TrainConfig] = None, axis: str = "dp",
                 optimizer=None):
        self.loss_fn = loss_fn
        self.mesh = mesh
        self.axis = axis
        self.config = config or TrainConfig()
        c = self.config
        self.optimizer = optimizer if optimizer is not None else optax.chain(
            optax.add_decayed_weights(c.weight_decay),
            optax.sgd(c.learning_rate, momentum=c.momentum))
        # copy before device_put: the step donates its param buffers, and
        # device_put to a replicated sharding may alias the caller's arrays
        self.params = jax.device_put(
            jax.tree.map(lambda x: jnp.array(x, copy=True), params),
            NamedSharding(mesh, P()))                  # replicated
        if self.config.zero1:
            self.opt_state = _z1.init_state(self.optimizer, self.params,
                                            mesh, dp_axis=axis)
        else:
            self.opt_state = jax.device_put(
                self.optimizer.init(self.params), NamedSharding(mesh, P()))
        self._step = self._build_step()
        self._epoch = self._build_epoch()
        self._steps_cache: Dict[int, Callable] = {}
        self._epoch_calls = 0

    # -- jitted single step -------------------------------------------------

    def _build_step(self):
        if self.config.zero1:
            return self._build_step_zero1()
        axis, loss_fn, optimizer = self.axis, self.loss_fn, self.optimizer
        accum = self.config.grad_accum

        def step(params, opt_state, x, y):
            def shard_step(params, x, y):
                # differentiate the *global* (pmean'd) loss: AD inserts the
                # gradient all-reduce itself, the reference's reducefn sum
                # (common.lua:112-137), and types its result as unvarying
                # over the axis, so out_specs=P() passes the vma check as
                # it is. (An explicit post-grad pmean would double-count
                # under shard_map's auto-psum of replicated-input
                # cotangents.)
                def global_loss(p, xm, ym):
                    return lax.pmean(loss_fn(p, xm, ym), axis)

                if accum == 1:
                    loss, grads = jax.value_and_grad(global_loss)(
                        params, x, y)
                else:
                    # microbatch fold: one scan keeps a single
                    # microbatch's activations live at a time (shared
                    # implementation, train/accum.py)
                    loss, grads = accum_value_and_grad(
                        global_loss, params, (x, y), accum)
                return loss, grads

            loss, grads = shard_map(
                shard_step, mesh=self.mesh,
                in_specs=(P(), P(axis), P(axis)), out_specs=(P(), P()),
            )(params, x, y)
            updates, opt_state = optimizer.update(grads, opt_state, params)
            params = optax.apply_updates(params, updates)
            return params, opt_state, loss

        return jax.jit(step, donate_argnums=(0, 1))

    def _build_step_zero1(self):
        """The ZeRO-1 step: the optimizer runs INSIDE shard_map on this
        rank's parameter chunks (parallel/zero1.py); the opt state must
        come from zero1.init_state (the constructor does)."""
        axis, loss_fn, optimizer = self.axis, self.loss_fn, self.optimizer
        accum = self.config.grad_accum
        n_dp = self.mesh.shape[axis]

        def step(params, opt_state, x, y):
            def shard_step(params, opt_state, x, y):
                if accum == 1:
                    loss, grads = jax.value_and_grad(loss_fn)(
                        params, x, y)
                else:
                    loss, grads = accum_value_and_grad(
                        loss_fn, params, (x, y), accum)
                params, opt_state = _z1.update_chunks(
                    optimizer, params, grads, opt_state, axis, n_dp)
                return params, opt_state, lax.pmean(loss, axis)

            st_specs = _z1.state_specs(opt_state, axis)
            return shard_map(
                shard_step, mesh=self.mesh,
                in_specs=(P(), st_specs, P(axis), P(axis)),
                out_specs=(P(), st_specs, P()),
                check_vma=False)(params, opt_state, x, y)

        return jax.jit(step, donate_argnums=(0, 1))

    def step(self, x, y) -> float:
        """One optimizer step (one reference "iteration", SURVEY.md §3.5)."""
        x, y = self._shard_batch(x, y)
        self.params, self.opt_state, loss = self._step(
            self.params, self.opt_state, x, y)
        return float(loss)

    # -- jitted whole epoch (scan over batches, zero host round-trips) ------

    def _build_epoch(self):
        step = self._step

        def epoch(params, opt_state, xs, ys):
            def body(carry, batch):
                params, opt_state = carry
                x, y = batch
                params, opt_state, loss = step(params, opt_state, x, y)
                return (params, opt_state), loss

            (params, opt_state), losses = lax.scan(
                body, (params, opt_state), (xs, ys))
            return params, opt_state, losses

        return jax.jit(epoch, donate_argnums=(0, 1))

    def _build_steps_on_batch(self, n_steps: int):
        step = self._step

        def steps(params, opt_state, x, y):
            def body(carry, _):
                params, opt_state = carry
                params, opt_state, loss = step(params, opt_state, x, y)
                return (params, opt_state), loss

            (params, opt_state), losses = lax.scan(
                body, (params, opt_state), None, length=n_steps)
            return params, opt_state, losses

        return jax.jit(steps, donate_argnums=(0, 1))

    def run_steps(self, x, y, n_steps: int):
        """``n_steps`` optimizer steps on ONE fixed batch inside a single
        jitted scan. The batch stays device-resident across steps, so this
        is the pure compute hot loop — what MFU measurement needs (and the
        extreme case of the zero-coordination north star: not even data
        loading between steps). Returns the per-step losses."""
        x, y = self._shard_batch(x, y)
        fn = self._steps_cache.get(n_steps)
        if fn is None:
            fn = self._steps_cache[n_steps] = \
                self._build_steps_on_batch(n_steps)
        self.params, self.opt_state, losses = fn(
            self.params, self.opt_state, x, y)
        return losses

    def run_epoch(self, x: np.ndarray, y: np.ndarray,
                  rng: np.random.RandomState) -> float:
        """Shuffle, batch, and run one full epoch inside lax.scan."""
        c = self.config
        n = (len(x) // c.batch_size) * c.batch_size
        order = rng.permutation(len(x))[:n]
        xs = x[order].reshape(-1, c.batch_size, *x.shape[1:])
        ys = y[order].reshape(-1, c.batch_size, *y.shape[1:])
        xs, ys = self._shard_batch(xs, ys, batched=True)
        self._epoch_calls += 1
        trace = (jax.profiler.trace(c.profile_dir)
                 if c.profile_dir is not None and self._epoch_calls == 2
                 else contextlib.nullcontext())
        with trace:
            self.params, self.opt_state, losses = self._epoch(
                self.params, self.opt_state, xs, ys)
            return float(jnp.mean(losses))   # forced inside the trace

    def _shard_batch(self, x, y, batched: bool = False):
        dim = 1 if batched else 0
        n_dp = self.mesh.shape[self.axis]
        rows = x.shape[dim]
        if rows % (n_dp * self.config.grad_accum):
            raise ValueError(
                f"batch of {rows} does not split over {self.axis}={n_dp} "
                f"× grad_accum={self.config.grad_accum}")
        spec = [None] * (dim + 1)
        spec[dim] = self.axis
        sharding = NamedSharding(self.mesh, P(*spec))
        return (jax.device_put(x, sharding), jax.device_put(y, sharding))

    # -- fit loop: validation, early stopping, checkpointing ----------------

    def fit(self, x_train, y_train, x_val, y_val,
            eval_fn: Optional[Callable] = None,
            checkpoint_store=None, checkpoint_name: str = "model.ckpt",
            conf=None, log: Optional[Callable[[str], None]] = None
            ) -> Dict[str, Any]:
        """Train with holdout early stopping (the finalfn role,
        common.lua:144-202). ``conf`` (a PersistentTable) records progress
        across restarts; ``checkpoint_store`` receives the best params."""
        c = self.config
        rng = np.random.RandomState(c.seed)
        eval_fn = eval_fn or (lambda p, x, y: float(self.loss_fn(p, x, y)))
        best_val = float("inf")
        best_epoch = 0
        history = []
        t0 = time.time()

        # two checkpoints: "<name>" holds the best-validation params (the
        # deliverable), "<name>.resume" holds last-epoch params AND
        # optimizer state — resuming from the best-only file would rewind
        # training to the best epoch and zero the momentum buffers
        resume_name = checkpoint_name + ".resume"
        start_epoch = 1
        if conf is not None and "epoch" in conf and checkpoint_store is not None \
                and ckpt.exists(checkpoint_store, resume_name):
            loaded_p, loaded_st = ckpt.load_pytree(
                checkpoint_store, resume_name,
                (self.params, self.opt_state), check_shapes=True,
                check_dtypes=True)
            self.params = jax.device_put(
                loaded_p, NamedSharding(self.mesh, P()))
            if self.config.zero1:
                # keep the optimizer state SHARDED on resume — fully
                # replicating it would materialize the n_dp-fold memory
                # zero1 exists to avoid (code-review r3)
                st_specs = _z1.state_specs(loaded_st, self.axis)
                self.opt_state = jax.tree.map(
                    lambda l, sp: jax.device_put(
                        l, NamedSharding(self.mesh, sp)),
                    loaded_st, st_specs)
            else:
                self.opt_state = jax.device_put(
                    loaded_st, NamedSharding(self.mesh, P()))
            start_epoch = int(conf["epoch"]) + 1
            best_val = float(conf.get("best_val", best_val))
            best_epoch = int(conf.get("best_epoch", 0))

        for epoch in range(start_epoch, c.max_epochs + 1):
            train_loss = self.run_epoch(x_train, y_train, rng)
            val_loss = eval_fn(self.params, x_val, y_val)
            history.append({"epoch": epoch, "train_loss": train_loss,
                            "val_loss": val_loss})
            if log:
                log(f"epoch {epoch}: train={train_loss:.4f} "
                    f"val={val_loss:.4f}")
            if val_loss < best_val:
                best_val, best_epoch = val_loss, epoch
                if checkpoint_store is not None:
                    ckpt.save_pytree(checkpoint_store, checkpoint_name,
                                     self.params)
            if checkpoint_store is not None:
                ckpt.save_pytree(checkpoint_store, resume_name,
                                 (self.params, self.opt_state))
            if conf is not None:
                conf.set({"epoch": epoch, "best_val": best_val,
                          "best_epoch": best_epoch})
                conf.update()
            if epoch - best_epoch >= c.patience:
                break       # early stopping: no "loop"

        return {"epochs": len(history) + start_epoch - 1,
                "best_val": best_val, "best_epoch": best_epoch,
                "history": history, "wall_time": time.time() - t0}
