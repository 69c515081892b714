"""Traffic kind `train`: back-to-back calls of the program's
`make_train_step` on a (dp, sp) mesh, a fresh batch of token ids from the
seed each step, placed on the device before its step is dispatched."""

from __future__ import annotations

import functools
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from perfbench import compare, counts, harness, reference, weights


def program_config(cfg: dict):
    """The published keys as the program's `TransformerConfig`."""
    from lua_mapreduce_tpu.models.transformer import TransformerConfig
    if cfg["rms_norm_eps"] != 1e-5:
        raise SystemExit("the program's RMSNorm epsilon is fixed at 1e-5")
    return TransformerConfig(
        vocab=cfg["vocab_size"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"],
        n_layers=cfg["num_hidden_layers"], d_ff=cfg["intermediate_size"],
        max_seq=cfg["max_position_embeddings"],
        n_kv_heads=cfg["num_key_value_heads"], rope=True,
        rope_base=float(cfg["rope_theta"]), norm="rms", ffn="swiglu",
        window=cfg.get("sliding_window") or 0)


@functools.partial(jax.jit, static_argnames=("table",))
def _delta_norms(params: dict, key, table: tuple) -> dict:
    """Norm of every leaf's change from the weights the seed gives."""
    out = {}
    for index, name, shape, std in table:
        start = weights.make_leaf(key, index, shape, std, jnp.bfloat16)
        diff = params[name].astype(jnp.float32) - start.astype(jnp.float32)
        out[name] = jnp.sqrt(jnp.sum(jnp.square(diff)))
    return out


def batch_of(cfg: dict, traffic: dict, seed: int, index: int) -> tuple:
    """(tokens, targets) of step ``index`` of the run, from the seed."""
    rows = weights.token_rows(seed, index, traffic["batch"],
                              traffic["seq_len"] + 1, cfg["vocab_size"])
    return rows[:, :-1], rows[:, 1:]


class Trainer:
    """The compiled step with its state: set-up drives it through its
    first steps, and the window goes on with this same object."""

    def __init__(self, cell, seed: int, devices: list):
        import optax
        from jax.sharding import Mesh

        from lua_mapreduce_tpu.models import transformer as tfm
        self.cfg, self.traffic, self.seed = cell.config, cell.traffic, seed
        t = self.traffic
        self.mesh = Mesh(np.array(devices).reshape(t["dp"], t["sp"]),
                         ("dp", "sp"))
        self.adam = dict(t["adam"])
        opt = optax.adam(self.adam["lr"], b1=self.adam["b1"],
                         b2=self.adam["b2"], eps=self.adam["eps"])
        self.f32_master = t["f32_master"]
        if self.f32_master:
            from lua_mapreduce_tpu.train.precision import with_f32_master
            opt = with_f32_master(opt)
        self.params = tfm.shard_params_moe(
            weights.make_params(self.cfg, seed), self.mesh)
        self.opt_state = tfm.init_opt_state(opt, self.params, self.mesh)
        self.step = tfm.make_train_step(program_config(self.cfg), self.mesh,
                                        opt, attn=t["attn"])
        self._shard = functools.partial(tfm.shard_batch, self.mesh)
        self.losses = []

    def batch(self, index: int) -> tuple:
        return batch_of(self.cfg, self.traffic, self.seed, index)

    def one(self, index: int):
        """Place batch ``index``; the returned call runs its step and
        waits for the loss."""
        tokens, targets = self._shard(*map(jnp.asarray, self.batch(index)))

        def call():
            self.params, self.opt_state, loss = self.step(
                self.params, self.opt_state, tokens, targets)
            self.losses.append(jax.block_until_ready(loss))
        return call

    def first_gradient(self) -> tuple:
        """After one step Adam's first moment is (1 - b1) times the
        gradient that the optimizer got: every leaf's norm, and a sample
        of its rows (on the host)."""
        (adam_state,) = [s for s in jax.tree.leaves(
            self.opt_state, is_leaf=lambda s: hasattr(s, "mu"))
            if hasattr(s, "mu")]
        scale = 1.0 / (1.0 - self.adam["b1"])
        norms = {k: scale * float(v)
                 for k, v in reference.leaf_norms(adam_state.mu).items()}
        sample = {k: scale * np.asarray(v)
                  for k, v in reference.sample_rows(adam_state.mu).items()}
        return norms, sample

    def change_norms(self) -> dict:
        """Of the parameters as the optimizer holds them: the float32
        masters where it keeps them, else the working copy."""
        held = self.opt_state[0] if self.f32_master else self.params
        norms = _delta_norms(held, weights.seed_key(self.seed),
                             weights.indexed(self.cfg))
        return {k: float(v) for k, v in norms.items()}

    def free(self):
        self.params = self.opt_state = self.step = None


def first_steps(trainer: Trainer, followed: int) -> dict:
    """Drive the first ``followed`` steps through the window's own call
    and feed, and read what the comparison needs from the state."""
    prog = {"loss": []}
    for n in range(followed):
        trainer.one(n)()
        if n == 0:
            prog["grad1"], prog["sample1"] = trainer.first_gradient()
    prog["loss"] = [float(x) for x in trainer.losses[:followed]]
    prog["delta"] = trainer.change_norms()
    return prog


def run(cell, seed: int, seconds: float, trace: bool, devices: list,
        clock, log, make_trainer=Trainer, work_dir=None) -> tuple:
    t = cell.traffic
    followed = t["followed_steps"]
    trainer = make_trainer(cell, seed, devices)
    jax.block_until_ready(trainer.params)
    print(f"set-up: weights and state by {clock():.2f} s", file=sys.stderr)
    prog = first_steps(trainer, followed)
    setup_s = clock()
    built_before = log.programs

    def one(i):
        return trainer.one(followed + i)

    if trace:
        loop, trace_file = harness.traced(
            lambda: harness.measured_loop(one, float("inf"),
                                          at_most=t["traced_calls"]),
            work_dir)
    else:
        loop, trace_file = harness.measured_loop(one, seconds), None
    built = log.programs - built_before
    steps = len(loop["times"])
    tokens = steps * t["batch"] * t["seq_len"]
    window_losses = [float(x) for x in trainer.losses[followed:]]
    peak = harness.memory_peak_bytes(devices)
    trainer.free()

    print(f"steps {steps} window_s {loop['window_s']:.4f} of which feeding "
          f"{loop['window_s'] - sum(loop['times']):.4f} slowest step "
          f"{int(np.argmax(loop['times']))} at {1e3 * max(loop['times']):.1f} "
          f"ms programs built in "
          f"the window {built} first losses {prog['loss']} last loss "
          f"{window_losses[-1]}", file=sys.stderr)
    t0 = time.perf_counter()
    ref = reference.train_readings(
        cell.config, seed, [trainer.batch(n) for n in range(followed)],
        trainer.adam)
    print(f"reference took {time.perf_counter() - t0:.2f} s", file=sys.stderr)
    readings, notes = compare.train(prog, ref)
    print(f"comparison notes {notes}", file=sys.stderr)
    measured = {
        "train_tokens_per_s": tokens / loop["window_s"],
        "step_p95_ms": 1e3 * harness.percentile_nearest_rank(
            loop["times"], 95),
        "setup_s": setup_s,
    }
    context = {"cell": cell, "loop": loop, "calls": steps,
               "programs_built": built, "trace_file": trace_file,
               "chips": len(devices), "device": devices[0]}
    failed = int(np.sum(~np.isfinite(window_losses)))
    return measured, context, readings, {"attempted": steps, "failed": failed,
                                         "memory_peak_bytes": peak}
