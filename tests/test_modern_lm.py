"""Modern-architecture knobs: RoPE + RMSNorm + SwiGLU (the llama_style
preset), composing with GQA and every execution form. Discipline as
everywhere: each sharded/incremental path golden-diffed against the
single-device oracle."""

import os
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from lua_mapreduce_tpu.models import transformer as tfm
from lua_mapreduce_tpu.models.transformer import _rope
from lua_mapreduce_tpu.parallel.mesh import make_mesh


@pytest.fixture(scope="module")
def mesh():
    return make_mesh(dp=4, mp=2, devices=jax.devices("cpu")[:8],
                     axis_names=("dp", "sp"))


@pytest.fixture(scope="module")
def cfg():
    return tfm.TransformerConfig.llama_style(
        vocab=64, d_model=32, n_heads=4, n_kv_heads=2, n_layers=2,
        d_ff=48, max_seq=128)


class TestRopeUnit:
    def test_rotation_preserves_pair_norms(self):
        x = jnp.asarray(np.random.RandomState(0).randn(2, 8, 3, 16),
                        jnp.float32)
        pos = jnp.arange(8) * 7
        r = _rope(x, pos, 10000.0)
        h = 8
        n0 = np.asarray(x[..., :h] ** 2 + x[..., h:] ** 2)
        n1 = np.asarray(r[..., :h] ** 2 + r[..., h:] ** 2)
        np.testing.assert_allclose(n1, n0, rtol=1e-5, atol=1e-5)

    def test_position_zero_is_identity(self):
        x = jnp.asarray(np.random.RandomState(1).randn(1, 1, 2, 8),
                        jnp.float32)
        r = _rope(x, jnp.zeros((1,), jnp.int32), 10000.0)
        np.testing.assert_allclose(np.asarray(r), np.asarray(x),
                                   rtol=1e-6, atol=1e-6)

    def test_dot_products_depend_on_relative_position(self):
        """<rope(q,m), rope(k,n)> must equal <rope(q,m+s), rope(k,n+s)>
        — the property that makes rope a RELATIVE encoding."""
        rng = np.random.RandomState(2)
        q = jnp.asarray(rng.randn(1, 1, 1, 16), jnp.float32)
        k = jnp.asarray(rng.randn(1, 1, 1, 16), jnp.float32)

        def dot(m, n):
            qm = _rope(q, jnp.asarray([m]), 10000.0)
            kn = _rope(k, jnp.asarray([n]), 10000.0)
            return float(jnp.sum(qm * kn))

        assert abs(dot(9, 4) - dot(21, 16)) < 1e-4
        assert abs(dot(9, 4) - dot(9, 5)) > 1e-6  # and DOES move with gap

    def test_odd_head_dim_rejected(self):
        bad = tfm.TransformerConfig(d_model=12, n_heads=4, rope=True)
        with pytest.raises(ValueError, match="even head_dim"):
            tfm.init_transformer(jax.random.PRNGKey(0), bad)


def test_param_set_matches_arch(cfg):
    params = tfm.init_transformer(jax.random.PRNGKey(0), cfg)
    assert "pos_emb" not in params          # rope: no position table
    assert "L0_ff3_W" in params             # swiglu up-projection
    assert "L0_ff1_b" not in params         # no biases
    assert "L0_ln1_b" not in params         # rms: scale only
    assert "lnf_b" not in params
    with pytest.raises(ValueError, match="unknown norm"):
        tfm.init_transformer(jax.random.PRNGKey(0),
                             dataclasses.replace(cfg, norm="batch"))
    with pytest.raises(ValueError, match="unknown ffn"):
        tfm.init_transformer(jax.random.PRNGKey(0),
                             dataclasses.replace(cfg, ffn="relu"))


def test_swiglu_and_rms_formulas(cfg):
    """One block's FFN/norm against hand-written formulas."""
    params = tfm.init_transformer(jax.random.PRNGKey(3), cfg)
    x = jnp.asarray(np.random.RandomState(4).randn(2, 5, 32), jnp.float32)
    got = tfm._norm(params, "L0_ln1", x, cfg)
    want = x * (1.0 / np.sqrt(np.mean(np.asarray(x) ** 2, -1,
                                      keepdims=True) + 1e-5)) \
        * np.asarray(params["L0_ln1_g"])
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5,
                               atol=1e-5)
    out, aux = tfm._ffn(params, 0, x, cfg, None)
    w1, w3, w2 = (np.asarray(params[f"L0_ff{i}_W"]) for i in (1, 3, 2))
    xx = np.asarray(x)
    g = xx @ w1
    want = ((g / (1 + np.exp(-g))) * (xx @ w3)) @ w2
    np.testing.assert_allclose(np.asarray(out), want, rtol=1e-4,
                               atol=1e-4)
    assert aux == 0.0


@pytest.mark.parametrize("attn", ["ring", "zigzag", "ulysses"])
def test_sharded_forward_matches_oracle(mesh, cfg, attn):
    params = tfm.init_transformer(jax.random.PRNGKey(5), cfg)
    toks = jnp.asarray(np.random.RandomState(6).randint(0, 64, (4, 64)),
                       jnp.int32)
    want = tfm.transformer_apply(params, toks, cfg=cfg)
    got = tfm.make_sharded_apply(cfg, mesh, attn=attn)(params, toks)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.heavy
def test_train_step_learns_and_remat_parity(mesh, cfg):
    """llama_style training on the mesh: learns the copy task, and
    remat=True gives identical numbers."""
    rng = np.random.RandomState(7)
    b, l = 8, 64
    start = rng.randint(0, 64, (b, 1))
    seq = (start + np.arange(l + 1)) % 64
    tokens = jnp.asarray(seq[:, :-1], jnp.int32)
    targets = jnp.asarray(seq[:, 1:], jnp.int32)
    params = tfm.init_transformer(jax.random.PRNGKey(8), cfg)
    opt = optax.adam(3e-3)
    td = tfm.shard_batch(mesh, tokens, targets)

    losses = {}
    for remat in (False, True):
        c = dataclasses.replace(cfg, remat=remat)
        step = tfm.make_train_step(c, mesh, opt, attn="zigzag")
        p = jax.tree.map(jnp.copy, params)
        st = opt.init(p)
        first = last = None
        for _ in range(25):
            p, st, loss = step(p, st, *td)
            first = first if first is not None else float(loss)
            last = float(loss)
        losses[remat] = (first, last)
    assert losses[False][1] < 0.7 * losses[False][0], losses
    np.testing.assert_allclose(losses[True], losses[False], rtol=1e-5)


@pytest.mark.heavy
def test_3d_tp_modern_matches_oracle(cfg):
    """rope + rms + swiglu on the 3-D tp mesh (MHA heads — GQA stays
    rejected there): one step's loss equals the 2-D step's."""
    from jax.sharding import Mesh
    mha = dataclasses.replace(cfg, n_kv_heads=0)
    devices = jax.devices("cpu")[:8]
    mesh3 = Mesh(np.array(devices).reshape(2, 2, 2), ("dp", "sp", "mp"))
    mesh2 = Mesh(np.array(devices[:4]).reshape(2, 2), ("dp", "sp"))
    rng = np.random.RandomState(9)
    seq = rng.randint(0, 64, (4, 33))
    tokens = jnp.asarray(seq[:, :-1], jnp.int32)
    targets = jnp.asarray(seq[:, 1:], jnp.int32)
    params = tfm.init_transformer(jax.random.PRNGKey(10), mha)
    opt = optax.sgd(0.1)

    step2 = tfm.make_train_step(mha, mesh2, opt, attn="ring")
    p2 = jax.tree.map(jnp.copy, params)
    _, _, loss2 = step2(p2, opt.init(p2), *tfm.shard_batch(mesh2, tokens,
                                                           targets))

    step3 = tfm.make_train_step_3d(mha, mesh3, opt, attn="ring")
    p3 = tfm.shard_params_3d(params, mesh3, mha)
    _, _, loss3 = step3(p3, opt.init(p3), *tfm.shard_batch(mesh3, tokens,
                                                           targets))
    assert abs(float(loss2) - float(loss3)) < 2e-5


@pytest.mark.heavy
def test_pp_modern_runs(cfg):
    """Pipeline stacking handles the swiglu/rms key set (no fixed
    name list): one pp step on the llama-style MHA config."""
    from jax.sharding import Mesh
    mha = dataclasses.replace(cfg, n_kv_heads=0)
    mesh = Mesh(np.array(jax.devices("cpu")[:2]), ("pp",))
    params = tfm.init_transformer(jax.random.PRNGKey(11), mha)
    stacked = tfm.shard_params_pp(params, mesh, mha)
    # round trip through stack/unstack preserves every key
    rt = tfm.unstack_params_pp(tfm.stack_params_pp(params, mha), mha)
    assert set(rt) == set(params)
    opt = optax.sgd(0.05)
    step = tfm.make_train_step_pp(mha, mesh, opt, n_micro=2)
    rng = np.random.RandomState(12)
    seq = rng.randint(0, 64, (4, 17))
    _, _, loss = step(stacked, opt.init(stacked),
                      jnp.asarray(seq[:, :-1], jnp.int32),
                      jnp.asarray(seq[:, 1:], jnp.int32))
    assert np.isfinite(float(loss))


@pytest.mark.heavy
def test_decode_and_prefill_match_full_forward(mesh, cfg):
    params = tfm.init_transformer(jax.random.PRNGKey(13), cfg)
    prompt = jnp.asarray(np.random.RandomState(14).randint(0, 64, (4, 8)),
                         jnp.int32)
    n_new = 6
    got = tfm.greedy_decode(params, prompt, n_new, cfg=cfg)
    toks = prompt
    for _ in range(n_new):
        logits = tfm.transformer_apply(params, toks, cfg=cfg)
        nxt = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
        toks = jnp.concatenate([toks, nxt[:, None]], axis=1)
    assert np.array_equal(np.asarray(got), np.asarray(toks))
    pre = tfm.greedy_decode(params, prompt, n_new, cfg=cfg,
                            use_prefill=True)
    assert np.array_equal(np.asarray(pre), np.asarray(got))
    # sharded prefill too — rope positions ride _shard_pos
    shp = tfm.greedy_decode(params, prompt, n_new, cfg=cfg,
                            use_prefill=True, mesh=mesh, attn="ring")
    assert np.array_equal(np.asarray(shp), np.asarray(got))
    # a batch NOT divisible by dp replicates the batch axis instead of
    # failing (inference batches are often smaller than training dp)
    small = tfm.greedy_decode(params, prompt[:1], n_new, cfg=cfg,
                              use_prefill=True, mesh=mesh, attn="ring")
    ref = tfm.greedy_decode(params, prompt[:1], n_new, cfg=cfg)
    assert np.array_equal(np.asarray(small), np.asarray(ref))


def test_flops_accounting_swiglu(cfg):
    gelu = dataclasses.replace(cfg, ffn="gelu", norm="ln", rope=False)
    diff = tfm.flops_per_token(cfg, 16) - tfm.flops_per_token(gelu, 16)
    assert diff == 3.0 * cfg.n_layers * 2.0 * cfg.d_model * cfg.d_ff


@pytest.mark.heavy
def test_char_lm_converges_on_real_text():
    """Convergence, not finiteness (VERDICT r3 item 4): the full modern
    stack (llama-style + zero1 + bf16 f32-master, zigzag sp) trained
    char-level on the repo's own docs must beat a fixed loss target.
    Initial loss is ~ln(64)=4.16; the target proves real learning on
    real text through every lever at once. The committed artifact
    (benchmarks/results/lm_train.json) is the same run at a tighter
    target and bigger budget."""
    import argparse

    from examples.lm.train_lm import run

    args = argparse.Namespace(
        dp=4, sp=2, seq=128, batch=8, steps=100, grad_accum=2,
        attn="zigzag", kv_heads=0, modern=True, window=0, zero1=True,
        bf16=True, ckpt=None, ckpt_every=10, data="repo-docs",
        target_loss=3.0, out_json=None)
    summary = run(args)
    assert summary["reached_target"], summary["losses"]
    assert summary["losses"][0][1] > 3.4     # started near ln(64)


@pytest.mark.heavy
def test_lm_resume_is_exact(tmp_path):
    """Kill-and-resume equals never-stopped (the reference's resume
    matrix applied to the LM family): a 40-step run and a 20-step run
    resumed for the back 20 must produce IDENTICAL logged losses on the
    shared steps — per-step seeded batches + checkpointed
    (params, opt_state, step) leave no divergence anywhere."""
    import argparse

    from examples.lm.train_lm import run

    def mk(steps, resume):
        return argparse.Namespace(
            dp=4, sp=2, seq=64, batch=4, steps=steps, grad_accum=1,
            attn="zigzag", kv_heads=0, modern=False, window=0,
            zero1=False, bf16=False, ckpt=f"shared:{tmp_path}/ck",
            ckpt_every=10, data=None, target_loss=None, out_json=None,
            resume=resume)

    straight = run(mk(40, resume=False))

    import shutil
    shutil.rmtree(tmp_path / "ck")
    first = run(mk(20, resume=False))       # writes ckpt at step 20
    second = run(mk(40, resume=True))       # resumes at 20, runs 21-40

    assert second["resumed_at"] == 20, second
    tail = {s: l for s, l in straight["losses"] if s > 20}
    tail2 = {s: l for s, l in second["losses"] if s > 20}
    # shared cadence steps must agree exactly
    shared = set(tail) & set(tail2)
    assert shared, (straight["losses"], second["losses"])
    for s in sorted(shared):
        assert tail[s] == tail2[s], (s, tail[s], tail2[s])
    # and the front half really trained (sanity that first ran)
    assert first["steps"] == 20


@pytest.mark.heavy
def test_char_lm_validation_tracking():
    """Corpus-mode validation: a held-out tail is evaluated on a fixed
    window set every eval_every steps; best-so-far tracking feeds the
    reference-style early stopping (common.lua:144-202's discipline).
    Learning must show up on the HELD-OUT split, not just train."""
    import argparse

    from examples.lm.train_lm import run

    args = argparse.Namespace(
        dp=4, sp=2, seq=64, batch=8, steps=60, grad_accum=1,
        attn="zigzag", kv_heads=0, modern=True, window=0, zero1=False,
        bf16=False, ckpt=None, ckpt_every=10, data="repo-docs",
        target_loss=None, out_json=None, resume=False,
        val_frac=0.1, eval_every=15, patience=0)
    s = run(args)
    assert len(s["val_losses"]) == 4, s["val_losses"]
    first_val = s["val_losses"][0][1]
    assert s["best_val"] is not None and s["best_val"] < first_val
    assert s["best_step"] >= 15 and s["stopped_early"] is False


def test_device_trace_writes_profile(tmp_path):
    """utils/profiling.device_trace captures a jit region into a
    TensorBoard-readable trace directory."""
    import jax
    import jax.numpy as jnp

    from lua_mapreduce_tpu.utils.profiling import annotate, device_trace

    d = str(tmp_path / "trace")
    with device_trace(d):
        with annotate("tiny-matmul"):
            x = jnp.ones((64, 64))
            jax.block_until_ready(jax.jit(lambda a: a @ a)(x))
    files = [os.path.join(r, f) for r, _, fs in os.walk(d) for f in fs]
    assert files, "no trace output written"
    assert any("trace" in f or f.endswith(".pb") or ".xplane." in f
               for f in files), files


@pytest.mark.heavy
def test_lm_sigkill_mid_training_resumes(tmp_path):
    """Chaos e2e for the LM family: SIGKILL the training process after
    an observed checkpoint (no cleanup runs — the async writer dies
    with it), then --resume completes the budget from the atomic
    snapshot. The store's tmp+rename publish guarantees the reader
    never sees a torn checkpoint, whatever instant the KILL landed."""
    import signal
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "JAX_NUM_CPU_DEVICES")}
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
    prog = [sys.executable, "-m", "examples.lm.train_lm"]
    ck = ["--ckpt", f"shared:{tmp_path}/ck"]

    p = subprocess.Popen(prog + ["--steps", "500", "--ckpt-every", "5"]
                         + ck, cwd=repo, env=env,
                         stdout=subprocess.PIPE, text=True)
    try:
        # async semantics: 'checkpoint @' prints at SUBMIT; durability
        # of submit N is proven by submit N+1 (one write in flight at
        # most). Kill after the SECOND line → checkpoint #1 is on disk.
        # A watchdog kills a hung/drifted child so readline can't block
        # the suite forever.
        import threading
        watchdog = threading.Timer(240, p.kill)
        watchdog.daemon = True
        watchdog.start()
        seen = 0
        for line in p.stdout:
            if "checkpoint @" in line:
                seen += 1
                if seen == 2:
                    break
        assert seen == 2, "never observed two checkpoints (hung child?)"
        p.send_signal(signal.SIGKILL)
    finally:
        watchdog.cancel()
        if p.poll() is None:
            p.kill()
        p.wait(timeout=30)

    r = subprocess.run(prog + ["--steps", "30", "--ckpt-every", "10",
                               "--resume"] + ck,
                       cwd=repo, env=env, capture_output=True, text=True,
                       timeout=600)
    assert r.returncode == 0, r.stdout[-500:] + r.stderr[-500:]
    assert "resumed from checkpoint at step" in r.stdout, r.stdout[-400:]
    assert "done: final loss" in r.stdout, r.stdout[-400:]
