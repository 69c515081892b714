"""Long-context LM training demo: the full sequence-parallel stack.

The reference's training example is the digits MLP run as looping
MapReduce (examples/APRIL-ANN/, SURVEY.md §3.5); this demo is the same
role for the long-context family this framework adds: a decoder-only
transformer trained data- AND sequence-parallel over a mesh, with every
memory/throughput lever on:

- zigzag ring attention (``attn="zigzag"``): causal work balanced
  across sequence shards, no device holds the full sequence;
- block rematerialization (``cfg.remat``) + gradient accumulation
  (``grad_accum``): the two activation-memory levers;
- atomic checkpointing to any Store backend every ``ckpt_every`` steps.

Synthetic task: learn tok[t+1] = (tok[t] + step) % vocab with a
per-sequence stride — next-token loss drops fast, so the demo shows
real learning in seconds. Run on one host with a virtual mesh:

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    JAX_PLATFORMS=cpu python -m examples.lm.train_lm --steps 30
"""

from __future__ import annotations

import argparse
import time

import numpy as np


def synthetic_batch(rng, vocab: int, batch: int, seq: int):
    """Sequences tok[t+1] = (tok[t] + stride) % vocab, stride ∈ {1, 2}."""
    start = rng.randint(0, vocab, (batch, 1))
    stride = rng.randint(1, 3, (batch, 1))
    toks = (start + stride * np.arange(seq + 1)) % vocab
    return toks[:, :-1].astype(np.int32), toks[:, 1:].astype(np.int32)


# char-level tokenizer for the real-text mode: 64 classes, everything
# outside the set folds to index 0 (space) — vocab stays MXU-irrelevant
# small but the statistics are real English
_CHARSET = (" abcdefghijklmnopqrstuvwxyz0123456789.,;:!?'\"()-_/=+*#%<>[]\n`|")
_CHAR_TO_ID = {c: i for i, c in enumerate(_CHARSET)}
REPO_DOCS = "repo-docs"          # sentinel: train on this repo's docs


def load_corpus(data: str, tok: str = "char"):
    """``data`` is a path to a text file, or REPO_DOCS for the repo's
    own documentation (~80 KB of real English, checked in — the 'small
    corpus' of VERDICT r3 item 4). ``tok`` picks the tokenizer:
    ``"char"`` (the 64-way charset) or ``"word:N"`` (word-level over
    the N most frequent corpus tokens, id 0 = <unk>). Returns
    (ids int32 array, id_to_str list, joiner string)."""
    import os
    repo = os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))
    if data == REPO_DOCS:
        paths = [os.path.join(repo, p)
                 for p in ("README.md", "docs/DESIGN.md", "SURVEY.md")]
    else:
        paths = [data]
    text = "\n".join(open(p, encoding="utf-8", errors="replace").read()
                     for p in paths).lower()
    if tok == "char":
        ids = np.array([_CHAR_TO_ID.get(c, 0) for c in text], np.int32)
        return ids, list(_CHARSET), ""
    if tok.startswith("word:"):
        import collections
        import re
        n_vocab = int(tok.split(":", 1)[1])
        if n_vocab < 2:
            raise SystemExit(f"--tok {tok}: vocab must be >= 2")
        words = re.findall(r"[a-z0-9']+|[^\sa-z0-9']", text)
        common = collections.Counter(words).most_common(n_vocab - 1)
        id_to_str = ["<unk>"] + [w for w, _ in common]
        w_to_id = {w: i for i, w in enumerate(id_to_str)}
        ids = np.array([w_to_id.get(w, 0) for w in words], np.int32)
        return ids, id_to_str, " "
    raise SystemExit(f"unknown --tok {tok!r} (use 'char' or 'word:N')")


def corpus_batch(rng, data: np.ndarray, batch: int, seq: int):
    if len(data) < seq + 2:
        raise SystemExit(
            f"corpus has {len(data)} tokens — needs at least seq+2 = "
            f"{seq + 2} for one training window; use a bigger file or "
            f"a smaller --seq")
    off = rng.randint(0, len(data) - seq - 1, batch)
    idx = off[:, None] + np.arange(seq + 1)
    toks = data[idx]
    return toks[:, :-1], toks[:, 1:]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--dp", type=int, default=0,
                    help="data-parallel mesh axis (0 = auto: factor "
                         "the visible devices as dp x sp)")
    ap.add_argument("--sp", type=int, default=0,
                    help="sequence-parallel mesh axis (0 = auto)")
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--steps", type=int, default=30)
    ap.add_argument("--grad-accum", type=int, default=2)
    ap.add_argument("--attn", default="zigzag",
                    choices=["ring", "zigzag", "ulysses"])
    ap.add_argument("--kv-heads", type=int, default=0,
                    help="grouped-query attention kv heads "
                         "(0 = n_heads, plain MHA)")
    ap.add_argument("--modern", action="store_true",
                    help="llama-style recipe: rope + rmsnorm + swiglu")
    ap.add_argument("--window", type=int, default=0,
                    help="sliding-window attention (ring only; the "
                         "banded ring also truncates its hops)")
    ap.add_argument("--zero1", action="store_true",
                    help="shard optimizer state over dp (ZeRO-1)")
    ap.add_argument("--bf16", action="store_true",
                    help="bf16 params with f32 master weights")
    ap.add_argument("--ckpt", default=None,
                    help="storage spec for checkpoints, e.g. shared:/tmp/lm")
    ap.add_argument("--ckpt-every", type=int, default=10)
    ap.add_argument("--resume", action="store_true",
                    help="continue from --ckpt's lm.ckpt if present; "
                         "batches are per-step seeded, so the resumed "
                         "run is exactly the run that never stopped")
    ap.add_argument("--data", default=None,
                    help="real-text mode: a text file path, "
                         f"or '{REPO_DOCS}' for this repo's docs "
                         "(default: the synthetic stride task)")
    ap.add_argument("--tok", default="char",
                    help="corpus tokenizer: 'char' (64-way charset) or "
                         "'word:N' (word-level vocab of the N most "
                         "frequent corpus tokens, id 0 = <unk> — the "
                         "MXU-relevant embedding/softmax width)")
    ap.add_argument("--d-model", type=int, default=64)
    ap.add_argument("--n-layers", type=int, default=2)
    ap.add_argument("--n-heads", type=int, default=4)
    ap.add_argument("--d-ff", type=int, default=128)
    ap.add_argument("--val-frac", type=float, default=0.1,
                    help="corpus tail held out for validation "
                         "(corpus mode only; 0 disables)")
    ap.add_argument("--eval-every", type=int, default=25,
                    help="steps between validation evals (corpus mode)")
    ap.add_argument("--patience", type=int, default=0,
                    help=">0: stop after this many evals without a new "
                         "best validation loss (the reference's "
                         "APRIL-ANN early-stopping discipline, "
                         "common.lua:144-202)")
    ap.add_argument("--target-loss", type=float, default=None,
                    help="stop once train loss < target; --steps becomes "
                         "the max budget and the run FAILS (exit 1) if "
                         "the target is never reached")
    ap.add_argument("--out-json", default=None,
                    help="write the run summary (loss curve, tokens/sec) "
                         "to this path")
    ap.add_argument("--profile", default=None, metavar="DIR",
                    help="wrap the run in a jax.profiler device trace "
                         "written to DIR (view with TensorBoard)")
    args = ap.parse_args()
    summary = run(args)
    if args.out_json:
        import json
        with open(args.out_json, "w") as f:
            json.dump(summary, f, indent=1)
            f.write("\n")
    if args.target_loss is not None and not summary["reached_target"]:
        final = summary["losses"][-1][1] if summary["losses"] else "n/a"
        raise SystemExit(
            f"target loss {args.target_loss} not reached in "
            f"{args.steps} steps (final {final})")


def run(args) -> dict:
    import contextlib

    from lua_mapreduce_tpu.utils.jax_env import place_compile_cache
    place_compile_cache()
    import jax

    with contextlib.ExitStack() as _stack:
        if getattr(args, "profile", None):
            from lua_mapreduce_tpu.utils.profiling import device_trace
            _stack.enter_context(device_trace(args.profile))
        return _run_inner(args, jax)


def _run_inner(args, jax) -> dict:
    import jax.numpy as jnp
    import optax
    from jax.sharding import Mesh

    from lua_mapreduce_tpu.models import transformer as tfm
    from lua_mapreduce_tpu.store.router import get_storage_from
    from lua_mapreduce_tpu.train import checkpoint as ckpt

    devices = jax.devices()
    if not args.dp and not args.sp:
        # auto mesh: use the visible devices — sp=2 when it divides
        # (the sequence-parallel path stays exercised), else pure dp;
        # dp capped to the largest value the batch geometry supports
        # (batch divides into dp, and each device's rows split into
        # grad_accum microbatches). One real chip → dp=1 x sp=1.
        nv = len(devices)
        args.sp = 2 if nv % 2 == 0 else 1
        ga = max(args.grad_accum, 1)
        args.dp = next(
            (d for d in range(nv // args.sp, 0, -1)
             if args.batch % d == 0 and (args.batch // d) % ga == 0),
            None)
        if args.dp is None:
            raise SystemExit(
                f"no feasible dp: batch={args.batch} must split as "
                f"batch % dp == 0 with (batch // dp) % grad_accum == 0 "
                f"for some dp <= {nv // args.sp} (grad_accum={ga}) — "
                f"adjust --batch or --grad-accum, or pass --dp/--sp "
                f"explicitly")
    elif not args.dp or not args.sp:
        free = len(devices) // max(args.dp, args.sp, 1)
        args.dp = args.dp or free
        args.sp = args.sp or free
    n = args.dp * args.sp
    if len(devices) < n:
        raise SystemExit(
            f"need {n} devices for dp={args.dp} x sp={args.sp}, have "
            f"{len(devices)} — set "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={n} "
            f"JAX_PLATFORMS=cpu for a virtual mesh")
    mesh = Mesh(np.array(devices[:n]).reshape(args.dp, args.sp),
                ("dp", "sp"))

    tok = getattr(args, "tok", "char") or "char"
    if tok != "char" and not args.data:
        raise SystemExit(f"--tok {tok} builds its vocab FROM the corpus;"
                         " it requires --data (the synthetic task is "
                         "char-mode only)")
    data, id_to_str, joiner = (load_corpus(args.data, tok) if args.data
                               else (None, list(_CHARSET), ""))
    # embedding/softmax width: the tokenizer's vocab, padded up to a
    # lane-aligned multiple of 128 in word mode (char mode keeps the
    # historical 64 — artifacts stay comparable across rounds)
    vocab = 64 if tok == "char" else -(-len(id_to_str) // 128) * 128
    mk = (tfm.TransformerConfig.llama_style if args.modern
          else tfm.TransformerConfig)
    cfg = mk(vocab=vocab, d_model=getattr(args, "d_model", 64),
             n_heads=getattr(args, "n_heads", 4),
             n_layers=getattr(args, "n_layers", 2),
             d_ff=getattr(args, "d_ff", 128), max_seq=args.seq,
             remat=True, n_kv_heads=args.kv_heads, window=args.window)
    if args.window and args.attn != "ring":
        raise SystemExit("--window runs sequence-parallel as the "
                         "banded ring: use --attn ring")
    params = tfm.init_transformer(jax.random.PRNGKey(0), cfg)
    opt = optax.adam(3e-3)
    if args.bf16:
        from lua_mapreduce_tpu.train.precision import with_f32_master
        params = jax.tree.map(lambda x: x.astype(jnp.bfloat16), params)
        opt = with_f32_master(opt)
    # zigzag batches are pre-permuted HOST-side (shard_batch below), so
    # the steady-state step never pays a cross-shard resharding — the
    # persistent-layout integration (VERDICT r2 item 8)
    zz = args.attn == "zigzag"
    step = tfm.make_train_step(cfg, mesh, opt, attn=args.attn,
                               grad_accum=args.grad_accum,
                               zigzag_layout=zz, zero1=args.zero1)
    schedule = "zigzag" if zz else "contiguous"
    if args.zero1:
        from lua_mapreduce_tpu.parallel import zero1 as z1
        opt_state = z1.init_state(opt, params, mesh)
    else:
        # on the mesh as the step returns them, or step 2 recompiles
        params = tfm.shard_params_moe(params, mesh)
        opt_state = tfm.init_opt_state(opt, params, mesh)

    store = get_storage_from(args.ckpt) if args.ckpt else None
    target = getattr(args, "target_loss", None)
    # validation: hold out the corpus TAIL (contiguous, so no train
    # window ever overlaps it) and pin a fixed set of eval windows —
    # the reference's train/validate split discipline for the LM family
    val_frac = getattr(args, "val_frac", 0.0) if data is not None else 0.0
    eval_every = max(1, getattr(args, "eval_every", 25) or 25)
    patience = getattr(args, "patience", 0) or 0
    val_batch = None
    if val_frac > 0:
        n_val = int(len(data) * val_frac)
        if n_val < args.seq + 2:
            raise SystemExit(
                f"--val-frac {val_frac} keeps only {n_val} tokens — "
                f"needs at least seq+2 = {args.seq + 2}")
        train_data, val_data = data[:-n_val], data[-n_val:]
        data = train_data
        n_win = min(16, max(1, (len(val_data) - 1) // args.seq))
        offs = np.linspace(0, len(val_data) - args.seq - 1, n_win,
                           dtype=np.int64)
        idx = offs[:, None] + np.arange(args.seq + 1)
        vt = val_data[idx]
        val_batch = (jnp.asarray(vt[:, :-1]), jnp.asarray(vt[:, 1:]))

        @jax.jit
        def val_loss_fn(p, toks, tgts):
            logits = tfm.transformer_apply(p, toks, cfg=cfg)
            return optax.softmax_cross_entropy_with_integer_labels(
                logits, tgts).mean()
    start_step = 0
    if (store is not None and getattr(args, "resume", False)
            and store.exists("lm.ckpt")):
        # resume-EXACT: the checkpoint carries (params, opt_state, step);
        # batches are derived per-step from the seed below, so a resumed
        # run replays the identical remaining data stream — continuing
        # from step k is bit-for-bit the run that never stopped
        # (the reference's task-doc resume matrix, applied to the LM)
        tmpl = {"params": params, "opt": opt_state,
                "step": jnp.zeros((), jnp.int32)}
        # strict load: a checkpoint from a different run configuration
        # (other dtype policy, other zero1 sharding) must fail HERE with
        # the loader's clear message, not deep inside the first step
        state = ckpt.load_pytree(store, "lm.ckpt", tmpl,
                                 check_shapes=True, check_dtypes=True)
        params, opt_state = state["params"], state["opt"]
        start_step = int(state["step"])
        print(f"resumed from checkpoint at step {start_step}", flush=True)
    losses = []
    val_losses = []
    best_val, best_step, stopped_early = None, start_step, False
    best_params = None
    saver = ckpt.AsyncCheckpoint()
    reached = target is None
    t0 = time.time()
    warm_t0 = None              # tokens/sec excludes the compile step
    from lua_mapreduce_tpu.parallel.mesh import opt_state_layout
    from lua_mapreduce_tpu.utils.profiling import build_log, build_table
    builds, built = build_log(), None   # programs built by the last look
    i = start_step
    try:
        for i in range(start_step + 1, args.steps + 1):
            # per-step seeded batches (not one sequential stream): resume at
            # step k sees exactly the batches steps k+1.. would have seen
            rng = np.random.RandomState(1000 + 7919 * i)
            if data is not None:
                toks, tgts = corpus_batch(rng, data, args.batch, args.seq)
            else:
                toks, tgts = synthetic_batch(rng, cfg.vocab, args.batch,
                                             args.seq)
            params, opt_state, loss = step(
                params, opt_state,
                *tfm.shard_batch(mesh, toks, tgts, schedule=schedule))
            if i == start_step + 1:
                warm_t0 = time.time()
            # loss is only fetched (device→host sync) on the print cadence —
            # a per-step fetch would serialize async dispatch and the
            # reported tokens/sec would measure the synchronized regime
            if i == start_step + 1 or i % 5 == 0 or i == args.steps:
                lf = float(loss)
                losses.append((i, round(lf, 4)))
                print(f"step {i:4d}  loss {lf:.4f}  "
                      f"({time.time() - t0:.1f}s)", flush=True)
                if target is not None and lf < target:
                    reached = True
                    print(f"target loss {target} reached at step {i}",
                          flush=True)
                    break
            if val_batch is not None and i % eval_every == 0:
                # CPU backends: the train step's in-flight collectives must
                # drain before another compiled program launches
                jax.block_until_ready(params)
                vl = float(val_loss_fn(params, *val_batch))
                val_losses.append((i, round(vl, 4)))
                if best_val is None or vl < best_val:
                    best_val, best_step = vl, i
                    if patience:
                        # the train step donates its param buffers, so a
                        # live reference would dangle — snapshot to host
                        best_params = jax.device_get(params)
                print(f"  val  {i:4d}  loss {vl:.4f}"
                      + ("  (best)" if best_step == i else ""), flush=True)
                if patience and (i - best_step) >= patience * eval_every:
                    stopped_early = True
                    print(f"early stop at step {i}: no val improvement "
                          f"since step {best_step} "
                          f"({patience} evals)", flush=True)
                    break
            if store is not None and i % args.ckpt_every == 0:
                # async: the device→host snapshot is synchronous (consistent
                # with this step), serialization + publish overlap training
                saver.submit(store, "lm.ckpt",
                             {"params": params, "opt": opt_state,
                              "step": jnp.asarray(i, jnp.int32)})
                print(f"  checkpoint @ step {i}", flush=True)
            if built is None:
                # where set-up went: every program built up to here
                print(build_table(builds.rows(), builds.process_start),
                      flush=True)
                held, total, split, whole = opt_state_layout(opt_state)
                print(f"optimizer state: {held / 1e9:.4g} of "
                      f"{total / 1e9:.4g} GB a device, {split} leaves "
                      f"split, {whole} whole", flush=True)
                built = builds.programs
            elif builds.programs != built:
                for row in builds.rows(built):
                    print(f"recompiled at step {i}: {row['program']} "
                          f"(trace {row['trace_s']:.3f} s, build "
                          f"{row['build_s']:.3f} s)", flush=True)
                built = builds.programs
    finally:
        # an exception mid-loop (OOM, NaN guard, SIGTERM) must not
        # abandon the in-flight write: the 'checkpoint @ step' log
        # line is only ever true because this wait always runs
        saver.wait()
    jax.block_until_ready(params)   # CPU backends: don't overlap the
    #                                   decode program with in-flight
    #                                   train collectives
    if patience and best_params is not None:
        # the early-stopping DELIVERABLE is the best-validation model
        # (common.lua:144-202's discipline, as train/harness.fit does):
        # restore it for the final checkpoint, sample, and caller
        params = jax.device_put(best_params)
        if store is not None:
            ckpt.save_pytree(store, "lm.ckpt",
                             {"params": params, "opt": opt_state,
                              "step": jnp.asarray(best_step, jnp.int32)})
            print(f"  checkpoint restored to best-val step {best_step}",
                  flush=True)
    ran_any = i > start_step
    steps_done = i
    toks_per_step = args.batch * args.seq
    warm_s = time.time() - (warm_t0 or t0)
    tokens_per_sec = (toks_per_step * max(0, steps_done - start_step - 1)
                      / max(warm_s, 1e-9)) if ran_any else 0.0
    if ran_any:
        print(f"done: final loss {float(loss):.4f} "
              f"({args.attn} attention, dp={args.dp} sp={args.sp}, "
              f"grad_accum={args.grad_accum}, remat=on"
              + (", llama-style" if args.modern else "")
              + (f", window={args.window}" if args.window else "")
              + (", zero1" if args.zero1 else "")
              + (", bf16+f32-master" if args.bf16 else "") + ")")

    if not ran_any:                 # resumed at/past the whole budget:
        sample = None               # params are loaded, nothing to train
        print(f"checkpoint already at step {start_step} >= --steps "
              f"{args.steps}; nothing to train", flush=True)
    elif data is None:
        # generate: parallel prompt prefill + KV-cached greedy decode
        prompt = np.arange(8, dtype=np.int32)[None, :] % cfg.vocab
        out = np.asarray(tfm.greedy_decode(
            params, jnp.asarray(prompt), 8, cfg=cfg, use_prefill=True))[0]
        print(f"prompt {prompt[0].tolist()} -> continuation "
              f"{out[8:].tolist()} (stride-1 truth: "
              f"{[(8 + i) % cfg.vocab for i in range(8)]})")
        sample = out.tolist()
    else:
        # sample a continuation of a corpus prompt, decoded to text;
        # lengths scale with the model's positional budget, and ids the
        # tokenizer doesn't cover (vocab is lane-padded) print as '?'
        p_len = min(32, max(4, cfg.max_seq // 4))
        n_new = min(48, cfg.max_seq - p_len)
        toks, _ = corpus_batch(rng, data, 1, p_len)
        out = np.asarray(tfm.greedy_decode(
            params, jnp.asarray(toks), n_new, cfg=cfg,
            use_prefill=True))[0]
        sample = joiner.join(id_to_str[t] if t < len(id_to_str) else "?"
                             for t in out)
        print(f"sample: {sample!r}")

    return {
        "data": args.data or "synthetic-stride",
        "losses": losses,
        "val_losses": val_losses,
        "best_val": best_val,
        "best_step": best_step if best_val is not None else None,
        "stopped_early": stopped_early,
        "steps": steps_done,
        "resumed_at": start_step or None,
        "reached_target": reached,
        "target_loss": target,
        "tokens_per_sec": round(tokens_per_sec, 1),
        "platform": jax.default_backend(),
        "config": {
            "dp": args.dp, "sp": args.sp, "seq": args.seq,
            "batch": args.batch, "grad_accum": args.grad_accum,
            "attn": args.attn, "modern": args.modern, "tok": tok,
            "zero1": args.zero1, "bf16": args.bf16,
            "vocab": cfg.vocab, "d_model": cfg.d_model,
            "n_layers": cfg.n_layers, "d_ff": cfg.d_ff,
        },
        "sample": sample,
    }


if __name__ == "__main__":
    main()
