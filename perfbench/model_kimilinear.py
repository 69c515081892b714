"""What the `session` driver asks of a model family, for
Kimi-Linear-48B-A3B-Instruct (`"session_model": "model_kimilinear"` in
the configuration): weights, the program's configuration, the counters
and the comparison that decides `correct`."""

from __future__ import annotations

import gc
import sys
import time

import numpy as np

from perfbench import (counts_kimilinear, reference_kimilinear,
                       weights_kimilinear)
# the turns' rows and the readings are sarvam's: the same comparison
from perfbench.model_sarvam import readings_of, turns_of

# the checked turns run `decode_from(stats=True)`: where the router sent
# each token is part of the comparison
COUNTERS = True


def pattern(cfg: dict) -> tuple:
    """Every layer's mixer, from the published 1-indexed lists: `kda`
    where `kda_layers` names it, `latent` where `full_attn_layers`
    does; the two lists together name every layer once."""
    la, n = cfg["linear_attn_config"], cfg["num_hidden_layers"]
    kda, full = set(la["kda_layers"]), set(la["full_attn_layers"])
    if kda & full or kda | full != set(range(1, n + 1)):
        raise SystemExit("model_kimilinear: kda_layers and full_attn_layers "
                         f"do not name each of the {n} layers once")
    return tuple(weights_kimilinear.kind(cfg, i) for i in range(n))


def program_config(cfg: dict):
    """The published keys as the program's `TransformerConfig`. A
    program that cannot be told this configuration (one without the KDA
    kind or latent attention without positions) stops here, with a name
    for what it lacks."""
    try:
        from lua_mapreduce_tpu.models import transformer as tfm
        from lua_mapreduce_tpu.models.transformer import (
            LatentAttention, LinearAttention, TransformerConfig)
        h, dk, taps = weights_kimilinear.kda_dims(cfg)
        pc = TransformerConfig(
            vocab=cfg["vocab_size"], d_model=cfg["hidden_size"],
            n_heads=cfg["num_attention_heads"],
            n_layers=cfg["num_hidden_layers"],
            d_ff=cfg["intermediate_size"], max_seq=cfg["model_max_length"],
            rope=not cfg["mla_use_nope"],
            positions="none" if cfg["mla_use_nope"] else None, norm="rms",
            norm_eps=float(cfg["rms_norm_eps"]), ffn="swiglu",
            tied_head=cfg["tie_word_embeddings"],
            latent=LatentAttention(
                q_rank=cfg["q_lora_rank"] or 0, kv_rank=cfg["kv_lora_rank"],
                nope_dim=cfg["qk_nope_head_dim"],
                rope_dim=cfg["qk_rope_head_dim"], v_dim=cfg["v_head_dim"]),
            linear=LinearAttention(heads=h, head_dim=dk, conv=taps),
            attn_pattern=pattern(cfg),
            moe_experts=cfg["router_experts"], moe_router="grouped",
            moe_top_k=cfg["num_experts_per_token"],
            moe_groups=cfg["num_expert_group"],
            moe_topk_groups=cfg["topk_group"],
            moe_scale=float(cfg["routed_scaling_factor"]),
            moe_d_ff=cfg["moe_intermediate_size"],
            moe_shared=cfg["num_shared_experts"],
            moe_held=(cfg["first_expert_held"], cfg["num_experts"]),
            moe_first_dense=cfg["first_k_dense_replace"])
        tfm._check_arch(pc)
        return pc
    except (ImportError, TypeError, ValueError) as e:
        raise SystemExit(f"model_kimilinear: the program cannot be told "
                         f"this configuration: {e}") from e


def make_params(cfg: dict, seed: int):
    """The seed's weights in the served type. A program that cannot be
    told this configuration says so first, before 8.6 GB are drawn.

    From here to `judge` the interpreter's cyclic collector is off, as a
    serving process holds it off once it is warm: tracing and compiling
    27 layers of two kinds leave some 470k live objects behind, and a
    full collection walks them all (0.3 s on an 8-core x86 host), at a
    moment the interpreter picks: inside the timed window it is a turn
    of 1,050-1,071 ms among turns of 950, in one run and not the next
    (PERF.md section 6). Set-up and the window free their garbage by
    reference counts, as they do with the collector on."""
    program_config(cfg)
    params = weights_kimilinear.make_params(cfg, seed)
    gc.disable()
    return params


def carried_caches(cfg: dict, t: dict) -> dict:
    """The shapes of the caches a turn of the cell's traffic carries
    through its scan: what the program's prefill leaves, as
    `decode_caches` lays it out (`jax.eval_shape`: nothing is
    computed)."""
    import jax
    import jax.numpy as jnp
    from lua_mapreduce_tpu.models import transformer as tfm
    pc = program_config(cfg)
    total = t["context_len"] + t["n_new"]

    def build():
        empty = tfm._layer_caches(pc, lambda kind, p: kind.empty(
            p, t["batch"], total, jnp.bfloat16))
        return tfm.decode_caches(empty, cfg=pc, p_len=t["context_len"],
                                 total=total)

    return jax.eval_shape(build)


def cache_bytes(caches: dict) -> dict:
    """The caches by kind, from the pytree's shapes: the recurrent
    states and convolution tails (`state_bytes`), their one snapshot
    (`snapshot_bytes`), the latent rows that grow with the context
    (`growing_bytes`)."""
    out = {"state_bytes": 0, "snapshot_bytes": 0, "growing_bytes": 0}
    for name, leaf in caches.items():
        nbytes = int(np.prod(leaf.shape)) * np.dtype(leaf.dtype).itemsize
        kind = name.split("_", 1)[1]
        if kind in ("conv", "state"):
            out["state_bytes"] += nbytes
        elif kind in ("conv0", "state0", "at0"):
            out["snapshot_bytes"] += nbytes
        else:
            out["growing_bytes"] += nbytes
    return out


def say_counters(counters: dict, cfg: dict, t: dict) -> dict:
    """The program's counters of the checked turns, and the caches by
    kind that its scan carries beside what the configuration says they
    hold, as fields of an lmr-trace span where a tracer is installed
    (`lm.session.counters`), and on standard error. Returns them."""
    touched = np.concatenate([np.asarray(c["experts_touched"])
                              for c in counters.values()])
    held = np.concatenate([np.asarray(c["held_assignments"])
                           for c in counters.values()])
    fields = {
        "held_assignments_per_token": float(held.mean() / t["batch"]),
        "held_assignments_expected":
            counts_kimilinear.held_assignments_expected(cfg),
        "experts_touched_mean": float(touched.mean()),
        "experts_touched_max": int(touched.max()),
        "experts_touched_expected":
            counts_kimilinear.experts_touched_expected(cfg, t["batch"]),
    }
    want = counts_kimilinear.cache_bytes(cfg, t["batch"],
                                         t["context_len"] + t["n_new"])
    for name, value in cache_bytes(carried_caches(cfg, t)).items():
        fields[name] = value
        fields[name + "_configured"] = want[name]
    from lua_mapreduce_tpu.trace.span import active_tracer
    tracer = active_tracer()
    if tracer is not None:
        tracer.op("lm.session.counters", time.time(), **fields)
    print("counters " + " ".join(f"{k} {v}" for k, v in fields.items()),
          file=sys.stderr)
    return fields


def cache_bytes_off(fields: dict) -> float:
    """The largest share by which a kind of cache that the program
    carries differs from what the configuration's shapes and types give
    (`say_counters`' fields): 0 where each is as configured, 0.5 for a
    KDA state held in bfloat16. The comparison of tokens cannot see such
    a state: the reference with its state in bfloat16 reads under the
    sound program's own rounding (PERF.md section 4)."""
    return max(abs(fields[k] - fields[k + "_configured"])
               / fields[k + "_configured"]
               for k in ("state_bytes", "snapshot_bytes", "growing_bytes"))


def judge(cell, seed: int, session, picks: list) -> dict:
    """The reference's forward over context + turn for the checked
    rows, after the program's counters are said and left on the cell
    (`counted`). The tails run twice: left to the reference's own router (on
    standard error: what it would read with every near-tied choice
    counted as an error of the logits), and forced to the experts the
    program's router chose. The readings are the forced pass's: the
    tokens' logit gaps, and the share of the program's experts that the
    reference, at the same hidden state, did not choose; and
    `cache_bytes_off`, the caches' precision against the
    configuration's. The collector is on again from here, after the
    window (`make_params`)."""
    gc.enable()
    cfg = cell.config
    # the counters on the cell, for `readers/kimilinear_moe.py`
    cell.counted = say_counters(session.counters, cfg, cell.traffic)
    context = session.context()
    per_row = []
    for row in sorted({row for _, row in picks}):
        tails, served, experts = turns_of(session, picks, row)
        state = reference_kimilinear.context_pass(cfg, seed, context[row])
        free, forced = reference_kimilinear.tails_pass(
            cfg, seed, state, tails, [{}, {"forced": experts}])
        per_row.append((served, experts, free, forced))
    served = np.concatenate([p[0] for p in per_row], 0)
    experts = np.concatenate([p[1] for p in per_row], 1)
    merged = [{"logits": np.concatenate([p[j]["logits"] for p in per_row], 0),
               "experts": np.concatenate([p[j]["experts"] for p in per_row],
                                         1)} for j in (2, 3)]
    print(f"served tokens: {len(np.unique(served))} distinct of "
          f"{served.size}", file=sys.stderr)
    left = readings_of(served, experts, merged[0])
    print("left to its own choices the reference reads "
          + " ".join(f"{k} {v:.6g}" for k, v in left.items()),
          file=sys.stderr)
    return dict(readings_of(served, experts, merged[1]),
                cache_bytes_off=cache_bytes_off(cell.counted))
