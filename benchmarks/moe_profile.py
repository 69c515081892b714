"""Sprint phase B: where do the MoE step's milliseconds go? (VERDICT r4
missing-5 / next-4: transformer_step_moe8 measured 472 ms vs 164 ms
dense with no diagnosis.)

The CPU cost analysis already names the suspect — at the bench tile
(T=16384, E=8, C=2T/E=4096, d=1024, ff=4096) the one-hot dispatch and
combine einsums of the original routing cost 2×1.1e12 MXU FLOPs per
layer (8× the expert FFN's 2.75e11-useful-FLOP share) and stream two
2 GiB (T,E,C) f32 one-hot tensors through HBM. Across 8 layers
fwd+bwd that predicts ~310 ms of pure routing overhead — the measured
gap is 308 ms. This script pins that story ON-CHIP, component by
component, and measures the fix (the sort+gather routing now default
in parallel/moe.py) against the einsum oracle at the exact bench
shape. Writes benchmarks/results/moe_profile.json.

Usage: python benchmarks/moe_profile.py
"""

from __future__ import annotations

import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from benchmarks.kernel_bench import _call_overhead, _measure_op  # noqa: E402

OUT = os.path.join(REPO, "benchmarks", "results", "moe_profile.json")

T, E, D, FF = 16384, 8, 1024, 4096


def profile(T=T, E=E, D=D, FF=FF, cap=None, target_s=0.35) -> dict:
    """The measured component breakdown; shape-parameterized so the CPU
    suite can smoke the exact code path the TPU window runs."""
    import jax
    import jax.numpy as jnp

    from lua_mapreduce_tpu.parallel import moe

    CAP = cap if cap is not None else 2 * T // E     # the bench's cap2x
    params = moe.init_moe(jax.random.PRNGKey(0), D, FF, E, jnp.bfloat16)
    x = jax.random.normal(jax.random.PRNGKey(1), (T, D), jnp.bfloat16)
    overhead = _call_overhead()
    results = {"device_kind": jax.devices()[0].device_kind,
               "config": f"T{T} E{E} cap{CAP} d{D} ff{FF} bf16 tokens "
                         f"(the transformer_step_moe8 tile)"}

    def timed(name, fn, args, flops_note=None, i0=None):
        # i0 = index of the array argument _measure_op perturbs per
        # iteration (it must not be the params DICT)
        if i0 is None:
            i0 = len(args) - 1
        def run(*a):
            out = fn(*a)
            return jnp.asarray(out, jnp.float32).reshape(-1)[:1]
        try:
            per_op, _ = _measure_op(run, args, i0, 64, target_s, overhead)
            row = {"ms": round(per_op * 1e3, 3)}
        except Exception as e:
            row = {"error": f"{type(e).__name__}: {e}"[:200]}
        if flops_note:
            row["analytic_flops"] = flops_note
        results[name] = row
        print(f"{name}: {row}", file=sys.stderr)
        return row

    def layer(impl):
        def f(params, x):
            out, aux = moe.moe_ffn_reference(params, x, capacity=CAP,
                                             impl=impl)
            return out.astype(jnp.float32).sum() + aux
        return f

    def layer_grad(impl):
        def f(params, x):
            g = jax.grad(layer(impl), argnums=(0, 1))(params, x)
            return (sum(v.astype(jnp.float32).sum()
                        for v in g[0].values())
                    + g[1].astype(jnp.float32).sum())
        return f

    def dense_ffn(w1, w2, x):
        h = jax.nn.gelu(x.astype(jnp.float32) @ w1)
        return h @ w2

    w1 = jax.random.normal(jax.random.PRNGKey(2), (D, FF), jnp.float32)
    w2 = jax.random.normal(jax.random.PRNGKey(3), (FF, D), jnp.float32)

    # --- component times (one layer, the bench tile) ---
    timed("dense_ffn_fwd", lambda x: dense_ffn(w1, w2, x), (x,),
          f"{2 * T * 2 * D * FF:.3e}")
    timed("dense_ffn_fwdbwd",
          lambda x: jax.grad(lambda x: dense_ffn(w1, w2, x).sum())(x),
          (x,))
    timed("moe_einsum_fwd", lambda p, x: layer("einsum")(p, x),
          (params, x),
          f"dispatch+combine {2 * 2 * T * E * CAP * D:.3e} + "
          f"expert_ffn {2 * E * CAP * 2 * D * FF:.3e}")
    timed("moe_einsum_fwdbwd", layer_grad("einsum"), (params, x))
    timed("moe_sorted_fwd", lambda p, x: layer("sorted")(p, x),
          (params, x),
          f"expert_ffn {2 * E * CAP * 2 * D * FF:.3e} + O(T log T) sort"
          f" + O((Tk+EC)d) gather bytes")
    timed("moe_sorted_fwdbwd", layer_grad("sorted"), (params, x))

    # routing machinery alone (no expert FFN): sorted route + gathers
    def route_only(p, x):
        (tok_of_slot, round_of_slot, slot_valid, slot_of_tok,
         gate_of_tok, aux) = moe._route_sorted(x, p["moe_router_W"],
                                               E, CAP)
        xe = moe._dispatch_gather(x.astype(jnp.float32), tok_of_slot,
                                  slot_valid, slot_of_tok)
        return xe.sum() + aux
    timed("sorted_route_and_gather_fwd", route_only, (params, x))

    def expert_only(xe):
        w = {k[4:]: v for k, v in params.items() if k.startswith("moe_w")
             or k.startswith("moe_b")}
        return moe._expert_ffn(w["w1"].astype(jnp.float32),
                               w["b1"].astype(jnp.float32),
                               w["w2"].astype(jnp.float32),
                               w["b2"].astype(jnp.float32), xe)
    xe = jax.random.normal(jax.random.PRNGKey(4), (E, CAP, D),
                           jnp.float32)
    timed("expert_ffn_only_fwd", expert_only, (xe,),
          f"{2 * E * CAP * 2 * D * FF:.3e}")

    # --- compiled cost analysis (XLA's own accounting, TPU compile) ---
    for impl in ("einsum", "sorted"):
        try:
            ca = (jax.jit(layer_grad(impl))
                  .lower(params, x).compile().cost_analysis())
            results[f"cost_analysis_{impl}_fwdbwd"] = {
                "flops": float(ca.get("flops", 0.0)),
                "bytes_accessed": float(ca.get("bytes accessed", 0.0)),
            }
        except Exception as e:
            results[f"cost_analysis_{impl}_fwdbwd"] = {
                "error": f"{type(e).__name__}: {e}"[:200]}

    return results


def main() -> int:
    from lua_mapreduce_tpu.utils.jax_env import (place_compile_cache,
                                                 require_tpu)
    place_compile_cache()
    require_tpu("moe_profile.py")

    results = profile()
    results["note"] = (
        "One MoE FFN layer at the transformer_step_moe8 tile. The CPU "
        "HLO cost analysis attributes 2.2e12 of the einsum impl's "
        "2.75e12 fwd FLOPs to the one-hot dispatch/combine contractions "
        "(8x the expert FFN's useful work) — 8 layers fwd+bwd predicted "
        "~310 ms of the measured 308 ms dense-vs-moe8 step gap. The "
        "sorted impl (argsort + row gathers, now the default) removes "
        "those contractions and the (T,E,C) HBM streams; "
        "transformer_step_moe8 in kernels.json is re-measured with it "
        "by the same sprint phase.")
    print(json.dumps(results, indent=1))
    with open(OUT, "w") as f:
        json.dump(results, f, indent=1)
        f.write("\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
