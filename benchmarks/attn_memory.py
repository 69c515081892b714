"""Quantify the flash-attention memory argument (VERDICT r3 item 6).

The ``auto`` policy routes flash_attention to Pallas on memory grounds
(ops/__init__.py): the XLA composition materializes the (L, L) score
matrix in HBM in both directions while the fused kernel pair never does.
DESIGN.md §9 asserted this ("1 GB at L=4096"); this script MEASURES it:

- **XLA side**: compile the reference composition (forward, and
  forward+backward as a train-shaped loss) and read the compiler's own
  buffer assignment (``compiled.memory_analysis()``) — temp bytes are
  exactly the materialized intermediates the policy claims exist.
- **Flash side**: the kernel's HBM residents are only the arrays the
  custom-VJP saves (q, k, v, o, lse, Δ + the cotangents), all O(L);
  VMEM working set is the block tiles. Both are computed from the same
  shape arithmetic the kernel's BlockSpecs use, next to the analytic
  O(L²) term for comparison.

Writes benchmarks/results/attn_memory.json with the backend recorded —
CPU buffer assignment is XLA's, not the TPU's, but the O(L²) temp term
is a lowering property, not a backend one; re-run on TPU appends a
tpu-keyed section.

Usage: python benchmarks/attn_memory.py
"""

from __future__ import annotations

import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

RESULTS = os.path.join(REPO, "benchmarks", "results", "attn_memory.json")

# the LM-family shapes kernel_bench.py benches (b, h, L, d)
SHAPES = [(4, 8, 2048, 128), (2, 8, 4096, 128), (1, 8, 8192, 128)]


def xla_measured(b, h, l, d):
    """Compiler-reported bytes for the XLA composition at (b,h,l,d)."""
    import jax
    import jax.numpy as jnp

    from lua_mapreduce_tpu.ops.attention import _attn_reference_xla

    q = jax.ShapeDtypeStruct((b, l, h, d), jnp.bfloat16)
    scale = d ** -0.5

    def fwd(q_, k_, v_):
        return _attn_reference_xla(q_, k_, v_, True, scale)

    def loss(q_, k_, v_):
        return _attn_reference_xla(q_, k_, v_, True, scale).sum()

    out = {}
    for name, fn in (("fwd", fwd),
                     ("grad", lambda *a: jax.grad(loss, argnums=(0, 1, 2))(*a))):
        ma = jax.jit(fn).lower(q, q, q).compile().memory_analysis()
        out[name] = {
            "temp_bytes": ma.temp_size_in_bytes,
            "arg_bytes": ma.argument_size_in_bytes,
            "out_bytes": ma.output_size_in_bytes,
        }
    return out


def flash_analytic(b, h, l, d, block_q=128, block_k=128):
    """Flash kernel pair's memory by construction (ops/attention.py):
    HBM holds only O(L) arrays; VMEM holds the per-step tiles. Row
    state (lse, Δ) rides lane-replicated ×_LANES for Mosaic block
    legality — counted here at its real replicated size."""
    from lua_mapreduce_tpu.ops.attention import _LANES

    bf16, f32 = 2, 4
    qkv = 3 * b * l * h * d * bf16
    o = b * l * h * d * bf16
    lse = b * l * h * f32 * _LANES               # lane-replicated out
    # backward residuals: (q, k, v, o, lse) saved + do cotangent + Δ row
    # (both lane-replicated operands) + dq/dk/dv f32 accumulators
    bwd_extra = (b * l * h * d * bf16            # do
                 + 2 * b * l * h * f32 * _LANES  # lse_r, delta_r
                 + 3 * b * l * h * d * f32)      # dq, dk, dv f32 accums
    vmem_fwd = (block_q * d * bf16 + 2 * block_k * d * bf16
                + block_q * block_k * f32        # score tile
                + block_q * d * f32              # o accumulator
                + 2 * block_q * _LANES * f32)    # m, l scratch
    return {
        "hbm_fwd_bytes": qkv + o + lse,
        "hbm_grad_bytes": qkv + o + lse + bwd_extra,
        "vmem_tile_bytes": vmem_fwd,
        "xla_score_term_bytes": b * h * l * l * f32,  # the O(L²) p matrix
    }


def main() -> None:
    from lua_mapreduce_tpu.utils.jax_env import place_compile_cache
    place_compile_cache()
    import jax

    backend = jax.default_backend()
    rows = {}
    for b, h, l, d in SHAPES:
        key = f"b{b}_h{h}_L{l}_d{d}"
        meas = xla_measured(b, h, l, d)
        ana = flash_analytic(b, h, l, d)
        rows[key] = {"xla_measured": meas, "flash": ana,
                     "xla_grad_temp_over_flash_grad_hbm": round(
                         meas["grad"]["temp_bytes"] /
                         max(1, ana["hbm_grad_bytes"]), 1)}
        print(f"{key}: xla grad temp {meas['grad']['temp_bytes']/2**30:.2f} "
              f"GiB vs flash grad HBM {ana['hbm_grad_bytes']/2**30:.3f} GiB "
              f"(O(L²) term {ana['xla_score_term_bytes']/2**30:.2f} GiB)",
              file=sys.stderr)

    existing = {}
    if os.path.exists(RESULTS):
        with open(RESULTS) as f:
            existing = json.load(f)
    existing[backend] = rows
    with open(RESULTS, "w") as f:
        json.dump(existing, f, indent=1)
        f.write("\n")
    print(json.dumps({backend: rows}))


if __name__ == "__main__":
    main()


def utest() -> None:
    """Shape arithmetic sanity: the O(L²) term dominates at L=4096."""
    a = flash_analytic(2, 8, 4096, 128)
    assert a["xla_score_term_bytes"] == 2 * 8 * 4096 * 4096 * 4
    assert a["xla_score_term_bytes"] > 5 * a["hbm_grad_bytes"]
