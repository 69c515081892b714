"""TPU kernel library (Pallas) — the APRIL-ANN-toolkit equivalent.

The reference keeps its tensor kernels in the external APRIL-ANN C++/CUDA
toolkit (examples/APRIL-ANN/common.lua:3-4; SURVEY.md §2.4): matrix ops
(``axpy``, common.lua:133), conv/pool/softmax for its NN examples. This
package is the TPU-native replacement: Pallas kernels tiled for the MXU
(128×128 systolic array) and VPU, with XLA reference implementations used
for (a) correctness tests and (b) non-TPU backends.

Backend policy (``default_backend``): per-op, not dogmatic. On TPU
each op's ``auto`` resolves through ``_TPU_AUTO_POLICY`` — a
hand-written kernel is a means, not an end, and for some ops XLA's
lowering is the better TPU program. Off-TPU everything resolves to
"xla" (Pallas-TPU kernels only lower on TPU). Every op takes
``backend=`` with values "auto" | "pallas" | "xla" | "pallas_interpret"
(interpreter mode, for CPU tests of the kernel path).
"""

from __future__ import annotations

import jax

# Routing on TPU. Speed: not measured on the current installation;
# ROADMAP A3-A5. What is on record for it: every "pallas" entry is
# compiled by the chip's compiler and compared with its "xla" twin by
# chip_smoke.py's kernel stage. The structural reasons behind the
# routing: conv2d's im2col patch round trip costs more than XLA's whole
# conv (DESIGN.md §8b); flash attention never materializes the O(L²)
# score matrix the XLA composition holds across fwd+bwd (DESIGN §9).
_TPU_AUTO_POLICY = {
    "matmul": "xla",
    "conv2d": "xla",
    "softmax": "xla",
    "maxpool2d": "pallas",
    "avgpool2d": "pallas",
    "flash_attention": "pallas",
    # weight-only int8: the kernel is the POINT (int8 tiles streamed
    # from HBM, dequant in VMEM) — the XLA composition materializes a
    # dequantized bf16 copy that jit hoists out of decode loops,
    # forfeiting the halved weight traffic the op exists for
    "q8_matmul": "pallas",
    # flash-decode (ops/decode.py): one query position vs the KV
    # cache, chunk-streamed with dynamic dead-chunk DMA elision
    "decode_attention": "pallas",
    # latent flash-decode (ops/mla_decode.py): a cached row is key and
    # value at once and crosses the bus once; XLA's composition reads
    # the cache for the scores and again for the values
    "mla_decode_attention": "pallas",
    # the held experts of a decode step (ops/moe_held.py): one call
    # streams the touched experts' weights back to back; XLA's
    # composition is a conditional an expert, each a cold stream
    "moe_held": "pallas",
}


def default_backend(op: str | None = None) -> str:
    """Resolved backend for ``op`` on the current platform: the
    ``_TPU_AUTO_POLICY`` route on TPU, 'xla' elsewhere."""
    if jax.default_backend() != "tpu":
        return "xla"
    return _TPU_AUTO_POLICY.get(op, "pallas")


def out_struct(shape, dtype, *like) -> jax.ShapeDtypeStruct:
    """``pallas_call`` out_shape that survives shard_map's vma typing.

    JAX checks varying-mesh-axes (vma) types inside ``shard_map`` and
    rejects a plain ``ShapeDtypeStruct`` out_shape; the output of a
    kernel varies over exactly the union of axes its operands vary over,
    so that union is propagated from ``like``. Outside shard_map every
    operand's vma is empty and this degrades to the plain struct.
    """
    vma = (frozenset().union(*(jax.typeof(a).vma for a in like))
           if like else frozenset())
    return jax.ShapeDtypeStruct(shape, dtype, vma=vma)


def resolve_backend(backend: str, op: str | None = None) -> str:
    if backend == "auto":
        return default_backend(op)
    if backend not in ("pallas", "xla", "pallas_interpret"):
        raise ValueError(f"unknown backend {backend!r}")
    return backend


from lua_mapreduce_tpu.ops.matmul import matmul  # noqa: E402
from lua_mapreduce_tpu.ops.softmax import log_softmax, softmax  # noqa: E402
from lua_mapreduce_tpu.ops.conv import conv2d  # noqa: E402
from lua_mapreduce_tpu.ops.pool import avgpool2d, maxpool2d  # noqa: E402
from lua_mapreduce_tpu.ops.attention import flash_attention  # noqa: E402
from lua_mapreduce_tpu.ops.decode import decode_attention  # noqa: E402
from lua_mapreduce_tpu.ops.mla_decode import (  # noqa: E402
    mla_causal_attention, mla_decode_attention)
from lua_mapreduce_tpu.ops.q8 import q8_matmul, quantize_q8  # noqa: E402

__all__ = [
    "default_backend", "resolve_backend",
    "matmul", "log_softmax", "softmax", "conv2d",
    "maxpool2d", "avgpool2d", "flash_attention", "decode_attention",
    "mla_decode_attention", "mla_causal_attention", "q8_matmul", "quantize_q8",
]
