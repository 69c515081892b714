"""Device-level tracing: the TPU-native deepening of utils/stats.py.

The reference's observability is host-side phase timing (map/reduce
cluster times, utils/stats.py's analog of server.lua's counters). On an
accelerator the interesting time is INSIDE the jitted step — kernel
schedules, collective overlap, HBM stalls — which only the XLA profiler
sees. :func:`device_trace` wraps any region in a jax.profiler trace
whose output TensorBoard (or xprof) renders; train_lm's ``--profile``
flag wires it around the train loop, and the distributed worker/server
CLIs (cli/execute_worker.py, cli/execute_server.py) expose the same
``--profile DIR`` around their execute/loop.
The LM program names its own device work for that trace: :func:`scope`
(``jax.named_scope``) puts the :data:`LM_SCOPES` into every operation's
``op_name``, the Pallas kernels carry the pinned :data:`LM_KERNELS`
names, and :func:`annotate` writes the one host span
(:data:`LM_HOST_SPANS`) onto the profiler's own clock.
:func:`maybe_annotate` bridges lmr-trace span names (DESIGN §22) into
the device profile so host and TPU timelines correlate.
"""

from __future__ import annotations

import contextlib
import os

# The names the LM program gives its device work (models/transformer.py,
# parallel/ring_attention.py); they are the contract with whatever
# reads a trace (perfbench/scope_reader.py; PERF.md section 3).
LM_SCOPES = ("lm.loss", "lm.embed", "lm.attn", "lm.ffn", "lm.head",
             "lm.opt", "lm.ring", "lm.prefill", "lm.first_token",
             "lm.decode",
             # inside lm.attn, latent attention: its projections, the
             # indexer's scores and top-k, the gather and attention; or,
             # without an indexer, attention over the whole cache
             "lm.mla", "lm.indexer", "lm.sparse", "lm.latent",
             # inside lm.attn, a hybrid stack's mixers, projections and
             # all: the state-space layer, the gated memory unit, and
             # differential attention over a window, over the whole
             # cache, and over another layer's cache
             "lm.ssm", "lm.gmu", "lm.swa", "lm.full", "lm.cross",
             # inside lm.ffn, the grouped expert layer
             "lm.moe.route", "lm.moe.experts", "lm.moe.shared")
LM_HOST_SPANS = ("lm.shard_batch",)
# ``name=`` of the pallas_calls (ops/attention.py, ops/decode.py,
# ops/mla_decode.py, ops/q8.py): the custom call's HLO result is ``%<name>.<n>`` whatever
# scope or transformation encloses it.
LM_KERNELS = ("flash_pallas", "flash_bwd_pallas_dq", "flash_bwd_pallas_dkv",
              "_decode_pallas", "q8_matmul_pallas", "_mla_decode_pallas")
# the jitted functions, so the trace's programs are ``jit_<name>``
LM_PROGRAMS = ("lm_train_step", "greedy_decode", "decode_from")


@contextlib.contextmanager
def device_trace(log_dir: str):
    """Trace everything inside the ``with`` to ``log_dir`` (created if
    missing). Traces include host annotations (:func:`annotate`, JAX's
    own ``PjitFunction(...)`` rows) and, on TPU, the device timeline;
    view with TensorBoard's profile plugin. The profiler is opened as
    the repo's benchmark opens it (``perfbench/harness.traced``): no
    Python call stacks (``python_tracer_level=0``) and
    ``host_tracer_level=2``, so an operator's trace is the kind the
    benchmark's readers are checked on, and as light. Entering the
    trace initializes the JAX backend."""
    import jax

    os.makedirs(log_dir, exist_ok=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    with jax.profiler.trace(log_dir, profiler_options=opts):
        yield log_dir


def scope(name: str):
    """``jax.named_scope``: every operation traced inside carries
    ``name`` in its ``op_name`` metadata (and through ``jvp(...)`` /
    ``transpose(...)`` in the backward pass). Trace-time only: the
    compiled operations do not change."""
    import jax

    return jax.named_scope(name)


def annotate(name: str):
    """Named sub-span inside a device_trace (jax.profiler.TraceAnnotation
    passthrough) — marks host-side phases so device ops group under
    readable labels."""
    import jax

    return jax.profiler.TraceAnnotation(name)


def maybe_annotate(name: str):
    """Best-effort :func:`annotate`: a no-op context when JAX (or the
    profiler) is unavailable. This is the lmr-trace bridge (DESIGN §22):
    a Tracer built with ``annotate=True`` — the ``--trace --profile``
    combination on the worker/server CLIs — enters one of these per
    span, so the SAME span names appear on the XLA profile's host rows
    and the Perfetto timeline exported from the store, and the host and
    device views correlate by name. Telemetry must never sink a job
    body, hence the swallow-to-no-op shape."""
    try:
        return annotate(name)
    except Exception:
        return contextlib.nullcontext()
