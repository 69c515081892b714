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
:func:`maybe_annotate` bridges lmr-trace span names (DESIGN §22) into
the device profile so host and TPU timelines correlate.
"""

from __future__ import annotations

import contextlib
import os


@contextlib.contextmanager
def device_trace(log_dir: str):
    """Trace everything inside the ``with`` to ``log_dir`` (created if
    missing). Traces include host Python annotations and, on TPU, the
    device timeline; view with TensorBoard's profile plugin. Entering
    the trace initializes the JAX backend."""
    import jax

    os.makedirs(log_dir, exist_ok=True)
    with jax.profiler.trace(log_dir):
        yield log_dir


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
