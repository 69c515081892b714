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

Set-up is the host time no window sees, and the **build log**
(:func:`build_log`) times it from inside: one row for every program
the process builds (trace, lower, then compile or the persistent
cache's fetch, by program name, with what ran until the next build),
from JAX's own monitoring events. It is always on: importing
``models.transformer`` registers its two listeners, once a process, so
it is live before a launcher's first program. That is free where
nothing is built, because JAX calls a listener only when it fires a
build event: a window that builds no program executes no line of it.
Its readers: ``examples/lm/train_lm.py`` (the table after the first
step, and a line for any later step that built something),
``chip_smoke.py`` (each stage's counts and rows), and the benchmark's
``perfbench/readers/build_log.py`` (the four ``*.setup`` metrics).
The log is its one sink: no lmr-trace span is recorded for a build (no
LM launcher installs a Tracer that anything drains).
"""

from __future__ import annotations

import contextlib
import functools
import os
import threading
import time

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
             # inside lm.attn, Kimi Delta Attention whole: projections,
             # convolution, gates, the delta rule, norm, out projection
             "lm.kda",
             # inside lm.ffn, the grouped expert layer
             "lm.moe.route", "lm.moe.experts", "lm.moe.shared")
LM_HOST_SPANS = ("lm.shard_batch",)
# ``name=`` of the pallas_calls (ops/attention.py, ops/decode.py,
# ops/mla_decode.py, ops/moe_held.py, ops/q8.py, ops/kda.py): the custom
# call's HLO result is ``%<name>.<n>`` whatever scope or transformation
# encloses it.
LM_KERNELS = ("flash_pallas", "flash_bwd_pallas_dq", "flash_bwd_pallas_dkv",
              "_decode_pallas", "q8_matmul_pallas", "_mla_decode_pallas",
              "_moe_held_pallas", "_kda_step_pallas")
# the jitted functions, so the trace's programs are ``jit_<name>``
LM_PROGRAMS = ("lm_train_step", "greedy_decode", "decode_from")

_TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
_LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_BUILD_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_EVENTS = {"/jax/compilation_cache/cache_hits": "hit",
                 "/jax/compilation_cache/cache_misses": "miss"}


def _program(fun_name: str) -> str:
    """``f`` of the ``jit(f)`` that lowerings and compiles are named."""
    return (fun_name[4:-1] if fun_name.startswith("jit(")
            and fun_name.endswith(")") else fun_name)


def _process_start():
    """Epoch seconds at which this process started: its start in clock
    ticks since boot (``/proc/self/stat``) beside the time since boot
    now (``/proc/uptime``, hundredths). None where either is missing."""
    try:
        with open("/proc/self/stat") as f:
            ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            up = float(f.read().split()[0])
        return time.time() - up + ticks / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError):
        return None


class _Pairing(threading.local):
    """One thread's events not yet made a row: the traces kept, the
    last lowering, the cache's word inside the open compile; and when
    the thread's last row ended."""

    def __init__(self):
        self.traces, self.lower, self.cache = [], None, None
        self.built_t1 = 0.0


class BuildLog:
    """Every program this process built, from JAX's monitoring events.

    JAX fires a time span (start and end on ``time.time()``) for each
    trace (``fun_name='f'``), each lowering and each backend compile
    (``fun_name='jit(f)'``; the compile's span holds the persistent
    cache's fetch on a hit, and the cache's hit or miss event fires
    inside it). A row is made when a backend compile ends, so a ``jit``
    traced inside another makes none: it is listed under the outer
    row's ``enclosed``, the three longest traces inside its trace span.
    A phase this build did not run reads 0: the trace where JAX served
    it from its cache (an ``eval_shape`` made it, rows ago) or an
    earlier ``lower()`` took it, trace and lowering where another
    program was lowered in between. So a row's ``t0..t1`` holds no
    other row, and start, rows and ``after_s`` tile the process's time.

    Keeps the first :data:`MAX_ROWS` rows and counts the rest
    (``dropped``); the three counts go on. Listeners run on the thread
    that builds, and each thread pairs its own events, of which it
    keeps the newest :data:`MAX_KEPT` traces: those inside a trace
    still open (one big program's trace encloses thousands), and those
    nothing lowered."""

    MAX_ROWS = 4096
    MAX_KEPT = 4096

    def __init__(self):
        self.programs = self.hits = self.misses = self.dropped = 0
        self._rows = []
        self._lock = threading.Lock()
        self._tls = _Pairing()

    def register(self) -> "BuildLog":
        import jax
        jax.monitoring.register_event_time_span_listener(self._span)
        jax.monitoring.register_event_listener(self._event)
        return self

    # -- JAX's side -----------------------------------------------------

    def _event(self, event, **kw):
        cache = _CACHE_EVENTS.get(event)
        if cache:
            self._tls.cache = cache

    def _span(self, event, t0, t1, fun_name="", **kw):
        tls = self._tls
        if event == _TRACE_EVENT:
            # traces end innermost first: the ones kept that began no
            # earlier than this one lie inside it
            inside, kept = [], tls.traces
            while kept and kept[-1]["t0"] >= t0:
                inner = kept.pop()
                inside += inner["enclosed"] + [(inner["t1"] - inner["t0"],
                                                inner["program"])]
            inside.sort(key=lambda e: -e[0])
            kept.append({"program": fun_name, "t0": t0, "t1": t1,
                         "enclosed": inside[:3]})
            del kept[:-self.MAX_KEPT]
        elif event == _LOWER_EVENT:
            # what the lowering traced itself (dozens of small
            # functions for one random draw) is part of it; the trace
            # it lowers is the newest one kept under its name, if that
            # ended after this thread's last row (none where an earlier
            # lowering took it; an older one was served from JAX's own
            # cache, and its time lies in the rows it was made between)
            kept, trace = tls.traces, None
            while kept and kept[-1]["t0"] >= t0:
                kept.pop()
            for i in reversed(range(len(kept))):
                if kept[i]["program"] == _program(fun_name):
                    if kept[i]["t1"] >= tls.built_t1:
                        trace = kept[i]
                    del kept[i:]
                    break
            tls.lower = {"name": fun_name, "trace": trace, "t0": t0, "t1": t1}
        elif event == _BUILD_EVENT:
            self._built(fun_name, t0, t1)

    def _built(self, fun_name, t0, t1):
        tls = self._tls
        program = _program(fun_name)
        cache, tls.cache = tls.cache or "off", None
        lower, tls.lower, tls.built_t1 = tls.lower, None, t1
        if lower is not None and lower["name"] != fun_name:
            lower = None
        trace = lower and lower["trace"]
        spans = [part and (part["t0"], part["t1"]) for part in (trace, lower)]
        spans.append((t0, t1))
        trace_s, lower_s, build_s = (s[1] - s[0] if s else 0.0 for s in spans)
        row = {"program": program, "trace_s": trace_s, "lower_s": lower_s,
               "build_s": build_s, "cache": cache,
               "t0": min(s[0] for s in spans if s), "t1": t1,
               "enclosed": [{"program": name, "trace_s": seconds}
                            for seconds, name in (trace["enclosed"] if trace
                                                  else [])]}
        with self._lock:
            self.programs += 1
            self.hits += cache == "hit"
            self.misses += cache == "miss"
            if len(self._rows) < self.MAX_ROWS:
                self._rows.append(row)
            else:
                self.dropped += 1

    # -- the readers' side ------------------------------------------------

    @functools.cached_property
    def process_start(self):
        """Epoch seconds at which the process began, read when first
        asked for (registering, at an import, opens no file)."""
        return _process_start()

    def counts(self) -> dict:
        with self._lock:
            return {"programs": self.programs,
                    "persistent_cache_hits": self.hits,
                    "persistent_cache_misses": self.misses}

    def rows(self, start: int = 0) -> list:
        """Copies of the rows from number ``start`` on, in the order
        they were built, each with ``after_s``: seconds from its ``t1``
        to the next row's ``t0`` (the program's first run, and whatever
        the host did before it built the next; None on the last row)."""
        with self._lock:
            rows = [dict(r) for r in self._rows]
        for row, after in zip(rows, rows[1:] + [None]):
            row["after_s"] = after["t0"] - row["t1"] if after else None
        return rows[start:]


def build_table(rows: list, process_start=None) -> str:
    """The rows as the launchers print them: when each build began
    (from the process's start where that is known, else from the first
    row), program, trace, lower, build, hit or miss, then ``after_s``."""
    origin = process_start if process_start is not None else (
        rows[0]["t0"] if rows else 0.0)
    lines = []
    if process_start is not None and rows:
        lines.append(f"build log: process start to first build "
                     f"{rows[0]['t0'] - process_start:.3f} s")
    lines.append(f"build log: {'at_s':>9} {'trace_s':>8} {'lower_s':>8} "
                 f"{'build_s':>8} {'cache':>5} {'after_s':>9}  program")
    for r in rows:
        after = "" if r["after_s"] is None else f"{r['after_s']:.3f}"
        inside = ", ".join(f"{e['program']} {e['trace_s']:.3f}"
                           for e in r["enclosed"])
        lines.append(
            f"build log: {r['t0'] - origin:9.3f} {r['trace_s']:8.3f} "
            f"{r['lower_s']:8.3f} {r['build_s']:8.3f} {r['cache']:>5} "
            f"{after:>9}  {r['program']}"
            + (f"  (traces inside: {inside})" if inside else ""))
    return "\n".join(lines)


_build_log = None
_build_log_lock = threading.Lock()


def build_log() -> BuildLog:
    """The process's one build log; the first call registers its
    listeners with JAX, any later call returns the same log."""
    global _build_log
    with _build_log_lock:
        if _build_log is None:
            _build_log = BuildLog().register()
        return _build_log


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
