"""What every driver shares: the manifest and the data files found by
name, the device and its peaks, the compile cache, the count of programs
built, the measured loop, the trace, and the result's last line."""

from __future__ import annotations

import importlib
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def load_json(*parts) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


class Cell:
    """One entry of BENCHMARK.json's `workloads` with the data files it
    names, all found under ``root`` (the checkout)."""

    def __init__(self, workload: str, root: str = ROOT):
        self.root = root
        self.manifest = manifest = load_json(root, "BENCHMARK.json")
        cells = {w["name"]: w for w in manifest["workloads"]}
        if workload not in cells:
            raise SystemExit(f"no workload {workload!r} in BENCHMARK.json "
                             f"(has: {sorted(cells)})")
        self.entry = cells[workload]
        self.name = workload
        self.chips = self.entry["chips"]
        configs = {c["name"]: c for c in manifest["configs"]}
        self.config = load_json(root, configs[self.entry["config"]]["file"])
        self.traffic = self.data("traffic", self.entry["traffic"])
        self.limits = self.data("limits", workload)

    def data(self, kind: str, name: str) -> dict:
        """`<first of paths>/<kind>/<name>.json`."""
        return load_json(self.root, self.manifest["paths"][0], kind,
                         name + ".json")

    def reports(self, metric: dict) -> bool:
        return self.name in metric.get("workloads", [self.name])

    def metrics(self, group: str) -> list:
        return [m for m in self.manifest[group] if self.reports(m)]


def driver_for(kind: str):
    return importlib.import_module(f"perfbench.drivers.{kind}")


# --------------------------------------------------------------------------
# the device
# --------------------------------------------------------------------------

def require_chips(chips: int, platform: str = "tpu") -> list:
    """The devices this cell runs on, or exit: nothing is measured on
    another platform or on fewer chips than the cell asks for."""
    import jax
    try:
        devices = jax.devices()
    except RuntimeError as e:
        raise SystemExit(f"perfbench: no accelerator: {e}")
    if devices[0].platform != platform:
        raise SystemExit(f"perfbench: measures a {platform}, but JAX's "
                         f"backend is {devices[0].platform!r}")
    if len(devices) < chips:
        raise SystemExit(f"perfbench: the cell needs {chips} chips, JAX "
                         f"finds {len(devices)}")
    return devices[:chips]


def peaks_of(device) -> dict:
    table = load_json(HERE, "peaks.json")["device_kind"]
    if device.device_kind not in table:
        raise SystemExit(f"perfbench: no peaks on record for device_kind "
                         f"{device.device_kind!r}; add it to peaks.json "
                         f"with its source")
    return table[device.device_kind]


def place_compile_cache() -> str:
    """JAX's persistent cache where JAX_COMPILATION_CACHE_DIR says, else
    at a fixed path in the checkout, every program kept."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(ROOT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def memory_peak_bytes(devices) -> int:
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)


class CompileLog:
    """Executables built (compiled or fetched from the persistent cache),
    from JAX's own monitoring events."""

    def __init__(self):
        import jax
        self.programs = self.hits = self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._timed)
        jax.monitoring.register_event_listener(self._event)

    def _timed(self, event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.programs += 1

    def _event(self, event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1


# --------------------------------------------------------------------------
# the measured loop
# --------------------------------------------------------------------------

def measured_loop(one, seconds: float, at_most: int | None = None) -> dict:
    """Call ``one(i)`` back to back (a closed loop of one caller) until
    ``seconds`` have passed or ``at_most`` calls are done. ``one``
    prepares its input, then returns a function that does the timed
    work and waits for it. Returns every call's time and the window's
    length, which ends when the last call that was started has ended."""
    import jax
    times = []
    opened = time.perf_counter()
    while True:
        with jax.profiler.TraceAnnotation("pb.feed"):
            work = one(len(times))
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("pb.call"):
            work()
        now = time.perf_counter()
        times.append(now - t0)
        if now - opened >= seconds or len(times) == at_most:
            return {"times": times, "window_s": now - opened}


def percentile_nearest_rank(values: list, q: float) -> float:
    ordered = sorted(values)
    rank = max(1, -(-len(ordered) * q // 100))
    return ordered[int(rank) - 1]


def traced(run, trace_dir: str):
    """Run ``run()`` under the profiler (device and host annotations,
    no Python call stacks) and return its result and the trace file."""
    import glob

    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    try:
        result = run()
    finally:
        jax.profiler.stop_trace()
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise SystemExit(f"perfbench: the profiler wrote no trace under "
                         f"{trace_dir}")
    return result, files[-1]


# --------------------------------------------------------------------------
# the comparison's verdict and the last line
# --------------------------------------------------------------------------

def judge(readings: dict, limits: dict) -> dict:
    """Each number compared beside its limit. A limit missing for a
    reading, or a reading missing for a limit, is not correct."""
    checks = {}
    for name in sorted(set(readings) | set(limits)):
        value, limit = readings.get(name), limits.get(name)
        ok = (value is not None and limit is not None
              and value == value and value <= limit)
        checks[name] = {"value": value, "limit": limit, "ok": bool(ok)}
    return checks


def layer_metrics(cell: Cell, context: dict) -> dict:
    """Every per-layer metric of this cell whose reader finds something
    to read. A metric is `layer_metrics/<name>.json`, which names its
    reader (`readers/<reader>.py`) and the reader's arguments."""
    out = {}
    for metric in cell.metrics("per_layer"):
        spec = cell.data("layer_metrics", metric["name"])
        reader = importlib.import_module(f"perfbench.readers.{spec['reader']}")
        value = reader.read(context, **spec.get("args", {}))
        if value is not None:
            out[metric["name"]] = {"value": value, "unit": metric["unit"]}
    return out


def emit(result: dict, checks: dict) -> None:
    """The comparison on standard error, then the one result line."""
    lines = [f"check {k}: value {v['value']} limit {v['limit']} "
             f"{'ok' if v['ok'] else 'NOT OK'}" for k, v in checks.items()]
    print("\n".join(lines), file=sys.stderr, flush=True)
    result["checks"] = {k: [v["value"], v["limit"]]
                        for k, v in checks.items()}
    print(json.dumps(result), flush=True)
