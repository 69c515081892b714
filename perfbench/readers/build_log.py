"""Set-up by phase, from the program's own build log
(`lua_mapreduce_tpu/utils/profiling.py`): a row for every program the
process built (trace, lower, compile or the persistent cache's fetch,
and `after_s`, the seconds until the next build began). The log is
always on; this reads it in the driver's process once the run is over,
and prints the whole table on standard error once a run. The
reference's programs are built after the window, and the readers'
second trace of a train or decode cell after them: the log is read up
to the first row of a reference program, told by name. The names are
data: `reference_programs/<module>.json` beside each
`perfbench/<module>.py` that is a reference, which lists every function
the module jits, under `after_window`, or under `in_setup_too` where a
driver calls it before the window as well (it then ends nothing). A new
reference brings its file; `tests/test_build_log.py` holds each file to
its module. What the reference builds under no name of its own before
its first listed program (its weights, its eager operations) stays in
the printed table; the four metrics name their programs and count none
of it. (The exact cut is the window's opening time, once a `benchmark`
PR puts it in `context`.) `part` is what is read:

- `start`: the process's start to the first row's `t0` (interpreter,
  imports, the backend and the hand-over of the chip). None where the
  process's start is not on record.
- `trace_lower`: summed `trace_s + lower_s` of the rows named in
  `programs`: paid at every start, cached nowhere.
- `build`: summed `build_s` of the same rows, hits and misses of the
  persistent cache printed beside it.
- `after`: summed `after_s` of the rows named in `programs` (the
  session's prefill: its run on the device, up to the next trace).

A program without the log (before PR 36), or none of `programs` built:
nothing returned. A test hands a recorded log in as
`context["build_log"]`: `{"process_start": ..., "rows": [...]}`."""

import functools
import glob
import json
import os
import sys

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@functools.cache
def reference_programs() -> frozenset:
    """The names only a reference builds, from every
    `reference_programs/*.json`."""
    names = set()
    for path in glob.glob(os.path.join(HERE, "reference_programs", "*.json")):
        with open(path) as f:
            names.update(json.load(f)["after_window"])
    return frozenset(names)


def recorded(context):
    """The log as a recording: the one handed in, else the process's."""
    if "build_log" in context:
        return context["build_log"]
    from lua_mapreduce_tpu.utils import profiling
    if not hasattr(profiling, "build_log"):
        return None
    log = profiling.build_log()
    return {"process_start": log.process_start, "rows": log.rows(),
            "dropped": log.dropped}


def read(context, part, programs=None):
    log = recorded(context)
    if log is None:
        return None
    theirs = [i for i, r in enumerate(log["rows"])
              if r["program"] in reference_programs()]
    log = dict(log, rows=log["rows"][:theirs[0]] if theirs else log["rows"])
    if not context.get("build_log_printed"):
        from lua_mapreduce_tpu.utils.profiling import build_table
        context["build_log_printed"] = True
        print(build_table(log["rows"], log["process_start"])
              + f"\nbuild log: {log.get('dropped', 0)} more rows not kept",
              file=sys.stderr)
    rows = log["rows"]
    if part == "start":
        if log["process_start"] is None or not rows:
            return None
        return rows[0]["t0"] - log["process_start"]
    rows = [r for r in rows if programs is None or r["program"] in programs]
    if not rows:
        return None
    if part == "trace_lower":
        return sum(r["trace_s"] + r["lower_s"] for r in rows)
    if part == "build":
        caches = [r["cache"] for r in rows]
        print(f"build log: {part} of {[r['program'] for r in rows]}: "
              f"{caches.count('hit')} hits, {caches.count('miss')} misses",
              file=sys.stderr)
        return sum(r["build_s"] for r in rows)
    if part == "after":
        return sum(r["after_s"] for r in rows if r["after_s"] is not None)
    raise ValueError(f"build_log: no part {part!r}")
