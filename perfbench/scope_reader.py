"""From the profiler's trace to the program's own names: device time by
the `lm.*` scopes that `models/transformer.py` opens (forward, backward,
optimizer; prefill, first token, decode), the collectives and the part
of them that nothing hides, and the device's idle time inside the host's
`lm.shard_batch` span.

`load` turns an `.xplane.pb` into plain rows, `reduce` works on those rows
alone (checked on recorded cuts of chip traces, `tests/data/`). A row is
[plane, line, name, start_ns, duration_ns, scope, opcode]: the first
five as `trace_reader.load` gives them, then the operation's scope path
(`jit(lm_train_step)/transpose(jvp(lm.loss))/lm.ffn/dot_general`) and
its HLO opcode.

Where the scope path comes from: the profiler keeps it in the trace
file, as the `tf_op` stat of each event's metadata. `jax.profiler.
ProfileData` shows an event's own stats only (offset and duration on a
TPU plane), so `event_scopes` reads the metadata from the file's wire
format, which takes a few lines and nothing but Python.
"""

from __future__ import annotations

import collections
import os
import re
import shutil
import statistics
import sys

from perfbench import trace_reader
from perfbench.trace_reader import DEVICE_PLANE, HOST_PLANE, OPS_LINE

ASYNC_LINE = "Async XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_EVENTS = ("pb.", "lm.", "PjitFunction(")
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
               "collective-permute", "all-to-all")
PHASES = (("opt", ("lm.opt",)), ("bwd", ("transpose(", "lm.loss")),
          ("fwd", ("lm.loss",)), ("prefill", ("lm.prefill",)),
          ("first_token", ("lm.first_token",)), ("decode", ("lm.decode",)))
FEED_SPAN = "lm.shard_batch"
_OPCODE = re.compile(r" ([a-z][a-z\-]*)\(")
_BLOCK = re.compile(r"lm\.(embed|attn|ffn|head|ring)\b")


# --------------------------------------------------------------------------
# the file
# --------------------------------------------------------------------------

def _varint(buf, i: int) -> tuple:
    value = shift = 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, i
        shift += 7


def _fields(buf):
    """(field number, value) of one protobuf message: an int for a
    varint, the bytes for a length-delimited or fixed-width field."""
    i = 0
    while i < len(buf):
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        else:
            if wire == 2:
                size, i = _varint(buf, i)
            elif wire in (1, 5):
                size = 8 if wire == 1 else 4
            else:
                raise ValueError(f"wire type {wire} in an xplane file")
            value, i = buf[i:i + size], i + size
        yield key >> 3, value


def _entry(buf) -> tuple:
    """A map entry's (key, value)."""
    got = dict(_fields(buf))
    return got.get(1, 0), got.get(2, b"")


def event_scopes(path: str) -> dict:
    """{device plane: {event name: scope path}} from the `tf_op` stat of
    the planes' event metadata (xplane.proto: XSpace.planes = 1; XPlane
    name = 2, event_metadata = 4, stat_metadata = 5; XEventMetadata
    name = 2, stats = 5; XStat metadata_id = 1, str_value = 5,
    ref_value = 7; XStatMetadata name = 2)."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    out = {}
    for field, plane in _fields(space):
        if field != 1:
            continue
        name, events, stat_names = "", [], {}
        for field, value in _fields(plane):
            if field == 2:
                name = str(value, "utf8")
            elif field == 4:
                events.append(_entry(value)[1])
            elif field == 5:
                key, meta = _entry(value)
                stat_names[key] = str(dict(_fields(meta)).get(2, b""), "utf8")
        if not DEVICE_PLANE.match(name):
            continue
        wanted = {k for k, v in stat_names.items() if v == "tf_op"}
        scopes = out[name] = {}
        for meta in events:
            event_name = scope = None
            for field, value in _fields(meta):
                if field == 2:
                    event_name = str(value, "utf8")
                elif field == 5:
                    stat = dict(_fields(value))
                    if stat.get(1) in wanted:
                        scope = (str(stat[5], "utf8") if 5 in stat
                                 else stat_names.get(stat.get(7), ""))
            if event_name and scope:
                scopes[event_name] = scope.rstrip(":")
    return out


def load(path: str) -> list:
    """Rows of the device planes' operation line (every operation), of
    their asynchronous line (collectives only) and of their line of
    program runs, and of the host's `pb.*`, `lm.*` and `PjitFunction(`
    events."""
    from jax.profiler import ProfileData
    scopes = event_scopes(path)
    rows = []
    for plane in ProfileData.from_file(path).planes:
        device = DEVICE_PLANE.match(plane.name)
        if not device and plane.name != HOST_PLANE:
            continue
        of_plane = scopes.get(plane.name, {})
        for line in plane.lines:
            if device and line.name not in (OPS_LINE, ASYNC_LINE,
                                            MODULES_LINE):
                continue
            for ev in line.events:
                if not device:
                    if ev.name.startswith(HOST_EVENTS):
                        rows.append([plane.name, line.name, ev.name[:120],
                                     int(ev.start_ns), int(ev.duration_ns),
                                     "", ""])
                    continue
                opcode = _OPCODE.search(ev.name)
                opcode = opcode.group(1) if opcode else ""
                if line.name == ASYNC_LINE and not is_collective(opcode):
                    continue
                rows.append([plane.name, line.name,
                             trace_reader.short_name(ev.name),
                             int(ev.start_ns), int(ev.duration_ns),
                             of_plane.get(ev.name, ""), opcode])
    return rows


# --------------------------------------------------------------------------
# the reduction
# --------------------------------------------------------------------------

def is_collective(opcode: str) -> bool:
    return opcode.removesuffix("-start").removesuffix("-done") in COLLECTIVES


def phase_of(scope: str) -> str:
    for phase, marks in PHASES:
        if all(m in scope for m in marks):
            return phase
    return "other"


def block_of(scope: str) -> str:
    found = _BLOCK.findall(scope)
    return found[-1] if found else "-"


def _length(intervals: list) -> int:
    return sum(hi - lo for lo, hi in trace_reader._union(intervals))


def _clipped(rows: list, lo: int, hi: int) -> list:
    """(row, start, end) of the rows that run inside [lo, hi)."""
    return [(r, max(r[3], lo), min(r[3] + r[4], hi)) for r in rows
            if r[4] > 0 and r[3] + r[4] > lo and r[3] < hi]


def _on_device(of_plane: list, lo: int, hi: int) -> tuple:
    """One device within [lo, hi): (row, start, end) of the operations
    that enclose no other and are no collective, and the collectives'
    intervals by kind, those of the asynchronous line among them."""
    ops = _clipped([r for r in of_plane if r[1] == OPS_LINE], lo, hi)
    _, leaves = trace_reader._nesting(
        [(i, a, b) for i, (_, a, b) in enumerate(ops)])
    compute, kinds = [], {"ring": [], "grad": []}
    in_flight = _clipped([r for r in of_plane if r[1] == ASYNC_LINE
                          and is_collective(r[6])], lo, hi)
    for row, a, b in [(ops[i][0], a, b) for i, a, b in leaves] + in_flight:
        if is_collective(row[6]):
            kinds["ring" if "lm.ring" in row[5] else "grad"].append((a, b))
        else:
            compute.append((row, a, b))
    return compute, kinds


def reduce(rows: list) -> dict:
    """Within the traced window (the host's first `pb.feed` to the end
    of its last `pb.call`; the device operations' own extent where the
    host wrote neither), the mean over the devices of:

    - `phase_block_s`: seconds of the operations that enclose no other,
      collectives left out, by "phase/block" of their scope path;
      `phase_s` the same by phase. An operation in no phase is `other`.
    - `collective_s`, `collective_exposed_s`: by `ring` (under
      `lm.ring`) and `grad` (the rest), the union of the collectives'
      intervals, asynchronous spans included, and the part of it in
      which no other operation runs on that device; `exposed_s` the
      same for all collectives together.
    - `idle_s`: the window less the union of all of the above, so that
      the phases, `exposed_s` and `idle_s` add up to the window.

    And `feed_idle_s`, the first device's idle time inside the host's
    `lm.shard_batch` spans; `first_token_s`, for each program run on
    the first device, from its start to the end of its last operation
    under `lm.prefill` or `lm.first_token`; `runs`, the program runs by
    module name."""
    host = [r for r in rows if r[0] == HOST_PLANE]
    device_rows = collections.defaultdict(list)
    for r in rows:
        if DEVICE_PLANE.match(r[0]):
            device_rows[r[0]].append(r)
    spans = [r for r in host if r[2] in trace_reader.WINDOW_SPANS]
    timed = spans or [r for rs in device_rows.values() for r in rs
                      if r[1] == OPS_LINE]
    if not timed or not device_rows:
        return {}
    lo = min(r[3] for r in timed)
    hi = max(r[3] + r[4] for r in timed)

    phase_block = collections.Counter()
    coll, exposed = collections.Counter(), collections.Counter()
    idle = exposed_all = 0.0
    devices = {plane: _on_device(of_plane, lo, hi)
               for plane, of_plane in device_rows.items()}
    n = len(devices)
    for compute, kinds in devices.values():
        for row, a, b in compute:
            key = f"{phase_of(row[5])}/{block_of(row[5])}"
            phase_block[key] += (b - a) / 1e9 / n
        running = [(a, b) for _, a, b in compute]
        under = _length(running)
        for kind, intervals in kinds.items():
            coll[kind] += _length(intervals) / 1e9 / n
            exposed[kind] += (_length(intervals + running) - under) / 1e9 / n
        everything = running + kinds["ring"] + kinds["grad"]
        exposed_all += (_length(everything) - under) / 1e9 / n
        idle += (hi - lo - _length(everything)) / 1e9 / n

    first = min(devices)
    compute, kinds = devices[first]
    busy = trace_reader._union([(a, b) for _, a, b in compute]
                               + kinds["ring"] + kinds["grad"])
    gaps = [(a, b) for a, b in zip([lo] + [iv[1] for iv in busy],
                                   [iv[0] for iv in busy] + [hi]) if b > a]
    feed = [(r[3], r[3] + r[4]) for r in host if r[2] == FEED_SPAN]
    feed_idle = sum(max(0, min(b, fb) - max(a, fa))
                    for a, b in gaps for fa, fb in feed) / 1e9

    modules = _clipped([r for r in device_rows[first]
                        if r[1] == MODULES_LINE], lo, hi)
    first_token = []
    for _, a, b in modules:
        ends = [e for row, s, e in compute if a <= s < b
                and phase_of(row[5]) in ("prefill", "first_token")]
        if ends:
            first_token.append((max(ends) - a) / 1e9)
    runs = collections.Counter(
        re.sub(r"\(\d+\)$", "", r[2]) for r, _, _ in modules)

    phase = collections.Counter()
    for key, s in phase_block.items():
        phase[key.split("/")[0]] += s
    return {"window_s": (hi - lo) / 1e9,
            "phase_block_s": dict(phase_block), "phase_s": dict(phase),
            "collective_s": dict(coll),
            "collective_exposed_s": dict(exposed),
            "exposed_s": exposed_all, "idle_s": idle,
            "feed_idle_s": feed_idle if feed else None,
            "first_token_s": first_token, "runs": dict(runs)}


def table(reduced: dict, calls: int) -> str:
    """The (phase, block) table in milliseconds a call, for the look by
    hand and for `PERF.md` section 5."""
    out = [f"scopes: window {1e3 * reduced['window_s']:.3f} ms, "
           f"{calls} calls, program runs {reduced['runs']}"]
    total = reduced["window_s"] / calls
    for key, s in sorted(reduced["phase_block_s"].items(),
                         key=lambda kv: -kv[1]):
        out.append(f"scopes: {key:22s} {1e3 * s / calls:10.3f} ms "
                   f"{100 * s / calls / total:6.2f}%")
    for kind in ("ring", "grad"):
        if reduced["collective_s"].get(kind):
            out.append(
                f"scopes: collectives/{kind:11s} "
                f"{1e3 * reduced['collective_exposed_s'][kind] / calls:10.3f}"
                f" ms exposed of "
                f"{1e3 * reduced['collective_s'][kind] / calls:.3f} ms")
    out.append(f"scopes: {'exposed collectives':22s} "
               f"{1e3 * reduced['exposed_s'] / calls:10.3f} ms")
    out.append(f"scopes: {'idle':22s} {1e3 * reduced['idle_s'] / calls:10.3f}"
               f" ms")
    return "\n".join(out)


# --------------------------------------------------------------------------
# what the readers get
# --------------------------------------------------------------------------

def of(context: dict) -> dict:
    """The reduction for this run's readers, made once. `run.py` hands
    `trace_reader`'s reduction to the readers and removes the trace file
    before they run; until it hands this one over too, as
    `context["scopes"]`, the calls are traced a second time here."""
    if "scopes" not in context:
        context["scopes"] = retrace(context)
    return context["scopes"]


SUBJECTS = {"train": "Trainer", "decode": "Decoder"}


def retrace(context: dict) -> dict:
    """Build the driver's subject again (the window's own was freed),
    warm it with one call, trace `traced_calls` calls as the driver did,
    and reduce that trace. The seed is not in `context`; device time
    does not depend on it."""
    from perfbench import harness
    cell = context["cell"]
    kind = cell.traffic["kind"]
    if kind not in SUBJECTS:
        return {}
    driver = harness.driver_for(kind)
    subject = getattr(driver, SUBJECTS[kind])(
        cell, 0, harness.require_chips(context["chips"],
                                       context["device"].platform))
    work_dir = os.path.join(harness.ROOT, ".perfbench_work",
                            cell.name + ".scopes")
    shutil.rmtree(work_dir, ignore_errors=True)
    try:
        subject.one(0)()
        loop, trace_file = harness.traced(
            lambda: harness.measured_loop(
                lambda i: subject.one(1 + i), float("inf"),
                at_most=cell.traffic["traced_calls"]), work_dir)
        reduced = reduce(load(trace_file))
    finally:
        subject.free()
        shutil.rmtree(work_dir, ignore_errors=True)
    if reduced:
        reduced["calls"] = len(loop["times"])
        print(table(reduced, reduced["calls"]), file=sys.stderr)
        print(f"scopes: traced again, median call "
              f"{1e3 * statistics.median(loop['times']):.3f} ms",
              file=sys.stderr)
    return reduced
