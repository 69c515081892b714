"""From the profiler's trace to numbers: device busy time, the time of
each device operation, and the idle gaps by what the host was doing.

`load` turns an `.xplane.pb` into plain rows; `reduce` works on those
rows alone, so that it can be checked on a recorded cut of a chip trace
(`tests/data/`). A row is [plane, line, name, start_ns, duration_ns].
"""

from __future__ import annotations

import collections
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
WINDOW_SPANS = ("pb.feed", "pb.call")


def load(path: str) -> list:
    """Rows of the device planes' operation lines and of the host's
    `pb.*` annotations."""
    from jax.profiler import ProfileData
    rows = []
    for plane in ProfileData.from_file(path).planes:
        device = DEVICE_PLANE.match(plane.name)
        if not device and plane.name != HOST_PLANE:
            continue
        for line in plane.lines:
            if device and line.name != OPS_LINE:
                continue
            for ev in line.events:
                if device or ev.name.startswith("pb."):
                    rows.append([plane.name, line.name, short_name(ev.name),
                                 int(ev.start_ns), int(ev.duration_ns)])
    return rows


_HLO = re.compile(r"^(%[^ ]+) = (.*?) ([a-z][a-z\-]*)\(")


def short_name(name: str) -> str:
    """The trace names a device operation by its whole HLO line; keep
    `%result opcode -> type`, the layouts dropped. The first word is
    what a reader's patterns are matched against."""
    m = _HLO.match(name)
    if not m:
        return name[:120]
    result, kind, opcode = m.groups()
    kind = re.sub(r"\{[^}]*\}", "", kind)
    return f"{result} {opcode} -> {kind[:72]}"


def summarize(path: str, top: int = 40) -> str:
    """Every plane and line of a trace with its heaviest event names: for
    the look by hand that comes before any reader is written."""
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(path).planes:
        out.append(f"PLANE {plane.name}")
        for line in plane.lines:
            total = collections.Counter()
            count = collections.Counter()
            first = None
            for ev in line.events:
                total[ev.name] += ev.duration_ns
                count[ev.name] += 1
                first = first or ev
            out.append(f"  LINE {line.name}: {sum(count.values())} events")
            if first is not None:
                out.append(f"    first: {first.name} start {first.start_ns} "
                           f"dur {first.duration_ns} stats "
                           f"{[(k, str(v)[:80]) for k, v in first.stats][:8]}")
            for name, ns in total.most_common(top):
                out.append(f"    {ns / 1e6:12.3f} ms {count[name]:6d}x {name}")
    return "\n".join(out)


def _union(intervals: list) -> list:
    merged = []
    for lo, hi in sorted(intervals):
        if merged and lo <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], hi)
        else:
            merged.append([lo, hi])
    return merged


def _nesting(events: list) -> tuple:
    """(seconds per operation name, the operations that enclose no
    other). An enclosing operation (a loop, a call) is counted without
    what runs inside it, and is not itself work on the device: the gaps
    between the operations of a loop's body are idle time."""
    total = collections.Counter()
    stack, leaves = [], []
    for name, lo, hi in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][2] <= lo:
            done = stack.pop()
            if done[3]:
                leaves.append(done[:3])
        if stack and hi <= stack[-1][2]:
            total[stack[-1][0]] -= hi - lo
            stack[-1][3] = False
        total[name] += hi - lo
        stack.append([name, lo, hi, True])
    leaves += [e[:3] for e in stack if e[3]]
    return {k: v / 1e9 for k, v in total.items()}, leaves


def reduce(rows: list) -> dict:
    """The traced window (from the first `pb.feed` to the end of the last
    `pb.call` on the host) and, within it, for each device: the seconds
    in which an operation ran (the union of the operations that enclose
    no other), and each operation's own seconds; and the idle gaps of
    the first device by the host's span and the operation that ended
    each."""
    host = [(r[2], r[3], r[3] + r[4]) for r in rows
            if r[0] == HOST_PLANE and r[2] in WINDOW_SPANS]
    if not host:
        return {}
    lo, hi = min(h[1] for h in host), max(h[2] for h in host)
    devices = collections.defaultdict(list)
    for plane, _, name, start, dur in rows:
        if (DEVICE_PLANE.match(plane) and dur > 0 and start + dur > lo
                and start < hi):
            devices[plane].append((name, max(start, lo),
                                   min(start + dur, hi)))
    if not devices:
        return {}
    busy, ops, leaves = {}, collections.Counter(), {}
    for plane, events in devices.items():
        own, leaves[plane] = _nesting(events)
        merged = _union([(e[1], e[2]) for e in leaves[plane]])
        busy[plane] = sum(b - a for a, b in merged) / 1e9
        for name, s in own.items():
            ops[name] += s / len(devices)
    first = sorted(devices)[0]
    merged = _union([(e[1], e[2]) for e in leaves[first]])
    starts = sorted((e[1], e[0]) for e in leaves[first])
    gaps = collections.Counter()
    edges = [lo] + [x for iv in merged for x in iv] + [hi]
    si = 0
    for a, b in zip(edges[0::2], edges[1::2]):
        if b <= a:
            continue
        while si < len(starts) and starts[si][0] < b:
            si += 1
        after = starts[si][1] if si < len(starts) else "window end"
        left = b - a
        for span, s_lo, s_hi in host:
            shared = min(b, s_hi) - max(a, s_lo)
            if shared > 0:
                gaps[f"{span}, before {after}"] += shared / 1e9
                left -= shared
        if left > 0:
            gaps[f"host:other, before {after}"] += left / 1e9
    return {"window_s": (hi - lo) / 1e9,
            "busy_s": sum(busy.values()) / len(busy),
            "busy_s_by_device": busy,
            "ops_s": dict(ops),
            "gaps_s": dict(gaps)}


def grouped(ops_s: dict) -> dict:
    """Operations that differ only in the number XLA appends to the
    result's name (one fusion per layer, as a rule) summed under one
    name, with how many there were: `%fusion fusion -> bf16[32,14336]
    x12`."""
    total, count = collections.Counter(), collections.Counter()
    for name, s in ops_s.items():
        first, _, rest = name.partition(" ")
        key = (re.sub(r"\.\d+$", "", first), rest)
        total[key] += s
        count[key] += 1
    return {f"{first} {rest} x{count[first, rest]}".replace("  ", " "): s
            for (first, rest), s in total.items()}


def matching_seconds(reduced: dict, patterns: list) -> float | None:
    """Summed own seconds (mean over devices) of the operations whose
    name matches any of ``patterns``; None where there is none."""
    found = [s for name, s in reduced.get("ops_s", {}).items()
             if any(re.search(p, name.split(" ")[0]) for p in patterns)]
    return sum(found) if found else None
