"""Device seconds under each of the program's scopes, whatever the
depth: `scope_reader.reduce` sorts operations by phase and block
(`lm.attn`, `lm.ffn`, ...); the scopes a layer opens inside a block
(`lm.attn/lm.indexer`, `lm.ffn/lm.moe.experts`) are read here, from the
same rows and within the same window."""

from __future__ import annotations

import collections

from perfbench import scope_reader, trace_reader
from perfbench.trace_reader import DEVICE_PLANE, HOST_PLANE, OPS_LINE

MARKS = ("lm.decode", "lm.embed", "lm.attn", "lm.mla", "lm.indexer",
         "lm.sparse", "lm.ffn", "lm.moe.route", "lm.moe.experts",
         "lm.moe.shared", "lm.head")


def seconds_under(rows: list, marks=MARKS) -> dict:
    """{mark: seconds}, the mean over the devices of the time of the
    operations that enclose no other, collectives left out, whose scope
    path holds the mark as one of its parts; within the traced window
    (`scope_reader.reduce`'s). Nothing on a device: an empty dict."""
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
    out = dict.fromkeys(marks, 0.0)
    for of_plane in device_rows.values():
        compute, _ = scope_reader._on_device(of_plane, lo, hi)
        for row, a, b in compute:
            parts = set(row[5].split("/"))
            for mark in marks:
                if mark in parts:
                    out[mark] += (b - a) / 1e9 / len(device_rows)
    return out


def table(seconds: dict, steps: int) -> str:
    return "\n".join(f"scopes: under {mark:16s} {1e3 * s / steps:10.4f} ms "
                     f"a step" for mark, s in seconds.items() if s)
