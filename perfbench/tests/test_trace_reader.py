"""The reduction from a trace to numbers: on made-up rows, on a trace the
CPU writes, and on a cut of a trace recorded on the chip."""

import gzip
import json
import os
import re

import pytest

from conftest import HERE
from perfbench import trace_reader

DEV = "/device:TPU:0"
HOST = trace_reader.HOST_PLANE


def rows_of(events, plane=DEV, line=trace_reader.OPS_LINE):
    return [[plane, line, name, lo, hi - lo] for name, lo, hi in events]


def test_busy_is_the_union_and_a_loop_is_counted_without_its_body():
    rows = rows_of([("pb.feed", 0, 100), ("pb.call", 100, 1000)], HOST,
                   "python")
    rows += rows_of([("while", 200, 800), ("fusion.1", 200, 400),
                     ("flash_fwd", 450, 700), ("copy", 900, 950),
                     ("no time at all", 200, 200),
                     ("before the window", -50, -10)])
    got = trace_reader.reduce(rows)
    assert got["window_s"] == pytest.approx(1000e-9)
    assert got["busy_s"] == pytest.approx(500e-9)
    assert got["ops_s"] == pytest.approx(
        {"while": 150e-9, "fusion.1": 200e-9, "flash_fwd": 250e-9,
         "copy": 50e-9})
    assert trace_reader.matching_seconds(got, ["flash"]) == \
        pytest.approx(250e-9)
    assert trace_reader.matching_seconds(got, ["decode"]) is None
    # the loop is not work: what its body leaves uncovered is idle
    assert got["gaps_s"] == pytest.approx(
        {"pb.feed, before fusion.1": 100e-9, "pb.call, before fusion.1": 100e-9,
         "pb.call, before flash_fwd": 50e-9, "pb.call, before copy": 200e-9,
         "pb.call, before window end": 50e-9})


def test_busy_is_the_mean_over_devices():
    rows = rows_of([("pb.call", 0, 1000)], HOST, "python")
    rows += rows_of([("a", 0, 500)], "/device:TPU:0")
    rows += rows_of([("a", 0, 250)], "/device:TPU:1")
    got = trace_reader.reduce(rows)
    assert got["busy_s"] == pytest.approx(375e-9)
    assert got["ops_s"]["a"] == pytest.approx(375e-9)
    assert got["busy_s_by_device"] == {"/device:TPU:0": 500e-9,
                                      "/device:TPU:1": 250e-9}


def test_the_breakdown_sums_a_layers_fusions_under_one_name():
    got = trace_reader.grouped({
        "%fusion.12 fusion -> bf16[32,14336]": 1.0,
        "%fusion.13 fusion -> bf16[32,14336]": 2.0,
        "%fusion.13 fusion -> bf16[32,4096]": 4.0,
        "%_decode_pallas custom-call -> f32[256,4,128]": 8.0,
        "pb.odd": 16.0})
    assert got == {"%fusion fusion -> bf16[32,14336] x2": 3.0,
                   "%fusion fusion -> bf16[32,4096] x1": 4.0,
                   "%_decode_pallas custom-call -> f32[256,4,128] x1": 8.0,
                   "pb.odd x1": 16.0}


def test_nothing_to_read_gives_nothing():
    assert trace_reader.reduce([]) == {}
    assert trace_reader.reduce(rows_of([("pb.call", 0, 10)], HOST, "x")) == {}


def test_load_reads_the_hosts_spans_from_a_real_trace(tmp_path):
    import jax
    import jax.numpy as jnp

    from perfbench import harness

    def work():
        return harness.measured_loop(
            lambda i: (lambda: jnp.ones((8, 8)).sum().block_until_ready()),
            float("inf"), at_most=2)

    loop, path = harness.traced(work, str(tmp_path))
    assert len(loop["times"]) == 2
    names = [r[2] for r in trace_reader.load(path)]
    assert names.count("pb.feed") == 2 and names.count("pb.call") == 2
    assert "PLANE /host:CPU" in trace_reader.summarize(path)


def recorded(name):
    with gzip.open(os.path.join(HERE, "data", name), "rt") as f:
        return json.load(f)


def brute_busy_ns(rows, enclosing=()):
    """Busy time by painting a timeline, 100 ns to a cell: another way to
    the union than the reader's."""
    dev = [r for r in rows if r[0] == DEV and r[2] not in enclosing]
    cells = bytearray(max(r[3] + r[4] for r in dev) // 100 + 2)
    for _, _, _, start, dur in dev:
        lo, hi = -(-start // 100), (start + dur) // 100
        cells[lo:hi] = b"\x01" * max(0, hi - lo)
    return 100 * sum(cells)


def test_the_recorded_train_step():
    """One train step at the published widths (4 layers, 2 x 4096, before
    the cell was resized), cut from the first trace taken on the chip (TPU
    v5 lite, PR 24): 916 rows."""
    rows = recorded("trace_train_step.json.gz")
    got = trace_reader.reduce(rows)
    assert got["window_s"] == pytest.approx(0.394331689)
    assert got["busy_s"] == pytest.approx(0.391330698)
    assert got["busy_s"] * 1e9 == pytest.approx(brute_busy_ns(rows), rel=2e-3)
    flash = [r for r in rows if re.search(
        "flash_pallas|flash_bwd_pallas", r[2].split(" ")[0])]
    assert len(flash) == 12      # 4 layers: forward, and two backward kernels
    assert trace_reader.matching_seconds(
        got, ["flash_pallas", "flash_bwd_pallas"]) == pytest.approx(
        sum(r[4] for r in flash) / 1e9) == pytest.approx(0.047719958)
    idle = 100 * (1 - got["busy_s"] / got["window_s"])
    assert idle == pytest.approx(0.761, abs=0.001)
    assert sum(got["ops_s"].values()) == pytest.approx(got["busy_s"], rel=1e-3)


def test_the_recorded_decode_scan():
    """40 ms inside the position scan of `mistral7b-decode-chat-1chip`: the
    `while` encloses every step's operations and is not itself work."""
    rows = recorded("trace_decode_scan.json.gz")
    loop = [r[2] for r in rows if r[2].startswith("%while ")]
    got = trace_reader.reduce(rows)
    assert got["window_s"] == pytest.approx(0.04)
    assert got["busy_s"] == pytest.approx(0.039773397)
    assert got["busy_s"] * 1e9 == pytest.approx(
        brute_busy_ns(rows, enclosing=loop), rel=5e-3)
    kernel = [r for r in rows if r[2].startswith("%_decode_pallas")]
    assert trace_reader.matching_seconds(got, ["^%_decode_pallas"]) == \
        pytest.approx(sum(r[4] for r in kernel) / 1e9) == \
        pytest.approx(0.00783592)
    # the loop's own time is what its body leaves uncovered
    assert got["ops_s"][loop[0]] == pytest.approx(
        got["window_s"] - got["busy_s"])
