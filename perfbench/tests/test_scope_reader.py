"""The reduction from a trace to the program's own names: the file's wire
format on a hand-made file and on a trace the CPU writes, `reduce` on
made-up rows and on cuts of the traces recorded on the chip (TPU v5
lite, PR 26), and the readers over what it returns."""

import gzip
import hashlib
import json
import os

import pytest

from conftest import HERE, ROOT
from perfbench import harness, scope_reader, trace_reader
from perfbench.readers import (collective_exposed_ms, first_token_ms,
                               host_span_idle_ms, scope_ms)

DEV = "/device:TPU:0"
HOST = trace_reader.HOST_PLANE
OPS, ASYNC, MODULES = (trace_reader.OPS_LINE, scope_reader.ASYNC_LINE,
                       scope_reader.MODULES_LINE)
STEP = "jit(lm_train_step)/"
FWD = STEP + "jvp(lm.loss)/lm.ffn/dot_general"
BWD = STEP + "transpose(jvp(lm.loss))/lm.ffn/dot_general"
OPT = STEP + "lm.opt/sub"
RING = STEP + "shard_map/jvp(lm.loss)/lm.attn/lm.ring/ppermute"
GRAD = STEP + "shard_map/psum_invariant"


def op(name, lo, hi, scope="", opcode="fusion", plane=DEV, line=OPS):
    return [plane, line, name, lo, hi - lo, scope, opcode]


def host(name, lo, hi):
    return [HOST, "python3", name, lo, hi - lo, "", ""]


def recorded(name):
    with gzip.open(os.path.join(HERE, "data", name), "rt") as f:
        return json.load(f)


def adds_up(got):
    return (sum(got["phase_s"].values()) + got["exposed_s"] + got["idle_s"]
            == pytest.approx(got["window_s"], rel=1e-9))


# --------------------------------------------------------------------------
# the file
# --------------------------------------------------------------------------

def varint(n):
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def field(number, value):
    if isinstance(value, int):
        return varint(number << 3) + varint(value)
    value = value.encode() if isinstance(value, str) else value
    return varint(number << 3 | 2) + varint(len(value)) + value


def plane_bytes(name, events, stat_names):
    """An XPlane with event metadata {id: (name, [(stat id, value)])}; a
    string value is a `str_value`, an int a `ref_value`."""
    out = field(1, 7) + field(2, name) + field(3, field(2, "a line"))
    for key, (event_name, stats) in events.items():
        meta = field(1, key) + field(2, event_name) + field(4, "display")
        for stat_id, value in stats:
            meta += field(5, field(1, stat_id) + field(
                5 if isinstance(value, str) else 7, value))
        out += field(4, field(1, key) + field(2, meta))
    for key, stat_name in stat_names.items():
        out += field(5, field(1, key) + field(2, field(1, key)
                                              + field(2, stat_name)))
    return out


def test_the_scope_path_is_read_from_the_files_event_metadata(tmp_path):
    names = {3: "flops", 26: "tf_op", 300: STEP + "lm.opt/add:"}
    device = plane_bytes("/device:TPU:0", {
        1: ("%fusion.1 = f32[8] fusion()", [(3, "12"), (26, FWD + ":")]),
        2: ("%add.2 = f32[8] add()", [(26, 300)]),
        900: ("%copy.3 = f32[8] copy()", [(3, "1")])}, names)
    other = plane_bytes("/host:CPU", {1: ("pb.call", [(26, "x")])}, names)
    path = tmp_path / "made.xplane.pb"
    path.write_bytes(field(1, other) + field(1, device) + field(2, "host"))
    assert scope_reader.event_scopes(str(path)) == {"/device:TPU:0": {
        "%fusion.1 = f32[8] fusion()": FWD,
        "%add.2 = f32[8] add()": STEP + "lm.opt/add"}}


def test_load_reads_a_real_trace_and_the_programs_host_span(tmp_path):
    """A CPU trace has no device plane: the host's rows come back, the
    program's own span among them, and nothing is reduced from them."""
    import jax
    import numpy as np
    from jax.sharding import Mesh

    from lua_mapreduce_tpu.models import transformer as tfm
    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1), ("dp", "sp"))
    rows = np.zeros((2, 8), np.int32)

    def work():
        return harness.measured_loop(
            lambda i: (lambda: jax.block_until_ready(
                tfm.shard_batch(mesh, rows, rows))), float("inf"), at_most=2)

    _, path = harness.traced(work, str(tmp_path))
    assert scope_reader.event_scopes(path) == {}
    got = scope_reader.load(path)
    names = [r[2] for r in got]
    assert names.count("pb.call") == 2
    assert names.count("lm.shard_batch") == 2
    assert all(r[0] == HOST and len(r) == 7 for r in got)
    assert scope_reader.reduce(got) == {}
    assert [r[:5] for r in got if r[2].startswith("pb.")] == \
        trace_reader.load(path)


# --------------------------------------------------------------------------
# the reduction, on made-up rows
# --------------------------------------------------------------------------

def test_phases_by_scope_and_a_loop_counted_without_its_body():
    rows = [host("pb.feed", 0, 100), host("pb.call", 100, 1000),
            op("%f", 100, 300, FWD), op("%b", 300, 700, BWD),
            op("%o", 700, 800, OPT), op("%copy", 850, 900, "", "copy"),
            op("%while", 100, 700, STEP + "while", "while")]
    got = scope_reader.reduce(rows)
    assert got["window_s"] == pytest.approx(1000e-9)
    assert got["phase_s"] == pytest.approx(
        {"fwd": 200e-9, "bwd": 400e-9, "opt": 100e-9, "other": 50e-9})
    assert got["phase_block_s"] == pytest.approx(
        {"fwd/ffn": 200e-9, "bwd/ffn": 400e-9, "opt/-": 100e-9,
         "other/-": 50e-9})
    assert got["idle_s"] == pytest.approx(250e-9)
    assert got["exposed_s"] == 0 and adds_up(got)


@pytest.mark.parametrize("scope,phase,block", [
    (FWD, "fwd", "ffn"), (BWD, "bwd", "ffn"), (OPT, "opt", "-"),
    (RING, "fwd", "ring"), (GRAD, "other", "-"),
    (STEP + "transpose(jvp(lm.loss))/lm.head/jit(log_softmax)/sub", "bwd",
     "head"),
    ("jit(greedy_decode)/lm.prefill/lm.attn/jit(_flash_pallas)/flash_pallas"
     "/pallas_call", "prefill", "attn"),
    ("jit(greedy_decode)/lm.first_token/argmax", "first_token", "-"),
    ("jit(greedy_decode)/lm.decode/while/body/closed_call/lm.ffn/dot_general",
     "decode", "ffn"),
    ("jit(step)/jvp(jit(_flash_pallas))/pallas_call", "other", "-")])
def test_a_scope_path_names_its_phase_and_block(scope, phase, block):
    assert scope_reader.phase_of(scope) == phase
    assert scope_reader.block_of(scope) == block


def test_a_planted_row_under_the_optimizer_moves_only_opt():
    rows = recorded("scopes_train_step.json.gz")
    before = scope_reader.reduce(rows)
    gap_lo = max(r[3] + r[4] for r in rows if r[0] == DEV and r[1] == OPS)
    planted = op("%planted", gap_lo + 1000, gap_lo + 501000, OPT)
    after = scope_reader.reduce(rows + [planted])
    assert after["phase_s"]["opt"] - before["phase_s"]["opt"] == \
        pytest.approx(500e-6)
    assert after["idle_s"] - before["idle_s"] == pytest.approx(-500e-6)
    for phase in ("fwd", "bwd", "other"):
        assert after["phase_s"][phase] == before["phase_s"][phase]
    assert adds_up(after)
    context = {"scopes": dict(after, calls=1)}
    assert scope_ms.read(context, "opt") - 1e3 * before["phase_s"]["opt"] \
        == pytest.approx(0.5)
    assert scope_ms.read(context, "fwd") == 1e3 * before["phase_s"]["fwd"]


def test_a_collective_is_exposed_only_where_nothing_else_runs():
    rows = [host("pb.call", 0, 1000),
            op("%matmul", 100, 500, BWD, "fusion"),
            # wholly under the matmul, on the asynchronous line
            op("%ag-start", 150, 450, GRAD, "all-gather-start", line=ASYNC),
            # beside nothing, on the operations' own line
            op("%ar", 600, 800, GRAD, "all-reduce"),
            # the ring's exchange: in flight from 820, waited for 850-900
            op("%cp-start", 820, 900, RING, "collective-permute-start",
               line=ASYNC),
            op("%flash", 820, 850, FWD, "custom-call"),
            op("%cp-done", 850, 900, RING, "collective-permute-done"),
            # an asynchronous copy is no collective and no row
            op("%copy-start", 900, 990, "", "copy-start", line=ASYNC)]
    got = scope_reader.reduce(rows)
    assert got["collective_s"] == pytest.approx(
        {"grad": 500e-9, "ring": 80e-9})
    assert got["collective_exposed_s"] == pytest.approx(
        {"grad": 200e-9, "ring": 50e-9})
    assert got["exposed_s"] == pytest.approx(250e-9)
    assert got["phase_s"] == pytest.approx({"bwd": 400e-9, "fwd": 30e-9})
    assert got["idle_s"] == pytest.approx(320e-9) and adds_up(got)
    context = {"scopes": dict(got, calls=2)}
    assert collective_exposed_ms.read(context) == pytest.approx(125e-6)
    # one chip: no collective, nothing to report
    alone = scope_reader.reduce(rows[:2])
    assert collective_exposed_ms.read(
        {"scopes": dict(alone, calls=1)}) is None


def test_times_are_the_mean_over_devices():
    rows = [host("pb.call", 0, 1000),
            op("%f", 0, 400, FWD), op("%ar", 400, 600, GRAD, "all-reduce"),
            op("%f", 0, 200, FWD, plane="/device:TPU:1"),
            op("%ar", 200, 600, GRAD, "all-reduce", plane="/device:TPU:1")]
    got = scope_reader.reduce(rows)
    assert got["phase_s"] == pytest.approx({"fwd": 300e-9})
    assert got["exposed_s"] == pytest.approx(300e-9)
    assert got["idle_s"] == pytest.approx(400e-9) and adds_up(got)


def test_idle_inside_the_hosts_span_and_the_first_token():
    rows = [host("pb.feed", 0, 300), host("lm.shard_batch", 100, 250),
            host("pb.call", 300, 1000),
            op("jit_greedy_decode(77)", 200, 900, line=MODULES),
            op("%p", 200, 500, "jit(greedy_decode)/lm.prefill/lm.ffn/dot"),
            op("%t", 520, 530, "jit(greedy_decode)/lm.first_token/argmax"),
            op("%d", 530, 900, "jit(greedy_decode)/lm.decode/while/body/x")]
    got = scope_reader.reduce(rows)
    assert got["feed_idle_s"] == pytest.approx(100e-9)   # 100-200 of 100-250
    assert got["first_token_s"] == pytest.approx([330e-9])
    assert got["runs"] == {"jit_greedy_decode": 1}
    context = {"scopes": dict(got, calls=1),
               "cell": type("Cell", (), {"traffic": {"n_new": 38}})}
    assert host_span_idle_ms.read(context) == pytest.approx(100e-6)
    assert first_token_ms.read(context) == pytest.approx(330e-6)
    assert scope_ms.read(context, "decode", per="decode_step") == \
        pytest.approx(370e-6 / 37)
    assert scope_ms.read(context, "prefill", block="ffn") == \
        pytest.approx(300e-6)
    assert scope_ms.read(context, "prefill", block="attn") is None


def test_a_program_without_the_scopes_gives_nothing():
    """The parent commit: operations and collectives, no `lm.` name."""
    rows = [host("pb.feed", 0, 100), host("pb.call", 100, 1000),
            op("%f", 100, 900, "jit(step)/jvp(jit(_flash_pallas))/x"),
            op("jit_step(5)", 100, 900, line=MODULES)]
    got = scope_reader.reduce(rows)
    assert got["phase_s"] == pytest.approx({"other": 800e-9})
    assert got["feed_idle_s"] is None
    context = {"scopes": dict(got, calls=1)}
    for phase in ("fwd", "bwd", "opt", "prefill", "decode"):
        assert scope_ms.read(context, phase) is None
    assert first_token_ms.read(context) is None
    assert host_span_idle_ms.read(context) is None
    assert collective_exposed_ms.read(context) is None
    for reader in (first_token_ms, host_span_idle_ms, collective_exposed_ms):
        assert reader.read({"scopes": {}}) is None
    assert scope_ms.read({"scopes": {}}, "fwd") is None
    assert scope_reader.reduce([]) == {}


# --------------------------------------------------------------------------
# the reduction, on recorded cuts of chip traces
# --------------------------------------------------------------------------

def accepted_patterns(metric):
    return harness.load_json(ROOT, "perfbench", "layer_metrics",
                             metric + ".json")["args"]["match"]


def test_the_recorded_train_step():
    """The second of the three traced steps of `mistral7b-train-1chip`
    (my chip run, PR 26): 463 rows, host spans included."""
    rows = recorded("scopes_train_step.json.gz")
    got = scope_reader.reduce(rows)
    old = trace_reader.reduce([r[:5] for r in rows])
    assert got["window_s"] == old["window_s"] == pytest.approx(0.362862366)
    assert got["idle_s"] == pytest.approx(old["window_s"] - old["busy_s"])
    assert adds_up(got) and got["exposed_s"] == 0
    assert got["phase_s"] == pytest.approx(
        {"fwd": 0.096258715, "bwd": 0.254935598, "opt": 0.005847945,
         "other": 0.003027344})
    assert got["phase_s"]["other"] < 0.05 * got["window_s"]
    assert got["runs"] == {"jit_lm_train_step": 1}
    assert got["phase_block_s"]["bwd/ffn"] == pytest.approx(0.148158, rel=1e-4)
    # the accepted kernel metric still finds its kernels, now by the
    # pinned names, in the forward and in the backward pass
    patterns = accepted_patterns("attn_kernel_roofline_pct.train")
    flash = [r for r in rows if "_pallas" in r[2].split(" ")[0]]
    kernels = {r[2].split(" ")[0]: r[5] for r in flash}
    assert sorted(k.split(".")[0] for k in kernels) == [
        "%flash_bwd_pallas_dkv", "%flash_bwd_pallas_dkv",
        "%flash_bwd_pallas_dq", "%flash_bwd_pallas_dq",
        "%flash_pallas", "%flash_pallas"]
    assert trace_reader.matching_seconds(old, patterns) == pytest.approx(
        sum(r[4] for r in flash) / 1e9) == pytest.approx(0.036995139)
    assert all(r[6] == "custom-call" for r in flash)
    for name, scope in kernels.items():
        phase = "bwd" if "bwd" in name else "fwd"
        assert (scope_reader.phase_of(scope), scope_reader.block_of(scope)) \
            == (phase, "attn")
    table = scope_reader.table(got, 1)
    assert "bwd/ffn" in table and "idle" in table


def test_the_recorded_2x2_train_step():
    """The second traced step of `mistral7b-train-2x2` on its four chips
    (my chip run, PR 26): the gradient all-reduce runs beside nothing,
    half of the ring's exchange is under the flash kernels."""
    rows = recorded("scopes_train_step_2x2.json.gz")
    got = scope_reader.reduce(rows)
    old = trace_reader.reduce([r[:5] for r in rows])
    assert got["window_s"] == old["window_s"] == pytest.approx(0.326068671)
    assert adds_up(got)
    # an exchange in flight with nothing on the operations' line is not
    # idle here: 0.35 us of this step
    assert got["idle_s"] == pytest.approx(old["window_s"] - old["busy_s"],
                                          rel=1e-3)
    assert got["idle_s"] < old["window_s"] - old["busy_s"]
    assert got["phase_s"] == pytest.approx(
        {"fwd": 0.066003678, "bwd": 0.138558889, "opt": 0.024569444,
         "other": 0.006114007})
    assert got["phase_s"]["other"] < 0.05 * got["window_s"]
    assert got["collective_s"] == pytest.approx(
        {"ring": 0.018031334, "grad": 0.075407983})
    assert got["collective_exposed_s"] == pytest.approx(
        {"ring": 0.009007633, "grad": 0.075407983})
    assert got["exposed_s"] == pytest.approx(0.084415616)
    assert got["feed_idle_s"] == pytest.approx(0.002834552)
    assert got["runs"] == {"jit_lm_train_step": 1, "jit__multi_slice": 2}
    # every collective is one of the two kinds, by scope and by opcode
    kinds = {(r[6].removesuffix("-start").removesuffix("-done"),
              "lm.ring" in r[5]) for r in rows
             if scope_reader.is_collective(r[6])}
    assert kinds == {("all-reduce", False), ("collective-permute", True)}
    flash = [r for r in rows if "_pallas" in r[2].split(" ")[0]]
    assert len(flash) == 36          # 4 chips: ring of 2, 2 layers
    assert trace_reader.matching_seconds(
        old, accepted_patterns("attn_kernel_roofline_pct.train")) == \
        pytest.approx(sum(r[4] for r in flash) / 4e9)
    assert "collectives/grad" in scope_reader.table(got, 1)


def test_the_recorded_decode_request():
    """One request of `mistral7b-decode-chat-1chip` from its program's
    start to 30 ms past its first token: prefill whole, three scanned
    steps. The cut has no host row, so the window is the operations'."""
    rows = recorded("scopes_decode_request.json.gz")
    got = scope_reader.reduce(rows)
    assert adds_up(got)
    assert got["first_token_s"] == pytest.approx([0.396881905])
    assert got["phase_s"]["prefill"] == pytest.approx(0.387679927)
    assert got["phase_s"]["first_token"] == pytest.approx(3.398e-6)
    assert got["phase_s"]["other"] < 0.05 * got["window_s"]
    assert got["feed_idle_s"] is None
    lo = min(r[3] for r in rows)
    hi = max(r[3] + r[4] for r in rows if r[1] == OPS)
    old = trace_reader.reduce([r[:5] for r in rows]
                              + [host("pb.call", lo, hi)[:5]])
    kernel = [r for r in rows if r[2].startswith("%_decode_pallas")]
    assert len(kernel) == 36 and all(
        scope_reader.phase_of(r[5]) == "decode"
        and scope_reader.block_of(r[5]) == "attn" for r in kernel)
    assert trace_reader.matching_seconds(
        old, accepted_patterns("decode_kernel_roofline_pct.serve")) == \
        pytest.approx(sum(r[4] for r in kernel) / 1e9)


# --------------------------------------------------------------------------
# the hand-over
# --------------------------------------------------------------------------

def test_the_calls_are_traced_again_where_no_reduction_is_handed_over(
        checkout, capsys):
    """On the CPU the second trace has no device plane, so there is
    nothing to reduce; the subject is built, called and freed."""
    import jax
    cell = harness.Cell("tiny-train", checkout)
    context = {"cell": cell, "chips": 1, "device": jax.devices()[0]}
    assert scope_reader.of(context) == {}
    assert context["scopes"] == {}
    assert scope_ms.read(context, "fwd") is None
    handed = {"cell": cell, "scopes": {"phase_s": {"fwd": 2.0}, "calls": 4}}
    assert scope_ms.read(handed, "fwd") == 500.0
    assert not os.path.exists(os.path.join(ROOT, ".perfbench_work",
                                           "tiny-train.scopes"))


ACCEPTED = {
    "configs/mistral-7b-v0.1.serve.json": "49227149c6ad1142",
    "configs/mistral-7b-v0.1.train.json": "ae04873bccc4e352",
    "layer_metrics/attn_kernel_roofline_pct.train.json": "177e82f7be0f1c2f",
    "layer_metrics/decode_kernel_roofline_pct.serve.json": "ee0492a7f78bc518",
    "layer_metrics/device_idle_pct.serve.json": "10fbdfbe36f64175",
    "layer_metrics/device_idle_pct.train.json": "10fbdfbe36f64175",
    "layer_metrics/programs_built_in_window.serve.json": "d45ec5561642b6e1",
    "layer_metrics/programs_built_in_window.train.json": "d45ec5561642b6e1",
    "layer_metrics/request_mfu_pct.serve.json": "46df089f3e6cdbcd",
    "layer_metrics/step_mfu_pct.train.json": "70ae2b1496d0ba00",
    "limits/mistral7b-decode-chat-1chip.json": "eb4e13690b21bc4f",
    "limits/mistral7b-train-1chip.json": "9966d98ed6898c57",
    "limits/mistral7b-train-2x2.json": "09d026221991de41",
    "peaks.json": "939f725afa164a16",
    "traffic/decode-b32-p384-n128.json": "8da36870a1a5c860",
    "traffic/train-3x4096.json": "ee3e27c45145f003",
    "traffic/train-8x4096-dp2sp2.json": "ae5ddaf40489f60f",
}


@pytest.mark.parametrize("name", sorted(ACCEPTED))
def test_an_accepted_data_file_is_as_pr_24_left_it(name):
    with open(os.path.join(ROOT, "perfbench", name), "rb") as f:
        assert hashlib.sha256(f.read()).hexdigest()[:16] == ACCEPTED[name]


def test_the_new_metrics_are_entries_at_the_end_and_files_beside_the_old():
    manifest = harness.load_json(ROOT, "BENCHMARK.json")
    names = [m["name"] for m in manifest["per_layer"]]
    assert names[8:] == [
        "fwd_ms.train", "bwd_ms.train", "opt_ms.train",
        "collective_exposed_ms.train", "feed_idle_ms.train",
        "prefill_ms.serve", "decode_step_ms.serve", "first_token_ms.serve"]
    for m in manifest["per_layer"][8:]:
        assert (m["unit"], m["better"], m["source"]) == \
            ("ms", "lower", "device_trace")
        spec = harness.load_json(ROOT, "perfbench", "layer_metrics",
                                 m["name"] + ".json")
        assert spec["reader"] in ("scope_ms", "collective_exposed_ms",
                                  "first_token_ms", "host_span_idle_ms")
