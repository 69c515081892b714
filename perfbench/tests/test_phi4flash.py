"""The Phi-4-mini-flash-reasoning files of the benchmark on the CPU: the
new readers on a made-up context, the manifest's entries, and the
`session` driver end to end at a tiny test-only size, sound and broken,
where `correct` has to come out false. (The counts' arithmetic and the
program against the reference are in tier-1: `tests/test_hybrid_lm.py`.)"""

import json
import os
import shutil

import pytest

from conftest import HERE, ROOT
from perfbench import counts_phi4flash, harness
from perfbench.readers import phi4flash_attn, phi4flash_mfu
from test_session import AlteredTokens, Float32, drive

CELL = "phi4flash-reason-decode-16k-1chip"
LIMITS = {"served_logit_gap": 0.01, "served_logit_gap_mean": 0.001}


class Device:
    device_kind = "TPU v5 lite"


def context_of(sub_scopes, trace=None, calls=2):
    return {"cell": harness.Cell(CELL), "calls": calls, "chips": 1,
            "device": Device(), "sub_scopes": sub_scopes, "trace": trace,
            "loop": {"window_s": 3.0}}


def test_the_readers_read_the_kernels_events_and_nothing_without_them(capsys):
    """On a program without the kernel's events or the scopes (the
    parent) every new reader returns None and the line leaves the metric
    out."""
    match = ["^%_decode_pallas"]
    for ctx in (context_of({}), context_of(None, {"ops_s": {}})):
        for report in ("kernel_ms", "roofline_pct", "rest_ms"):
            assert phi4flash_attn.read(ctx, report, match,
                                       ["lm.attn"]) is None
    # 2 calls x 32 steps; 30 ms of the kernel a step, 35 under lm.attn
    trace = {"ops_s": {"%_decode_pallas.120 custom-call -> f32": 64 * 27e-3,
                       "%_decode_pallas.112 custom-call -> f32": 64 * 3e-3,
                       "%fusion.1 fusion": 1.0}}
    ctx = context_of({"lm.attn": 64 * 35e-3}, trace)
    assert abs(phi4flash_attn.read(ctx, "kernel_ms", match) - 30.0) < 1e-9
    assert abs(phi4flash_attn.read(ctx, "rest_ms", match, ["lm.attn"])
               - 5.0) < 1e-9
    share = phi4flash_attn.read(ctx, "roofline_pct", match)
    # 22.17 GB a step (the positions 16384 .. 16415) at 819 GB/s = 27.07 ms
    assert abs(share - 100 * 27.07 / 30.0) < 0.1
    assert "bound by bytes" in capsys.readouterr().err
    mfu = phi4flash_mfu.read(ctx)
    assert abs(mfu - 100 * 2 * counts_phi4flash.turn_flops(
        ctx["cell"].config, 32, 16384, 32) / (3.0 * 197e12)) < 1e-9
    with pytest.raises(SystemExit):
        phi4flash_attn.read(ctx, "something", match)


def test_the_cell_finds_its_files_and_names_its_metrics():
    cell = harness.Cell(CELL)
    assert cell.chips == 1 and cell.traffic["kind"] == "session"
    assert {k: cell.traffic[k] for k in (
        "batch", "context_len", "n_new", "checked_requests", "checked_rows",
        "traced_calls")} == {
            "batch": 32, "context_len": 16384, "n_new": 32,
            "checked_requests": 2, "checked_rows": 1, "traced_calls": 2}
    assert cell.traffic["context_len"] % cell.traffic["prefill_chunk"] == 0
    assert sorted(cell.limits) == ["served_logit_gap",
                                   "served_logit_gap_mean"]
    names = [m["name"] for m in cell.metrics("per_layer")]
    assert names == ["device_idle_pct.serve", "programs_built_in_window.serve",
                     "decode_step_ms.session", "request_mfu_pct.phi4flash",
                     "mixers_ms.phi4flash", "attn_kernel_ms.phi4flash",
                     "attn_kernel_roofline_pct.phi4flash",
                     "mixers_rest_ms.phi4flash", "ffn_ms.phi4flash"]
    for name in names:
        spec = cell.data("layer_metrics", name)
        assert os.path.exists(os.path.join(
            ROOT, "perfbench", "readers", spec["reader"] + ".py"))
    assert [m["name"] for m in cell.metrics("end_to_end")] == [
        "serve_tokens_per_s", "request_p95_ms", "setup_s"]


def test_the_reference_imports_nothing_of_the_program():
    for name in ("reference_phi4flash", "weights_phi4flash",
                 "counts_phi4flash"):
        with open(os.path.join(ROOT, "perfbench", name + ".py")) as f:
            text = f.read()
        assert "import lua_mapreduce_tpu" not in text
        assert "from lua_mapreduce_tpu" not in text


@pytest.fixture
def phi4flash_checkout(checkout):
    """conftest's checkout with a tiny SambaY session cell added, as new
    files and new entries: the window (8) rolls three times over the
    context, which the prefill takes six positions at a time."""
    bench = os.path.join(checkout, "perfbench")
    shutil.copy(os.path.join(HERE, "data", "tiny-phi4flash.json"),
                os.path.join(bench, "configs"))
    with open(os.path.join(bench, "traffic", "session-tiny-phi4flash.json"),
              "w") as f:
        json.dump({"kind": "session", "why": "test only", "batch": 4,
                   "context_len": 24, "n_new": 8, "checked_requests": 2,
                   "checked_rows": 2, "traced_calls": 2,
                   "prefill_chunk": 6}, f)
    with open(os.path.join(bench, "limits", "tiny-session-phi4flash.json"),
              "w") as f:
        json.dump(LIMITS, f)
    path = os.path.join(checkout, "BENCHMARK.json")
    with open(path) as f:
        manifest = json.load(f)
    manifest["configs"].append(
        {"name": "tiny-phi4flash", "source": "test only", "reduced": [],
         "file": "perfbench/configs/tiny-phi4flash.json", "why": "test only"})
    manifest["workloads"].append(
        {"name": "tiny-session-phi4flash", "config": "tiny-phi4flash",
         "traffic": "session-tiny-phi4flash", "chips": 1, "why": "test only"})
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        if CELL in m.get("workloads", ()):
            m["workloads"].append("tiny-session-phi4flash")
    with open(path, "w") as f:
        json.dump(manifest, f)
    return checkout


def test_a_phi4flash_session_run_ends_in_the_contracts_line(
        phi4flash_checkout, capsys):
    """Every turn of the session starts from the same position: the
    states and the rolling buffers are taken back each time, and the
    checked turns (the 50th or so) still read the reference's tokens."""
    line, err = drive(phi4flash_checkout, capsys, "tiny-session-phi4flash",
                      make_session=Float32)
    assert line["correct"] is True, err
    assert line["attempted"] > 2 and line["failed"] == 0
    assert sorted(line["checks"]) == sorted(LIMITS)
    assert line["checks"]["served_logit_gap"][0] < 1e-4
    assert "programs built in the window 0" in err
    assert ("counters growing_bytes 16384 rolling_bytes 8192 state_bytes "
            "33792 snapshot_bytes") in err
    assert "growing_bytes_expected 16384 rolling_bytes_expected 8192" in err
    assert "output against the stream, by layer" in err


def test_a_broken_phi4flash_session_is_not_correct(phi4flash_checkout,
                                                   capsys):
    line, err = drive(phi4flash_checkout, capsys, "tiny-session-phi4flash",
                      make_session=AlteredTokens)
    assert line["correct"] is False
    value, limit = line["checks"]["served_logit_gap"]
    assert value > limit, err


def test_the_controls_read_higher_than_the_program(phi4flash_checkout,
                                                   capsys):
    """Each planted fault and the int8 control read over a limit of the
    tiny cell, the program under every one."""
    import jax
    from perfbench import controls_phi4flash
    controls_phi4flash.run(
        harness.Cell("tiny-session-phi4flash", phi4flash_checkout), [11],
        {11}, ["int8"], jax.devices()[:1], make_session=Float32)
    rows = [json.loads(line) for line
            in capsys.readouterr().out.strip().splitlines()]
    by = {r["what"]: r["readings"] for r in rows}
    assert all(by["program"][k] <= LIMITS[k] for k in LIMITS), by["program"]
    assert len(by) == 10
    for what, readings in by.items():
        if what != "program":
            assert any(readings[k] > LIMITS[k] for k in LIMITS), (what,
                                                                   readings)
