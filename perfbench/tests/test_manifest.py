"""BENCHMARK.json and the data files it names."""

import json
import os
import re

import pytest

from conftest import ROOT
from perfbench import harness

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PUBLISHED = {"hidden_size": 4096, "intermediate_size": 14336,
             "num_attention_heads": 32, "num_key_value_heads": 8,
             "num_hidden_layers": 32, "vocab_size": 32000,
             "sliding_window": 4096, "rope_theta": 10000.0,
             "rms_norm_eps": 1e-5, "max_position_embeddings": 32768,
             "tie_word_embeddings": False}


@pytest.fixture(scope="module")
def manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_every_cell_finds_its_files_by_name(manifest):
    for w in manifest["workloads"]:
        cell = harness.Cell(w["name"])
        assert cell.traffic["kind"] in ("train", "decode")
        assert harness.driver_for(cell.traffic["kind"]).run
        assert cell.limits, f"{w['name']} has no limits"
        for m in cell.metrics("per_layer"):
            spec = cell.data("layer_metrics", m["name"])
            assert os.path.exists(os.path.join(
                ROOT, "perfbench", "readers", spec["reader"] + ".py"))


def test_names_and_units_keep_to_the_allowed_characters(manifest):
    entries = (manifest["configs"] + manifest["workloads"]
               + manifest["end_to_end"] + manifest["per_layer"])
    names = [e["name"] for e in entries]
    names += [w[k] for w in manifest["workloads"] for k in ("config", "traffic")]
    names += [k for c in manifest["configs"] for k in c["reduced"]]
    assert all(NAME.match(n) for n in names), names
    for group in ("configs", "workloads"):
        got = [e["name"] for e in manifest[group]]
        assert len(got) == len(set(got))
    metrics = [m["name"] for m in manifest["end_to_end"] + manifest["per_layer"]]
    assert len(metrics) == len(set(metrics))
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
    for m in manifest["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1


def test_every_cell_reports_what_its_layer_metrics_move(manifest):
    end = {m["name"]: m for m in manifest["end_to_end"]}
    assert "setup_s" in end
    all_cells = [w["name"] for w in manifest["workloads"]]
    for w in all_cells:
        reported = [n for n, m in end.items()
                    if w in m.get("workloads", all_cells)]
        assert "setup_s" in reported and len(reported) >= 2, w
        layer = [m for m in manifest["per_layer"]
                 if w in m.get("workloads", all_cells)]
        assert layer, w
        for m in layer:
            assert m["moves"] in reported, (w, m["name"])
        assert any("mfu" in re.split(r"[._]", m["name"]) for m in layer), w


def test_only_the_2x2_train_cell_asks_for_four_chips(manifest):
    for w in manifest["workloads"]:
        assert w["chips"] == (4 if w["name"] == "mistral7b-train-2x2" else 1)


def test_configurations_keep_the_published_widths(manifest):
    for c in manifest["configs"]:
        with open(os.path.join(ROOT, c["file"])) as f:
            cfg = json.load(f)
        changed = sorted(k for k, v in PUBLISHED.items() if cfg[k] != v)
        assert changed == sorted(c["reduced"]), c["name"]
        assert set(c["reduced"]) <= {"num_hidden_layers",
                                     "tie_word_embeddings"}
        assert sorted(cfg["reduced"]) == sorted(c["reduced"])


def test_an_unknown_device_kind_is_an_error():
    class Device:
        device_kind = "TPU v9 imaginary"
    with pytest.raises(SystemExit):
        harness.peaks_of(Device())
    Device.device_kind = "TPU v5 lite"
    peaks = harness.peaks_of(Device())
    assert peaks["bf16_flops_per_s"] == 197e12
    assert peaks["hbm_bytes_per_s"] == 819e9


def test_a_cell_a_mix_a_configuration_and_a_metric_are_added_as_files(checkout):
    """The fixture added all four to a copy and edited no file."""
    cell = harness.Cell("tiny-train", checkout)
    assert cell.config["hidden_size"] == 64
    assert cell.traffic["seq_len"] == 32
    context = {"programs_built": 0, "cell": cell}
    found = harness.layer_metrics(cell, context)
    assert found == {"programs_built_in_window.tiny":
                     {"value": 0, "unit": "programs"}}
    for kind in ("configs", "traffic", "limits", "layer_metrics"):
        for name in os.listdir(os.path.join(ROOT, "perfbench", kind)):
            with open(os.path.join(ROOT, "perfbench", kind, name)) as a, \
                    open(os.path.join(checkout, "perfbench", kind, name)) as b:
                assert a.read() == b.read()
