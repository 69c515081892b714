"""A temporary checkout: BENCHMARK.json and the benchmark's data files
copied, with the tiny test-only configuration and mixes added as new
files and new entries (nothing edited), the way a later PR adds a cell."""

import json
import os
import shutil
import sys

import pytest

# four virtual CPU devices for the mesh cells, set before JAX starts (a
# session that already has more, as the repo's own tests do, keeps them)
if "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                               " --xla_force_host_platform_device_count=4")

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

TINY_LIMITS = {
    "tiny-train": {"grad1_error_worst_leaf": 0.08,
                   "grad1_worst_leaf": 0.02, "delta_worst_leaf": 0.2},
    "tiny-train-2x2": {"grad1_error_worst_leaf": 0.08,
                       "grad1_worst_leaf": 0.02, "delta_worst_leaf": 0.2},
    "tiny-decode": {"served_logit_gap": 0.05, "served_logit_gap_mean": 0.005,
                    "prompt_echo_mismatches": 0},
}


@pytest.fixture
def checkout(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    bench = tmp_path / "perfbench"
    for kind in ("configs", "traffic", "limits", "layer_metrics"):
        shutil.copytree(os.path.join(ROOT, "perfbench", kind), bench / kind)
    data = os.path.join(HERE, "data")
    shutil.copy(os.path.join(data, "tiny.json"), bench / "configs")
    shutil.copy(os.path.join(data, "train-tiny.json"), bench / "traffic")
    shutil.copy(os.path.join(data, "train-tiny-dp2sp2.json"), bench / "traffic")
    shutil.copy(os.path.join(data, "decode-tiny.json"), bench / "traffic")
    shutil.copy(bench / "layer_metrics" / "programs_built_in_window.train.json",
                bench / "layer_metrics" / "programs_built_in_window.tiny.json")
    for name, limits in TINY_LIMITS.items():
        (bench / "limits" / f"{name}.json").write_text(json.dumps(limits))
    manifest = json.loads((tmp_path / "BENCHMARK.json").read_text())
    manifest["configs"].append(
        {"name": "tiny", "source": "test only", "reduced": [],
         "file": "perfbench/configs/tiny.json", "why": "test only"})
    cells = {"tiny-train": ("train-tiny", 1), "tiny-decode": ("decode-tiny", 1),
             "tiny-train-2x2": ("train-tiny-dp2sp2", 4)}
    for name, (mix, chips) in cells.items():
        manifest["workloads"].append({"name": name, "config": "tiny",
                                      "traffic": mix, "chips": chips,
                                      "why": "test only"})
    for m in manifest["end_to_end"]:
        if m["name"] in ("train_tokens_per_s", "step_p95_ms"):
            m["workloads"] += ["tiny-train", "tiny-train-2x2"]
        if m["name"] in ("serve_tokens_per_s", "request_p95_ms"):
            m["workloads"].append("tiny-decode")
    manifest["per_layer"].append(
        {"name": "programs_built_in_window.tiny", "unit": "programs",
         "better": "lower", "source": "program_counter",
         "layer": "launchers and bootstrap", "moves": "train_tokens_per_s",
         "workloads": ["tiny-train"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(manifest))
    return str(tmp_path)
