"""A configuration, a traffic mix, a cell and a per-layer metric added as
files only, to a copy of the benchmark's tree, are found by name: no code
is edited."""
import json
import shutil
import time

from bench import harness
from bench.tests.conftest import TINY


def test_new_files_are_found_by_name(tmp_path, v5e_peaks):
    shutil.copytree(harness.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    shutil.copytree(TINY / "bench", tmp_path / "bench", dirs_exist_ok=True)
    doc = json.loads((TINY / "BENCHMARK.json").read_text())
    b = tmp_path / "bench"

    config = json.loads((b / "configs" / "tiny-bf16.json").read_text())
    config["architecture"]["num_hidden_layers"] = 1
    config["reduced"] = ["num_hidden_layers"]
    (b / "configs" / "tiny-one.json").write_text(json.dumps(config))
    mix = json.loads((b / "traffic" / "chat.json").read_text())
    mix["output"] = {"dist": "uniform", "min": 2, "max": 3}
    (b / "traffic" / "short.json").write_text(json.dumps(mix))
    (b / "cells" / "tiny-one.short.json").write_text(json.dumps(
        {"rate_rps": 3.0, "limits": {"logit_gap": 0.05}}))
    (b / "metrics" / "requests_seen.py").write_text(
        "def read(run):\n    return float(len(run.records))\n")
    doc["configs"].append({"name": "tiny-one", "source": "x",
                           "file": "bench/configs/tiny-one.json",
                           "reduced": ["num_hidden_layers"], "why": "x"})
    doc["workloads"].append({"name": "tiny-one.short", "config": "tiny-one",
                             "traffic": "short", "chips": 1, "why": "x"})
    doc["per_layer"].append({"name": "requests_seen", "unit": "requests",
                             "better": "higher", "source": "host_clock",
                             "layer": "client", "moves": "ttft_p95_ms",
                             "workloads": ["tiny-one.short"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(doc))

    cell = harness.load_cell("tiny-one.short", root=tmp_path, bench=b)
    assert cell.config["architecture"]["num_hidden_layers"] == 1
    assert cell.mix["output"]["max"] == 3
    assert [m["name"] for m in cell.metric_entries(True)][-1] == \
        "requests_seen"
    out = harness.run_cell(cell, 77, 1.5, True, time.perf_counter(),
                           require_chip=False, peaks=v5e_peaks,
                           out_dir=tmp_path / "trace")
    assert out["correct"], out["checks"]
    assert out["metrics"]["requests_seen"]["value"] > 0
    assert "ttft_p95_ms" not in out["metrics"]
