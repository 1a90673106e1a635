"""The step-work readers (``chunk_useful_share``, ``prefill_grid_live_share``)
listed for a cell as ``BENCHMARK.json`` entries only, in a copy of the tiny
tree: a traced run reports both, and each equals a recount from the step
shapes the harness's tracer keeps at launch."""
import json
import math
import shutil
import time

import pytest

from bench import harness
from bench.tests.conftest import TINY

ENTRIES = [
    {"name": "chunk_useful_share", "unit": "%", "better": "higher",
     "source": "program_counter",
     "layer": "fused step (serving/engine.py _decode and _chunk)",
     "moves": "ttft_p95_ms", "workloads": ["tiny-packed.chat"]},
    {"name": "prefill_grid_live_share", "unit": "%", "better": "higher",
     "source": "program_counter", "layer": "kernel kernels/paged_prefill",
     "moves": "ttft_p95_ms", "workloads": ["tiny-packed.chat"]},
]


def recount(run):
    """(useful share, live share) of the window's chunk launches, from the
    shapes alone."""
    serve = run.serve
    b, block = serve["max_batch"], serve["kv_block_size"]
    w0, w1 = run.window
    done = useful = walked = live = 0
    for s in run.tracer.launches:
        if s["kind"] != "chunk" or not w0 <= s["t"] < w1:
            continue
        done += b * s["chunk_t"]
        useful += sum(s["lens"][i] for i in s["active"])
        walked += b * s["table_width"]
        live += sum(math.ceil((s["starts"][i] + s["lens"][i]) / block)
                    for i in s["active"])
    return 100.0 * useful / done, 100.0 * live / walked


def test_step_work_readers_report_and_recount(tmp_path, v5e_peaks,
                                              monkeypatch):
    shutil.copytree(harness.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    shutil.copytree(TINY / "bench", tmp_path / "bench", dirs_exist_ok=True)
    doc = json.loads((TINY / "BENCHMARK.json").read_text())
    doc["per_layer"] += ENTRIES
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(doc))
    runs = []

    class Run(harness.Run):
        def __init__(self, *args, **kwargs):
            super().__init__(*args, **kwargs)
            runs.append(self)

    monkeypatch.setattr(harness, "Run", Run)
    cell = harness.load_cell("tiny-packed.chat", root=tmp_path,
                             bench=tmp_path / "bench")
    assert cell.config["serve"].get("prefill_budget") is None  # no pad rows
    out = harness.run_cell(cell, 2**31 + 77, 2.0, True, time.perf_counter(),
                           require_chip=False, peaks=v5e_peaks,
                           out_dir=tmp_path / "trace")
    assert out["correct"], out["checks"]
    useful = out["metrics"]["chunk_useful_share"]["value"]
    live = out["metrics"]["prefill_grid_live_share"]["value"]
    assert 0 < useful <= 100 and 0 < live <= 100
    (run,) = runs
    assert (useful, live) == pytest.approx(recount(run), rel=1e-12)


def test_step_work_readers_read_nothing_without_the_counts():
    """A program whose tracer keeps no step work (the parent of the change
    that added it), or an untraced run, gives no reading and no error."""
    import types
    for name in ("chunk_useful_share", "prefill_grid_live_share"):
        read = harness.load_reader(harness.BENCH, name)
        run = types.SimpleNamespace(tracer=None, window=(0.0, 1.0))
        assert read(run) is None
        run.tracer = types.SimpleNamespace(launches=[], spans=[], waits=[])
        assert read(run) is None
