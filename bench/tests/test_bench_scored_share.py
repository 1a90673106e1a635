"""The ``prefill_scored_share`` reader listed for a cell as a
``BENCHMARK.json`` entry only, in a copy of the tiny tree served through
the fused kernels (interpret mode on the CPU): a traced run reports it, and
it equals a recount of the prefill kernel's fetch rule over the tables the
window's chunk steps were handed.  A program whose tracer keeps no fetched
blocks reads nothing."""
import json
import shutil
import time
import types

import pytest

from bench import harness
from bench.tests.conftest import TINY

ENTRY = {"name": "prefill_scored_share", "unit": "%", "better": "lower",
         "source": "program_counter", "layer": "kernel kernels/paged_prefill",
         "moves": "ttft_p95_ms", "workloads": ["tiny-packed.chat"]}


def recount(run):
    """Fetched share of the window's chunk launches, from their step shapes
    and tables alone."""
    from repro.kernels.paged_prefill.ops import fetched_blocks
    b, block = run.serve["max_batch"], run.serve["kv_block_size"]
    w0, w1 = run.window
    walked = fetched = 0
    for s in run.tracer.launches:
        if s["kind"] != "chunk" or not w0 <= s["t"] < w1:
            continue
        walked += b * s["table_width"]
        fetched += sum(int(m.sum()) for m in fetched_blocks(
            s["starts"], s["lens"], s["table"], block))
    return 100.0 * fetched / walked


def test_scored_share_reports_and_recounts(tmp_path, v5e_peaks,
                                           monkeypatch):
    shutil.copytree(harness.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    shutil.copytree(TINY / "bench", tmp_path / "bench", dirs_exist_ok=True)
    config = tmp_path / "bench" / "configs" / "tiny-packed.json"
    doc = json.loads(config.read_text())
    doc["serve"]["attn_impl"] = "fused"
    config.write_text(json.dumps(doc))
    doc = json.loads((TINY / "BENCHMARK.json").read_text())
    doc["per_layer"].append(ENTRY)
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(doc))
    runs = []

    class Run(harness.Run):
        def __init__(self, *args, **kwargs):
            super().__init__(*args, **kwargs)
            runs.append(self)

    make_tracer = harness.make_tracer

    def tracer_with_tables(eng):
        tracer = make_tracer(eng)
        launch_span = tracer.launch_span

        def keep_table(t0, t1, step, spec=False):
            launch_span(t0, t1, step, spec)
            shape = tracer.launches[-1]
            if shape["kind"] == "chunk":
                shape["table"] = eng.sched.block_tables[
                    :, :shape["table_width"]].copy()

        tracer.launch_span = keep_table
        return tracer

    monkeypatch.setattr(harness, "Run", Run)
    monkeypatch.setattr(harness, "make_tracer", tracer_with_tables)
    cell = harness.load_cell("tiny-packed.chat", root=tmp_path,
                             bench=tmp_path / "bench")
    out = harness.run_cell(cell, 2**31 + 91, 2.0, True, time.perf_counter(),
                           require_chip=False, peaks=v5e_peaks,
                           out_dir=tmp_path / "trace")
    assert out["correct"], out["checks"]
    share = out["metrics"]["prefill_scored_share"]["value"]
    (run,) = runs
    assert share == pytest.approx(recount(run), rel=1e-12)
    assert 0 < share < 100


def test_scored_share_reads_nothing_without_the_counts():
    """A program whose tracer keeps no fetched blocks (the parent of the
    change that added them), or an untraced run, gives no reading and no
    error; so does a window with no chunk step."""
    read = harness.load_reader(harness.BENCH, "prefill_scored_share")
    run = types.SimpleNamespace(tracer=None, window=(0.0, 1.0))
    assert read(run) is None
    run.tracer = types.SimpleNamespace(launches=[], spans=[], waits=[],
                                       work=[(0, "chunk", 0.5, 1, 8, 2, 16,
                                              3)])
    assert read(run) is None
    run.tracer.scored = [(0, "chunk", 0.5, 4)]
    assert read(run) == pytest.approx(25.0)
    run.window = (0.6, 1.0)
    assert read(run) is None
