"""Fixtures of the benchmark's CPU tests: the tiny cells under
``data/tiny`` (same family as the benchmark's configurations, widths cut to
run in seconds on the CPU) and the v5e peaks."""
import json
import pathlib
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

DATA = pathlib.Path(__file__).resolve().parent / "data"
TINY = DATA / "tiny"


def tiny_cell(workload: str):
    from bench import harness
    cell = harness.load_cell(workload, root=TINY, bench=TINY / "bench")
    cell.bench = harness.BENCH          # the benchmark's own metric readers
    return cell


@pytest.fixture(scope="session")
def v5e_peaks():
    return json.loads((ROOT / "bench" / "peaks.json").read_text())[
        "devices"]["TPU v5 lite"]
