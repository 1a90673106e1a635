"""A second architecture family goes in with new files only: a Qwen3
module (``arch/qwen3.py``: per-head query and key RMSNorm, no QKV bias, on
the program's registered ``qwen3-*`` architecture), a configuration and a
cell, written with ``BENCHMARK.json`` entries into a copy of the
benchmark's tree.  A run of the cell is correct, the lower-precision
control in the program's place is not, and neither is a reference that
drops the per-head norms."""
import json
import shutil
import time

from bench import harness
from bench.tests.conftest import TINY

SEED = 2 ** 33 + 29
NEW = ("bench/arch/qwen3.py", "bench/configs/tiny-qwen3-packed.json",
       "bench/cells/tiny-qwen3-packed.chat.json")
CONFIG = {"name": "tiny-qwen3-packed", "source": "test",
          "file": "bench/configs/tiny-qwen3-packed.json", "reduced": [],
          "why": "tiny second family for CPU tests"}
WORKLOAD = {"name": "tiny-qwen3-packed.chat", "config": "tiny-qwen3-packed",
            "traffic": "chat", "chips": 1, "why": "x"}


def _files(root):
    return {p.relative_to(root).as_posix(): p.read_bytes()
            for p in root.rglob("*") if p.is_file()
            and "__pycache__" not in p.parts}


def _tree(tmp_path):
    """The benchmark's tree with the tiny cells, then the new files: returns
    the root, after checking that nothing but the new files and the new
    ``BENCHMARK.json`` entries differs from the tree as it was."""
    bench = tmp_path / "bench"
    shutil.copytree(harness.BENCH, bench,
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    shutil.copytree(TINY / "bench", bench, dirs_exist_ok=True)
    for f in NEW:
        (tmp_path / f).unlink()
    doc = json.loads((TINY / "BENCHMARK.json").read_text())
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(doc))
    before = _files(tmp_path)
    assert not set(NEW) & set(before)

    for f in NEW:
        shutil.copyfile(TINY / f, tmp_path / f)
    doc["configs"].append(CONFIG)
    doc["workloads"].append(WORKLOAD)
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(doc))
    after = _files(tmp_path)
    changed = {f for f in after if before.get(f) != after[f]}
    assert changed == set(NEW) | {"BENCHMARK.json"}
    old = json.loads(before["BENCHMARK.json"])
    assert all(doc[k][:len(v)] == v for k, v in old.items()
               if isinstance(v, list))
    return tmp_path


def _cell(root):
    return harness.load_cell(WORKLOAD["name"], root=root, bench=root / "bench")


def test_second_family_runs_correct_and_its_control_does_not(tmp_path):
    root = _tree(tmp_path)
    cell = _cell(root)
    out = harness.run_cell(cell, SEED, 2.0, False, time.perf_counter(),
                           require_chip=False, control=True)
    family = harness.family_of(cell.config, cell.bench)
    assert family.__file__ == str((root / NEW[0]).resolve())
    cfg = harness.model_config(cell.config, cell.bench)
    assert cfg.qk_norm and not cfg.qkv_bias
    assert out["correct"], out["checks"]
    assert out["notes"]["checked"]["tokens_in_window"] > 0
    assert not out["control"]["correct"], out["control"]


def test_second_family_reference_without_head_norms_is_not_correct(
        tmp_path, monkeypatch):
    cell = _cell(_tree(tmp_path))
    family = harness.family_of(cell.config, cell.bench)
    monkeypatch.setattr(family, "head_norm", lambda w, q, k: (q, k))
    out = harness.run_cell(cell, SEED, 2.0, False, time.perf_counter(),
                           require_chip=False)
    assert not out["correct"], out["checks"]
