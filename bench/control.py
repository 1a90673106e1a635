#!/usr/bin/env python3
"""Control readings of a cell's correctness check, on a chip.

    python3 bench/control.py --workload <cell> --seconds <s> SEED [SEED ...]

For each seed, one run of the cell at its own load and sizes, then the
reference twice over the same checked requests: at the configuration's
precision, against the served tokens (the program's reading, which decides
the run's ``correct``), and one precision step below, the tokens that lower
precision puts first put in the served tokens' place and judged by the same
comparison and limit (``control``: its ``correct`` has to come out false).
One JSON line per seed: the run's result line with ``seed`` and
``control`` added.  The benchmark's own runs never run the control.
"""
import argparse
import json
import pathlib
import sys
import time

T_START = time.perf_counter()
ROOT = pathlib.Path(__file__).resolve().parents[1]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("seeds", type=int, nargs="+")
    args = ap.parse_args()
    here = str(pathlib.Path(__file__).resolve().parent)
    sys.path[:] = [p for p in sys.path if p != here]
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    from bench import harness

    cell = harness.load_cell(args.workload)
    t0 = T_START
    for seed in args.seeds:
        out = harness.run_cell(cell, seed, args.seconds, False, t0,
                               control=True)
        print(json.dumps(dict(out, seed=seed), default=float), flush=True)
        t0 = time.perf_counter()
    return 0


if __name__ == "__main__":
    sys.exit(main())
