#!/usr/bin/env python3
"""Record the small trace the trace-reduction tests read, on a chip.

    python3 bench/tests/record_trace.py [--out DIR]

One traced run of the tiny packed cell (``bench/tests/data/tiny``): the
profiler's ``.xplane.pb`` (gzipped) and what the host recorded beside it (the anchor,
the profiled window, each launched step's shape and the engine's spans),
plus the reduction's own readings of it, go to ``DIR`` (default
``.bench_trace/recorded``); ``bench/tests/data/`` keeps a copy.  Needs a TPU.
"""
import argparse
import glob
import gzip
import json
import pathlib
import shutil
import sys
import time

T_START = time.perf_counter()
ROOT = pathlib.Path(__file__).resolve().parents[2]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--out", default=str(ROOT / ".bench_trace" / "recorded"))
    args = ap.parse_args()
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    from bench import harness

    data = ROOT / "bench" / "tests" / "data"
    cell = harness.load_cell("tiny-packed.chat", root=data / "tiny",
                             bench=data / "tiny" / "bench")
    cell.bench = harness.BENCH
    out = pathlib.Path(args.out)
    shutil.rmtree(out, ignore_errors=True)
    captured = {}
    real = harness.Run.device_steps

    def keep(self, program, kind):
        captured["run"] = self
        return real(self, program, kind)

    harness.Run.device_steps = keep
    result = harness.run_cell(cell, 1234, 3.0, True, T_START, out_dir=out)
    run = captured["run"]
    pb = sorted(glob.glob(str(out / "trace-1234" / "plugins" / "profile" /
                               "*" / "*.xplane.pb")))[-1]
    with open(pb, "rb") as f, gzip.open(out / "trace.xplane.pb.gz",
                                         "wb") as g:
        shutil.copyfileobj(f, g)
    host = {"anchor_host": run.trace.anchor_host,
            "trace_window": list(run.trace_window),
            "launches": run.tracer.launches,
            "spans": run.tracer.spans,
            "arch": run.arch, "form": run.form,
            "kv_itemsize": run.kv_itemsize,
            "result": result}
    with open(out / "trace_host.json", "w") as f:
        json.dump(host, f, default=float)
    print(json.dumps(result, default=float))
    return 0


if __name__ == "__main__":
    sys.exit(main())
