#!/usr/bin/env python3
"""Knee sweep of an open-loop cell, on a chip: the cell's mix offered at
each rate in turn to one engine built once.

    python3 bench/sweep.py --workload <cell> --seconds <s> RATE [RATE ...]

Each rate starts as the cell's runs do (warm, where the cell gives
``token_s``).  For each rate, one line: requests due in the window, the
requests holding a slot and the waiting queue at the window's start and
end, the median time to first token in the window's first and last thirds,
the p95 time to first token and time per output token, and output tokens
per second.  A rate holds where, across the window, the waiting queue does
not grow (by more than one request), the requests holding a slot do not
grow by more than a tenth of the slots, and the last third's median time
to first token is under twice the first third's.  The last line gives the
highest rate that holds and 0.8 of it; the cell's ``rate_rps`` is set by
hand.  No correctness check.
"""
import argparse
import asyncio
import dataclasses
import json
import pathlib
import sys
import time

import numpy as np

ROOT = pathlib.Path(__file__).resolve().parents[1]
KNEE_SHARE = 0.8        # an open-loop cell offers this share of its knee


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=424242)
    ap.add_argument("rates", type=float, nargs="+")
    args = ap.parse_args()
    here = str(pathlib.Path(__file__).resolve().parent)
    sys.path[:] = [p for p in sys.path if p != here]
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    from bench import harness, traffic

    cell = harness.load_cell(args.workload)
    harness.check_chip(cell.chips)
    harness.enable_compile_cache(ROOT)
    log = harness.CompileLog()
    eng = harness.build_engine(cell.config, args.seed, cell.bench)
    eng._ensure_state()
    widths, ts = harness.step_shapes(eng, traffic.longest(cell.mix))
    harness.precompile(eng, widths, ts, 8)
    harness.warm_up(eng, widths, ts)
    held = []
    for rate in args.rates:
        c = dataclasses.replace(cell, cell=dict(cell.cell, rate_rps=rate))
        recs, timing = asyncio.run(harness._drive(
            eng, c, args.seed, args.seconds, None, time.perf_counter(), log))
        w0, w1 = timing["window"]
        due = [r for r in recs if w0 <= r.due < w1]
        third = (w1 - w0) / 3
        ttft = lambda rs: [(r.first or timing["end"]) - r.due for r in rs]
        first = ttft([r for r in due if r.due < w0 + third])
        last = ttft([r for r in due if r.due >= w1 - third])
        seen = [r.seen(timing["end"]) for r in due]
        tpot = [(t[-1] - t[0]) / (len(t) - 1) for t in seen if len(t) >= 2]
        toks = sum(1 for r in recs for t in r.times if w0 <= t < w1)
        slots = eng.scfg.max_batch
        if (timing["waiting"][1] <= timing["waiting"][0] + 1
                and timing["live"][1] <= timing["live"][0] + slots / 10
                and np.median(last) < 2 * np.median(first)):
            held.append(rate)
        print(json.dumps({
            "rate_rps": rate, "due": len(due),
            "live_at_window_ends": timing["live"],
            "waiting_at_window_ends": timing["waiting"],
            "ttft_median_first_third_ms": float(np.median(first)) * 1e3,
            "ttft_median_last_third_ms": float(np.median(last)) * 1e3,
            "ttft_p95_ms": float(np.percentile(ttft(due), 95)) * 1e3,
            "tpot_p95_ms": float(np.percentile(tpot, 95)) * 1e3,
            "output_tok_s": toks / (w1 - w0),
            "compiles_in_window": timing["compiles"]}), flush=True)
    knee = max(held) if held else None
    rate = round(KNEE_SHARE * knee, 4) if knee else None
    print(json.dumps({"knee_rps": knee, "cell_rate_rps": rate}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
