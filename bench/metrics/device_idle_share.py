"""Device: share of the profiled window in which no operation ran,
1 - (union of the operations' intervals) / window, averaged over chips."""
from bench import devtrace as tr


def read(run):
    if run.trace is None:
        return None
    lo, hi = (run.trace.to_trace(t) for t in run.trace_window)
    shares = [1 - tr.busy_ns(p["ops"], lo, hi) / (hi - lo)
              for p in run.trace.devices.values()]
    return 100.0 * sum(shares) / len(shares) if shares else None
