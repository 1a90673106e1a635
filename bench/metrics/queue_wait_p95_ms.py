"""Front end / admission: 95th percentile of submit -> admission, over the
requests admitted in the window (the engine's ``queued`` span, on the
engine clock)."""
import numpy as np


def read(run):
    if run.tracer is None:
        return None
    w0, w1 = run.window
    vals = [wait for t, wait in run.tracer.waits if w0 <= t < w1]
    return float(np.percentile(vals, 95)) * 1e3 if vals else None
