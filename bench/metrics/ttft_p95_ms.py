"""95th percentile, over every request due in the window, of the first
streamed token's arrival less the time the request was due.  A request
with no token when measuring stopped counts with that time."""
import numpy as np


def read(run):
    vals = [min(r.first or run.end, run.end) - r.due
            for r in run.in_window()]
    return float(np.percentile(vals, 95)) * 1e3 if vals else None
