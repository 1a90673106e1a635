"""95th percentile, over the requests due in the window that streamed at
least two tokens before measuring stopped, of (last token - first token) /
(tokens - 1), over the tokens streamed by then."""
import numpy as np


def read(run):
    vals = []
    for r in run.in_window():
        t = r.seen(run.end)
        if len(t) >= 2:
            vals.append((t[-1] - t[0]) / (len(t) - 1))
    return float(np.percentile(vals, 95)) * 1e3 if vals else None
