"""Output tokens the clients received inside the window, over its length."""


def read(run):
    w0, w1 = run.window
    n = sum(1 for r in run.records for t in r.times if w0 <= t < w1)
    return n / (w1 - w0)
