"""Device milliseconds of one chunk step (prefill chunks with the decode
rows riding along): the chunk program's device time over its runs."""
from bench.harness import CHUNK_PROGRAM


def read(run):
    mods = run.modules(CHUNK_PROGRAM)
    return sum(m.dur for m in mods) / len(mods) * 1e-6 if mods else None
