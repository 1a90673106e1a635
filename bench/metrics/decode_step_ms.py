"""Device milliseconds of one decode step: the decode program's device time
in the profiled window over its runs there."""
from bench.harness import DECODE_PROGRAM


def read(run):
    mods = run.modules(DECODE_PROGRAM)
    return sum(m.dur for m in mods) / len(mods) * 1e-6 if mods else None
