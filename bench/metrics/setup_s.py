"""Process start to the first request of the ramp: imports, weights from
the seed, engine, warm-up (compiles or compile-cache reads)."""


def read(run):
    return run.setup_s
