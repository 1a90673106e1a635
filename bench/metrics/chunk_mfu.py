"""Whole chunk step: as ``decode_mfu`` for the chunk steps, counting the
useful positions only (each chunk row's chunk, each decode row's one
position, the head once per row), not the padding to the chunk bucket."""
from bench import work
from bench.harness import CHUNK_PROGRAM


def step_ops(shape, family, arch):
    """(linear ops, other ops) of one chunk step."""
    rows = work.rows(shape)
    positions = sum(n for _, n in rows)
    lin = 2 * family.linear_params(arch) * positions
    other = sum(family.attention_ops(arch, start + j + 1)
                for start, n in rows for j in range(n))
    other += family.head_ops(arch) * len(rows)
    return lin, other


def read(run):
    steps = run.device_steps(CHUNK_PROGRAM, "chunk")
    if not steps:
        return None
    least = sum(work.least_seconds(*step_ops(s, run.family, run.arch),
                                   run.form, run.peaks) for _, s, _ in steps)
    return 100.0 * least / (sum(m.dur for m, _, _ in steps) * 1e-9)
