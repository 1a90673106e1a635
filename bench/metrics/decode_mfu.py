"""Whole decode step: the least time of the step's model operations on the
chip (linear layers at the form's peak, attention and head at the bf16
peak), over the step's device time, summed over the profiled decode steps.
Only rows that carry a request count."""
from bench import work
from bench.harness import DECODE_PROGRAM


def step_ops(shape, family, arch):
    """(linear ops, other ops) of one decode step."""
    rows = work.rows(shape)
    lin = 2 * family.linear_params(arch) * len(rows)
    other = sum(family.attention_ops(arch, start + 1) + family.head_ops(arch)
                for start, _ in rows)
    return lin, other


def read(run):
    steps = run.device_steps(DECODE_PROGRAM, "decode")
    if not steps:
        return None
    least = sum(work.least_seconds(*step_ops(s, run.family, run.arch),
                                   run.form, run.peaks) for _, s, _ in steps)
    return 100.0 * least / (sum(m.dur for m, _, _ in steps) * 1e-9)
