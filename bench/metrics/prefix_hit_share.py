"""Scheduler / prefix cache: prompt positions the prefix cache covered, as
a share of all prompt positions admitted in the window (EngineStats
``prefill_positions_skipped`` over skipped plus prefilled)."""


def read(run):
    skipped = run.stats["prefill_positions_skipped"]
    total = skipped + run.stats["prefill_positions"]
    return 100.0 * skipped / total if total else None
