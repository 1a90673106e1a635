"""Kernel ``kernels/paged_prefill`` (chunk steps): the share of the KV
blocks its grid walks (``max_batch`` x the table width, per KV head per
layer) that hold the active rows' positions, summed over the chunk steps
launched in the window.  The engine counts both at each committed step
and hands them to the tracer (``Tracer.step_work``); a program that does
not count them reads nothing."""


def read(run):
    work = getattr(run.tracer, "work", None)
    if not work:
        return None
    w0, w1 = run.window
    walked = live = 0
    for _, kind, t, _, _, _, blocks_walked, blocks_live in work:
        if kind == "chunk" and w0 <= t < w1:
            walked += blocks_walked
            live += blocks_live
    return 100.0 * live / walked if walked else None
