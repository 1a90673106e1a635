"""Fused step (chunk steps): the share of the positions every linear layer
computes (``max_batch`` x the chunk bucket) that carry a request (a
prefilling row's chunk, a decoding row's token), summed over the chunk
steps launched in the window.  The engine counts both at each committed
step and hands them to the tracer (``Tracer.step_work``); a program that
does not count them reads nothing."""


def read(run):
    work = getattr(run.tracer, "work", None)
    if not work:
        return None
    w0, w1 = run.window
    done = useful = 0
    for _, kind, t, _, positions_run, positions_useful, _, _ in work:
        if kind == "chunk" and w0 <= t < w1:
            done += positions_run
            useful += positions_useful
    return 100.0 * useful / done if done else None
