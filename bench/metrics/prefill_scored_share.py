"""Kernel ``kernels/paged_prefill`` (chunk steps): the share of the KV
blocks handed to the kernel (``max_batch`` x the table width, per KV head
per layer) that it fetches — each row's resident real blocks and the blocks
its chunk is spliced into — summed over the chunk steps launched in the
window.  The engine counts both at each committed step and hands them to
the tracer (``Tracer.step_work``, ``Tracer.step_scored``); a program that
does not count the fetched blocks reads nothing."""


def read(run):
    scored = getattr(run.tracer, "scored", None)
    work = getattr(run.tracer, "work", None)
    if not scored or not work:
        return None
    w0, w1 = run.window
    walked_of = {step: walked for step, kind, t, _, _, _, walked, _ in work
                 if kind == "chunk" and w0 <= t < w1}
    walked = fetched = 0
    for step, _, _, blocks in scored:
        if step in walked_of:
            walked += walked_of[step]
            fetched += blocks
    return 100.0 * fetched / walked if walked else None
