"""Kernel ``kernels/paged_prefill`` (chunk steps): the least time of the
work it needs over its device time in the profiled chunk steps.  Per row
that carries a request: causal QK and PV ops of each chunk position over
the positions before it and itself; bytes of the resident K and V read
(positions before the chunk), the chunk's K and V written, and its queries
read and context written.  The least time is the larger of the two bounds;
which one it was goes to the run's notes."""
from bench import devtrace as tr
from bench import work
from bench.harness import CHUNK_PROGRAM

KERNEL = r"^paged_prefill_chunk_kernel\b"   # the kernel function's name


def step_work(shape, family, arch, itemsize):
    """(ops, bytes) the chunk kernel needs in one step, all layers."""
    per_tok = family.kv_bytes_per_token(arch, itemsize)
    qo = family.attention_io_bytes(arch, itemsize)
    ops = nbytes = 0
    for start, n in work.rows(shape):
        ops += sum(family.attention_ops(arch, start + j + 1) for j in range(n))
        nbytes += (start + n) * per_tok + n * qo
    return ops, nbytes


def read(run):
    ops = nbytes = t = 0
    for _, shape, inner in run.device_steps(CHUNK_PROGRAM, "chunk"):
        k = tr.named(inner, KERNEL)
        if not k:
            continue
        o, b = step_work(shape, run.family, run.arch, run.kv_itemsize)
        ops, nbytes, t = ops + o, nbytes + b, t + sum(e.dur for e in k)
    if not t:
        return None
    mem = nbytes / run.peaks["hbm_bytes_per_s"]
    comp = ops / run.peaks["bf16_flops_per_s"]
    run.notes["paged_prefill_bound"] = "memory" if mem >= comp else "compute"
    return 100.0 * max(mem, comp) / (t * 1e-9)
