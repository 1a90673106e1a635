"""Kernel ``kernels/paged_attention`` (decode): the least time of the work
it needs over its device time in the profiled decode steps.  Needed bytes:
each active row's resident K and V (its position plus the new one, all
layers, in the pool's dtype), the new K/V row written, and the query read
and context written; ops: QK and PV over the resident positions.  The
least time is the larger of bytes over HBM bandwidth and ops over the bf16
peak; idle rows and trash-block reads are not needed work."""
from bench import devtrace as tr
from bench import work
from bench.harness import DECODE_PROGRAM

KERNEL = r"^paged_attention_decode_kernel\b"   # the kernel function's name


def step_work(shape, family, arch, itemsize):
    """(ops, bytes) the decode kernel needs in one step, all layers."""
    per_tok = family.kv_bytes_per_token(arch, itemsize)
    qo = family.attention_io_bytes(arch, itemsize)
    ops = nbytes = 0
    for start, _ in work.rows(shape):
        ops += family.attention_ops(arch, start + 1)
        nbytes += (start + 2) * per_tok + qo
    return ops, nbytes


def read(run):
    ops = nbytes = t = 0
    for _, shape, inner in run.device_steps(DECODE_PROGRAM, "decode"):
        k = tr.named(inner, KERNEL)
        if not k:
            continue
        o, b = step_work(shape, run.family, run.arch, run.kv_itemsize)
        ops, nbytes, t = ops + o, nbytes + b, t + sum(e.dur for e in k)
    if not t:
        return None
    least = max(nbytes / run.peaks["hbm_bytes_per_s"],
                ops / run.peaks["bf16_flops_per_s"])
    return 100.0 * least / (t * 1e-9)
