"""Kernels: the Pallas paged-decode kernel's share of its roofline, in
percent. The least time is max(FLOPs / peak, bytes / bandwidth) for the
attention each decode step owes, counted from every lane's real length
(``counts.paged_decode_cost``: each cached K and V row read once), not
from the pages the kernel visits; the time is the kernel's own device
time in the trace. Moves ``itl_p95_ms``."""

from benchmarks.chip import counts, harness


def read(ctx):
    t = ctx.trace.op_s(harness.PAGED_KERNEL)
    steps = ctx.decodes()
    if t <= 0 or not steps:
        return None
    flops = nbytes = 0.0
    for ctxs in steps:
        f, b = counts.paged_decode_cost(ctx.model, ctxs)
        flops += f
        nbytes += b
    return 100.0 * counts.roofline_s(flops, nbytes, ctx.peak) / t
