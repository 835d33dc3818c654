"""Kernels: the expert layer's grouped matmuls (``ragged-dot`` kernels in
the trace) against their roofline, in percent. The least time is
max(FLOPs / peak, bytes / bandwidth) for the window's held assignments and
(layer, expert) hits as the program counts them (``moe_counts.gmm_cost``),
never from padded groups; the time is the kernels' own device time in the
window, prefill and decode. Moves ``itl_p95_ms``."""

from benchmarks.chip import counts, moe_counts


def read(ctx):
    t = ctx.trace.op_s(moe_counts.GMM_KERNEL)
    c = moe_counts.window_counts(ctx)
    if t <= 0 or not c:
        return None
    flops, nbytes = moe_counts.gmm_cost(ctx.model, c["assignments"],
                                        c["experts_hit"])
    return 100.0 * counts.roofline_s(flops, nbytes, ctx.peak) / t
