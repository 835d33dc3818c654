"""Model step: decode FLOPs of a mixture-of-experts model (2 per
attention, router and head weight and 4·L·H·dh per attended key for each
lane, plus 2·3·d·f per assignment of the decode steps to a held expert from
the program's routing counts, ``moe_counts.decode_flops``) over the device
time of the compiled decode program (``MetricContext.decode_program_s``)
at the chip's bf16 peak, in percent. Moves ``itl_p95_ms``."""

from benchmarks.chip import moe_counts


def read(ctx):
    c = moe_counts.window_counts(ctx, "decode")
    t = ctx.decode_program_s()
    if not c or t <= 0:
        return None
    flops = moe_counts.decode_flops(ctx.model, ctx.decodes(),
                                    c["assignments"])
    return 100.0 * flops / (t * ctx.peak["bf16_flops_per_s"])
