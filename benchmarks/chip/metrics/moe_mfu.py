"""Device: FLOPs of every token the window processed, prompt and decode,
over the window's length at the chip's bf16 peak, in percent, for a
mixture-of-experts model: 2 per attention, router and head weight and
4·L·H·dh per attended key for each token, plus 2·3·d·f per assignment to
a held expert from the program's routing counts
(``moe_counts.window_flops``). Moves ``out_tok_s``."""

from benchmarks.chip import moe_counts


def read(ctx):
    c = moe_counts.window_counts(ctx)
    w = ctx.trace.window_s
    if not c or w <= 0:
        return None
    flops = moe_counts.window_flops(ctx.model, ctx.decodes(), ctx.waves(),
                                    c["assignments"])
    return 100.0 * flops / (w * ctx.peak["bf16_flops_per_s"])
