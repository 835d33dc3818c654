"""Device: FLOPs of every token the window processed, prompt and
decode, over the window's length at the chip's bf16 peak, in percent
(the whole step's share of the peak). Moves ``out_tok_s``."""


def read(ctx):
    flops = ctx.decode_flops() + ctx.prefill_flops()
    w = ctx.trace.window_s
    if flops <= 0 or w <= 0:
        return None
    return 100.0 * flops / (w * ctx.peak["bf16_flops_per_s"])
