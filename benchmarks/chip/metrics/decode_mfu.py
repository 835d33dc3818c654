"""Model step: decode FLOPs (2 per matmul weight plus attention over each
lane's real length, ``counts.decode_flops``) over the device time of the
compiled decode program (``MetricContext.decode_program_s``) at the
chip's bf16 peak, in percent. Moves ``itl_p95_ms``."""


def read(ctx):
    t = ctx.decode_program_s()
    flops = ctx.decode_flops()
    if t <= 0 or flops <= 0:
        return None
    return 100.0 * flops / (t * ctx.peak["bf16_flops_per_s"])
