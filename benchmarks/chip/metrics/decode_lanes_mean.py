"""Scheduler: mean number of sequences in each decode step of the
window (lanes per ``decode_step`` call). Moves ``out_tok_s``."""


def read(ctx):
    steps = ctx.decodes()
    return sum(map(len, steps)) / len(steps) if steps else None
