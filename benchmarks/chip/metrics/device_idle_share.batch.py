"""Device: share of the window with no operation running on the chip
(1 - busy / window, busy the union of device-operation intervals), in
percent, in the closed-loop batch cells. Moves ``out_tok_s``."""


def read(ctx):
    return ctx.idle_share()
