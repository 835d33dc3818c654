"""Engine: host milliseconds of each engine tick in the window, the
``engine.tick`` span less the ``engine.*.sync`` spans nested in it (the
host blocked on the device), averaged over ticks. Moves ``out_tok_s``."""

from benchmarks.chip import engine_spans


def read(ctx):
    ticks = engine_spans.self_s(ctx, "engine.tick", r"^engine\..*\.sync$")
    return 1e3 * sum(ticks) / len(ticks) if ticks else None
