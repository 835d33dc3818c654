"""KV cache: DLZS page-score pulls to the host (``engine.kv.scores.sync``)
per decode step (``engine.decode.step``) in the window. Moves
``itl_p95_ms``."""

from benchmarks.chip import engine_spans


def read(ctx):
    steps = engine_spans.count(ctx, "engine.decode.step")
    pulls = engine_spans.count(ctx, "engine.kv.scores.sync")
    return pulls / steps if steps else None
