"""KV cache: host milliseconds of each decode step's page state (block
tables, allocation and eviction, copy-on-write, hot-page selection and
uploads), the ``engine.decode.page_state`` span less the DLZS score pull
``engine.kv.scores.sync`` nested in it, averaged over decode steps.
Moves ``itl_p95_ms``."""

from benchmarks.chip import engine_spans


def read(ctx):
    steps = engine_spans.self_s(ctx, "engine.decode.page_state",
                                r"^engine\.kv\.scores\.sync$")
    return 1e3 * sum(steps) / len(steps) if steps else None
