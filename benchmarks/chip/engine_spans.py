"""The program's own spans that fall in the traced window, for the
per-layer readers of the engine and the KV cache.

The program names its spans ``engine.<name>`` in the profiler trace and,
while a profiler session is active, keeps a copy of each with its host
times on the ``time.perf_counter`` clock (``repro.obs.profiled_spans``),
the clock the window is stamped on. A program that keeps no such copy
yields no spans, and the readers leave their metrics out.
"""

from __future__ import annotations

import re


def spans(ctx) -> list[tuple[str, float, float, int]]:
    """(name, start s, end s, thread) of each engine span that lies
    inside the window."""
    from repro.obs import trace
    kept = getattr(trace, "profiled_spans", None)
    if kept is None:
        return []
    return [s for s in kept()
            if ctx.in_window(s[1]) and ctx.in_window(s[2])]


def count(ctx, name: str) -> int:
    """Spans named ``name`` in the window."""
    return sum(1 for s in spans(ctx) if s[0] == name)


def self_s(ctx, name: str, less: str) -> list[float]:
    """Seconds of each span named ``name`` in the window, less the time
    of the spans on its thread nested in it whose names match ``less``."""
    rx = re.compile(less)
    window = spans(ctx)
    inner = [s for s in window if rx.search(s[0])]
    out = []
    for _, a, b, tid in (s for s in window if s[0] == name):
        out.append(b - a - sum(e - s for _, s, e, t in inner
                               if t == tid and a <= s and e <= b))
    return out
