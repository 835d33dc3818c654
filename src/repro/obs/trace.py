"""Host-side tracing: engine spans for ticks, in two outputs at once.

Every span also enters a ``jax.profiler.TraceAnnotation`` named
``engine.<span name>`` (``tick`` -> ``engine.tick``), so whenever a JAX
profiler session is active the engine's spans land in its trace on the
device events' clock, with telemetry on or off. Span args ride into the
annotation only while a session is active; without one a span costs
well under a microsecond. While a session is active the spans are also
kept in memory, with their host times (``profiled_spans``), and so are the
per-dispatch counts the engine keeps (``keep_counts``,
``profiled_counts``).

A ``Tracer`` (telemetry on) also collects structured span ("X" complete)
and instant ("i") events in memory with microsecond timestamps relative
to construction. ``NullTracer`` (telemetry off) keeps nothing. No span
syncs the device, so tracing never perturbs the engine's dispatch.

Exports:

* ``Tracer.export_chrome(path)`` — a ``{"traceEvents": [...]}`` JSON
  Chrome/Perfetto loads directly (chrome://tracing, ui.perfetto.dev).
* ``Tracer.export_jsonl(path)`` — one event per line (streamable); a
  leading ``{"meta": ...}`` header line carries run metadata.
* ``load_trace(path)`` — round-trip loader for both formats.
* ``phase_summary(events)`` — the per-phase time table
  ``tools/trace_summary.py`` and the traced launchers print: per-tick
  ms in admit/prefill/decode/swap plus the host remainder (tick time
  not inside any phase span).
"""

from __future__ import annotations

import collections
import json
import threading
import time
from typing import Optional

from jax.profiler import TraceAnnotation

PREFIX = "engine."      # profiler annotations are named PREFIX + span name

# Every span entered while a profiler session is active, as (profiler
# name, start s, end s, thread id) on the ``time.perf_counter`` clock:
# the spans the session's trace holds, for readers that want them
# without parsing its file. Bounded; the newest are kept.
PROFILED: collections.deque = collections.deque(maxlen=1 << 17)


def profiled_spans() -> list[tuple[str, float, float, int]]:
    """The spans kept in ``PROFILED``, oldest first."""
    return list(PROFILED)


# Counts the program tallies per device dispatch while a profiler session
# is active, as (profiler-style name, host time s, {count: value}) on the
# same clock as ``PROFILED``, for readers that sum them over a window.
PROFILED_COUNTS: collections.deque = collections.deque(maxlen=1 << 17)


def profiling() -> bool:
    """Is a JAX profiler session active?"""
    return TraceAnnotation.is_enabled()


def keep_counts(name: str, **values) -> None:
    """Keep one dispatch's counts as ``engine.<name>`` while profiling."""
    if TraceAnnotation.is_enabled():
        PROFILED_COUNTS.append((PREFIX + name, time.perf_counter(), values))


def profiled_counts() -> list[tuple[str, float, dict]]:
    """The counts kept in ``PROFILED_COUNTS``, oldest first."""
    return list(PROFILED_COUNTS)


class _Annotation(TraceAnnotation):
    """The profiler annotation a span enters. ``args`` is a throwaway,
    so code that annotates a span mid-way works with either tracer."""

    @property
    def args(self) -> dict:
        return {}


class _Profiled(_Annotation):
    """A span entered while a profiler session is active: the annotation
    carries its args, and its host times go to ``PROFILED``."""

    def __init__(self, name: str, args: dict):
        super().__init__(name, **args)
        self._name = name

    def __enter__(self) -> "_Profiled":
        self._t0 = time.perf_counter()
        return super().__enter__()

    def __exit__(self, et, ev, tb):
        super().__exit__(et, ev, tb)
        PROFILED.append((self._name, self._t0, time.perf_counter(),
                         threading.get_ident()))
        return False


def _annotation(name: str, args: dict) -> _Annotation:
    if TraceAnnotation.is_enabled():            # a profiler session is on
        return _Profiled(PREFIX + name, args)
    return _Annotation(PREFIX + name)


class NullTracer:
    """Disabled tracer: keeps nothing; its spans reach only the profiler.

    ``enabled`` is the guard hot paths check before building event
    arguments; span()/instant() still exist so cold paths can skip the
    guard entirely."""

    __slots__ = ()
    enabled = False

    def span(self, name: str, tid: int = 0, **args) -> _Annotation:
        return _annotation(name, args)

    def instant(self, name: str, tid: int = 0, **args) -> None:
        return None

    def name_track(self, tid: int, name: str) -> None:
        return None

    def clear(self) -> None:
        return None

    @property
    def events(self) -> list:
        return []


NULL_TRACER = NullTracer()


class _Span:
    """One open span; emits a complete ("X") event when it exits, and is
    a profiler annotation meanwhile.

    ``args`` is mutable until exit, so callers can annotate outcomes
    discovered mid-span (pages freed, wave splits, ...); the annotation
    carries them as they were at entry."""

    __slots__ = ("_tr", "name", "tid", "args", "_t0", "_ann")

    def __init__(self, tracer: "Tracer", name: str, tid: int, args: dict):
        self._tr = tracer
        self.name = name
        self.tid = tid
        self.args = args

    def __enter__(self) -> "_Span":
        self._ann = _annotation(self.name, self.args)
        self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, et, ev, tb):
        t1 = time.perf_counter()
        self._ann.__exit__(et, ev, tb)
        self._tr._complete(self.name, self.tid, self._t0, t1, self.args)
        return False


class Tracer:
    """Collects trace events in memory; export when the run is over.

    Timestamps are ``time.perf_counter()`` relative to construction, in
    microseconds (the trace_event unit). ``tid`` maps to a Perfetto
    track — 0 is the engine tick track; backends may use shard ids."""

    enabled = True

    def __init__(self, meta: Optional[dict] = None):
        self.meta = dict(meta or {})
        self.events: list[dict] = []
        self._t0 = time.perf_counter()
        self._track_names: dict[int, str] = {}

    def _us(self, t: float) -> float:
        return round((t - self._t0) * 1e6, 3)

    # -- emission -----------------------------------------------------------

    def span(self, name: str, tid: int = 0, **args) -> _Span:
        """Open a span; use as a context manager."""
        return _Span(self, name, tid, args)

    def _complete(self, name: str, tid: int, t0: float, t1: float,
                  args: dict) -> None:
        ev = {"name": name, "ph": "X", "pid": 0, "tid": tid,
              "ts": self._us(t0), "dur": round((t1 - t0) * 1e6, 3)}
        if args:
            ev["args"] = args
        self.events.append(ev)

    def instant(self, name: str, tid: int = 0, **args) -> None:
        ev = {"name": name, "ph": "i", "s": "t", "pid": 0, "tid": tid,
              "ts": self._us(time.perf_counter())}
        if args:
            ev["args"] = args
        self.events.append(ev)

    def name_track(self, tid: int, name: str) -> None:
        self._track_names[tid] = name

    def clear(self) -> None:
        """Drop collected events (e.g. after a warmup pass). The time
        origin is kept so timestamps stay monotonic across clears."""
        self.events = []

    # -- export -------------------------------------------------------------

    def _metadata_events(self) -> list[dict]:
        out = [{"name": "process_name", "ph": "M", "pid": 0, "tid": 0,
                "args": {"name": self.meta.get("backend", "engine")}}]
        for tid, name in sorted(self._track_names.items()):
            out.append({"name": "thread_name", "ph": "M", "pid": 0,
                        "tid": tid, "args": {"name": name}})
        return out

    def chrome_trace(self) -> dict:
        """The Chrome/Perfetto ``trace_event`` JSON document."""
        return {"traceEvents": self._metadata_events() + self.events,
                "displayTimeUnit": "ms",
                "otherData": self.meta}

    def export_chrome(self, path: str) -> None:
        with open(path, "w") as f:
            json.dump(self.chrome_trace(), f)
            f.write("\n")

    def export_jsonl(self, path: str) -> None:
        with open(path, "w") as f:
            f.write(json.dumps({"meta": self.meta}) + "\n")
            for ev in self._metadata_events() + self.events:
                f.write(json.dumps(ev) + "\n")


def load_trace(path: str) -> list[dict]:
    """Load events back from either export format (round-trip)."""
    if path.endswith(".jsonl"):
        events = []
        with open(path) as f:
            for line in f:
                line = line.strip()
                if not line:
                    continue
                doc = json.loads(line)
                if "ph" in doc:
                    events.append(doc)
        return events
    with open(path) as f:
        doc = json.load(f)
    return doc["traceEvents"] if isinstance(doc, dict) else doc


# scheduler phase spans -> phase_summary buckets
_PHASES = {"phase.admit": "admit", "phase.prefill": "prefill",
           "phase.decode": "decode"}
# swap activity spans (nested INSIDE prefill/decode phases — reported as
# its own bucket but not subtracted from them)
_SWAP = {"preempt", "swap_in", "shed"}


def phase_summary(events: list[dict]) -> dict:
    """Where tick time goes: totals and per-tick ms by phase.

    ``host`` is the tick-span remainder outside every scheduler phase —
    bookkeeping, packing, python overhead. ``swap`` sums preempt /
    swap-in / shed spans (they nest inside prefill/decode phases, so
    swap + the three phases can exceed the tick total). ``compile_ms``
    sums spans flagged as first-call dispatches."""
    sums = {"admit": 0.0, "prefill": 0.0, "decode": 0.0, "swap": 0.0}
    counts = {"admit": 0, "prefill": 0, "decode": 0, "swap": 0}
    ticks = 0
    tick_ms = 0.0
    compile_ms = 0.0
    for ev in events:
        if ev.get("ph") != "X":
            continue
        name = ev.get("name")
        dur_ms = float(ev.get("dur", 0.0)) / 1e3
        if name == "tick":
            ticks += 1
            tick_ms += dur_ms
            continue
        key = _PHASES.get(name)
        if key is None and name in _SWAP:
            key = "swap"
        if key is not None:
            sums[key] += dur_ms
            counts[key] += 1
        if (ev.get("args") or {}).get("compile"):
            compile_ms += dur_ms
    host = max(0.0, tick_ms - sums["admit"] - sums["prefill"]
               - sums["decode"])
    totals = {k: round(v, 3) for k, v in sums.items()}
    totals["host"] = round(host, 3)
    n = max(ticks, 1)
    per_tick = {k: round(v / n, 4) for k, v in sums.items()}
    per_tick["host"] = round(host / n, 4)
    return {"ticks": ticks,
            "wall_ms": round(tick_ms, 3),
            "totals_ms": totals,
            "per_tick_ms": per_tick,
            "counts": counts,
            "compile_ms": round(compile_ms, 3)}


def format_table(summary: dict, title: str = "") -> str:
    """Render a ``phase_summary`` dict as the per-phase time table
    printed by ``tools/trace_summary.py`` and the traced launchers."""
    head = f"trace_summary{f'[{title}]' if title else ''}: " \
           f"{summary['ticks']} ticks, {summary['wall_ms']:.1f}ms wall, " \
           f"{summary['compile_ms']:.1f}ms in first-call dispatches"
    rows = [head,
            f"  {'phase':<10}{'total ms':>12}{'per-tick ms':>14}"
            f"{'spans':>8}"]
    counts = summary.get("counts", {})
    for key in ("admit", "prefill", "decode", "swap", "host"):
        rows.append(
            f"  {key:<10}{summary['totals_ms'][key]:>12.2f}"
            f"{summary['per_tick_ms'][key]:>14.4f}"
            f"{counts.get(key, ''):>8}")
    return "\n".join(rows)
