"""From a JAX profiler trace to the numbers the per-layer metrics read.

The trace is the ``.xplane.pb`` that ``jax.profiler.start_trace`` writes,
read with ``jax.profiler.ProfileData``. Three kinds of events are used:

* device operations — on a TPU the events of each ``/device:TPU:n``
  plane's "XLA Ops" line; where a trace has no device plane (the CPU
  backend, as in the tests) every host event that carries an ``hlo_op``
  statistic;
* programs — the "XLA Modules" line of each device plane, one event per
  execution of a compiled program (``jit__decode_fn`` and the like);
  without that line a program's time is the sum of its operations';
* host spans — ``jax.profiler.TraceAnnotation`` events whose names
  start with ``bench.``, written by the benchmark around the calls it
  makes into the program, on the same clock as the device events.

Busy time is the union of the intervals in which any operation runs on
a device, clipped to the ``bench.window`` span and averaged over the
devices; idle is the rest of the window. Idle time is charged to the
innermost benchmark span open on the host at each moment of it.
"""

from __future__ import annotations

import bisect
import collections
import dataclasses
import glob
import os
import re
import warnings

WINDOW_SPAN = "bench.window"


@dataclasses.dataclass
class Event:
    device: str
    name: str
    start: float          # ns
    end: float            # ns
    module: str = ""
    prog: int = -1        # index of the enclosing program execution
    self_ns: float = 0.0  # duration less that of the ops nested in it


@dataclasses.dataclass
class Trace:
    ops: list
    programs: list
    spans: list
    window: tuple

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    def devices(self) -> list[str]:
        return sorted({e.device for e in self.ops})

    def _clip(self, e) -> float:
        lo, hi = self.window
        return max(0.0, min(e.end, hi) - max(e.start, lo))

    def busy_intervals(self, device: str) -> list[tuple[float, float]]:
        lo, hi = self.window
        ivs = sorted((max(e.start, lo), min(e.end, hi)) for e in self.ops
                     if e.device == device and e.end > lo and e.start < hi)
        merged: list[list[float]] = []
        for s, t in ivs:
            if merged and s <= merged[-1][1]:
                merged[-1][1] = max(merged[-1][1], t)
            else:
                merged.append([s, t])
        return [(s, t) for s, t in merged]

    def busy_s(self) -> float:
        """Seconds with an operation running, averaged over devices."""
        devs = self.devices()
        if not devs:
            return 0.0
        tot = sum(t - s for d in devs for s, t in self.busy_intervals(d))
        return tot * 1e-9 / len(devs)

    def program_s(self, pattern: str, *, holding: str = "") -> float:
        """Device seconds of program executions whose name matches
        ``pattern``; with ``holding`` only executions that run an
        operation whose name matches it. Programs the program leaves
        unnamed (``jit__unknown``) are told apart so, by the kernels
        they run."""
        rx = re.compile(pattern)
        if not self.programs:
            if holding:
                return 0.0
            return 1e-9 * sum(self._clip(e) for e in self.ops
                              if rx.search(e.module))
        keep = range(len(self.programs))
        if holding:
            hit = {e.prog for e in self.ops if re.search(holding, e.name)}
            keep = [i for i in keep if i in hit]
        return 1e-9 * sum(self._clip(self.programs[i]) for i in keep
                          if rx.search(self.programs[i].name))

    def op_s(self, pattern: str) -> float:
        """Device seconds of operations whose name matches ``pattern``."""
        rx = re.compile(pattern)
        return 1e-9 * sum(self._clip(e) for e in self.ops
                          if rx.search(e.name))

    def top_ops(self, n: int = 10) -> list[list]:
        """The operations that took most device time of their own (a
        loop's time less its body's), by program and operation."""
        tot: collections.Counter = collections.Counter()
        lo, hi = self.window
        for e in self.ops:
            if e.start >= lo and e.end <= hi:
                tot[_label(e)] += e.self_ns
        k = max(1, len(self.devices()))
        return [[name, ns * 1e-9 / k] for name, ns in tot.most_common(n)]

    def idle_gaps(self, n: int = 10) -> list[list]:
        """Idle seconds per device, summed by the innermost benchmark span
        open on the host during each part of each gap (the window span
        itself where none is)."""
        lo, hi = self.window
        starts, names = _innermost(self.spans)
        tot: collections.Counter = collections.Counter()
        devs = self.devices() or ["none"]
        for d in devs:
            t = lo
            for s, e in self.busy_intervals(d) + [(hi, hi)]:
                if s > t:
                    _charge(tot, starts, names, t, s)
                t = max(t, e)
        return [[name, ns * 1e-9 / len(devs)]
                for name, ns in tot.most_common(n)]


def _charge(tot, starts, names, a: float, b: float) -> None:
    """Add the interval [a, b) to the names of the segments it covers."""
    i = bisect.bisect_right(starts, a) - 1
    while a < b:
        nxt = starts[i + 1] if i + 1 < len(starts) else b
        end = min(b, max(nxt, a))
        if end > a:
            tot[names[i] if i >= 0 else WINDOW_SPAN] += end - a
        a = end
        i += 1


def _label(e: Event) -> str:
    """``program:op kind shape`` from an op whose name is its HLO text,
    e.g. ``jit__unknown(31):%copy.139 copy bf16[16,2049,16,16,128]``."""
    m = re.match(r"(%?[\w.-]+) = (\S+?)(?:\{[^ ]*\})? ([\w-]+)\(", e.name)
    op = (f"{m.group(1)} {m.group(3)} {m.group(2)}" if m
          else e.name.split(" = ")[0][:120])
    if "tpu_custom_call" in e.name:
        op += " tpu_custom_call"
    return f"{e.module}:{op}" if e.module else op


def _self_times(ops: list) -> None:
    """Set each op's own time: its duration less that of the ops nested
    directly in it on the same device (a while loop holds its body)."""
    by_dev = collections.defaultdict(list)
    for e in ops:
        by_dev[e.device].append(e)
    for lst in by_dev.values():
        stack: list[Event] = []
        for e in sorted(lst, key=lambda e: (e.start, -e.end)):
            e.self_ns = e.end - e.start
            while stack and stack[-1].end <= e.start:
                stack.pop()
            if stack and e.end <= stack[-1].end:
                stack[-1].self_ns -= e.end - e.start
            stack.append(e)


def _innermost(spans: list) -> tuple[list, list]:
    """Cut properly nested host spans into consecutive segments, each
    named by the innermost span open in it: (segment starts, names)."""
    starts, names, stack = [], [], []

    def close():
        top = stack.pop()
        starts.append(top.end)
        names.append(stack[-1].name if stack else WINDOW_SPAN)

    for sp in sorted(spans, key=lambda s: (s.start, -s.end)):
        while stack and stack[-1].end <= sp.start:
            close()
        starts.append(sp.start)
        names.append(sp.name)
        stack.append(sp)
    while stack:
        close()
    return starts, names


def latest_xplane(log_dir: str) -> str:
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return max(paths, key=os.path.getmtime)


def _stat(event, key):
    with warnings.catch_warnings():     # the binding's stats type warns
        warnings.simplefilter("ignore", DeprecationWarning)
        for k, v in event.stats:
            if k == key:
                return v
    return None


def load(path: str) -> Trace:
    """Read one ``.xplane.pb`` into a ``Trace``."""
    import jax

    pd = jax.profiler.ProfileData.from_file(path)
    ops, programs, spans = [], [], []
    device_planes = [p for p in pd.planes
                     if p.name.startswith("/device:TPU:")
                     and "SparseCore" not in p.name]
    for plane in device_planes:
        for line in plane.lines:
            if line.name not in ("XLA Ops", "XLA Modules"):
                continue
            dst = ops if line.name == "XLA Ops" else programs
            for e in line.events:
                dst.append(Event(plane.name, e.name, e.start_ns,
                                 e.start_ns + e.duration_ns))
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("bench."):
                    spans.append(Event("host", e.name, e.start_ns,
                                       e.start_ns + e.duration_ns))
                elif not device_planes and e.duration_ns > 0:
                    op = _stat(e, "hlo_op")
                    if op is not None:
                        ops.append(Event("cpu", str(op), e.start_ns,
                                         e.start_ns + e.duration_ns,
                                         str(_stat(e, "hlo_module") or "")))
    _assign_modules(ops, programs)
    _self_times(ops)
    win = [s for s in spans if s.name == WINDOW_SPAN]
    if win:
        window = (win[0].start, win[0].end)
    elif ops:
        window = (min(e.start for e in ops), max(e.end for e in ops))
    else:
        window = (0.0, 0.0)
    return Trace(ops, programs, spans, window)


def _assign_modules(ops: list, programs: list) -> None:
    """Give each device operation the program execution that encloses it
    on the same device (its index and name)."""
    by_dev = collections.defaultdict(list)
    for i, p in enumerate(programs):
        by_dev[p.device].append(i)
    for lst in by_dev.values():
        lst.sort(key=lambda i: programs[i].start)
    idx = collections.defaultdict(int)
    for e in sorted(ops, key=lambda e: e.start):
        if e.module:
            continue
        lst = by_dev.get(e.device, [])
        j = idx[e.device]
        while j < len(lst) and programs[lst[j]].end < e.start:
            j += 1
        idx[e.device] = j
        if j < len(lst) and programs[lst[j]].start <= e.start:
            e.prog = lst[j]
            e.module = programs[lst[j]].name
