"""The readers of the program's own spans (``engine_spans`` and the
metrics it feeds): on spans built by hand, on a program that keeps none,
and through the harness at smoke size on the CPU."""

import time
import types

import pytest

from benchmarks.chip import harness
from benchmarks.chip.tests import smoke
from repro.obs import trace

READERS = ("tick_host_ms", "page_state_ms", "score_pulls_per_step")
UNITS = {"tick_host_ms": "ms", "page_state_ms": "ms",
         "score_pulls_per_step": "pulls"}

# two ticks of engine spans (name, start, end), in ms, on one thread;
# the window is [0, 1000] and the last tick lies past it
TICKS = [
    ("engine.tick", 10, 380),
    ("engine.phase.prefill", 30, 120),
    ("engine.prefill.sync", 80, 100),
    ("engine.phase.decode", 120, 370),
    ("engine.decode.step", 125, 360),
    ("engine.decode.page_state", 140, 195),
    ("engine.kv.scores.sync", 150, 170),
    ("engine.decode.dispatch", 205, 245),
    ("engine.decode.sync", 260, 340),
    ("engine.api.tick", 382, 395),
    ("engine.tick", 425, 880),
    ("engine.decode.step", 435, 860),
    ("engine.decode.page_state", 450, 495),
    ("engine.decode.sync", 610, 850),
    ("engine.tick", 990, 1100),
    ("engine.decode.step", 995, 1090),
    ("engine.kv.scores.sync", 1000, 1010),
]


def ctx(lo=0.0, hi=1.0):
    return types.SimpleNamespace(in_window=lambda t: lo <= t <= hi)


def read(name, c):
    return harness.find("metrics", name).read(c)


@pytest.fixture
def kept(monkeypatch):
    spans = [(n, a * 1e-3, b * 1e-3, 7) for n, a, b in TICKS]
    monkeypatch.setattr(trace, "profiled_spans", lambda: list(spans))
    return spans


def test_readers_on_spans_built_by_hand(kept):
    # ticks 370 - (20 + 20 + 80) and 455 - 240 ms
    assert read("tick_host_ms", ctx()) == pytest.approx((250 + 215) / 2)
    # page state 55 - 20 and 45 ms
    assert read("page_state_ms", ctx()) == pytest.approx(40.0)
    # one pull in the window over two steps
    assert read("score_pulls_per_step", ctx()) == pytest.approx(0.5)


def test_children_on_another_thread_are_not_taken_off(kept):
    kept.append(("engine.decode.sync", 0.5, 0.6, 8))
    assert read("tick_host_ms", ctx()) == pytest.approx((250 + 215) / 2)


def test_window_without_spans_reads_nothing(kept):
    assert all(read(m, ctx(2.0, 3.0)) is None for m in READERS)


def test_program_that_keeps_no_spans_reads_nothing(monkeypatch):
    monkeypatch.delattr(trace, "profiled_spans")
    assert all(read(m, ctx()) is None for m in READERS)


def test_traced_smoke_cell_reads_the_engine_spans():
    name = "smoke.closed"
    mets = ["itl_p95_ms", "decode_lanes_mean"]
    bench = smoke.bench(name, mets)
    bench["per_layer"] += [{"name": m, "unit": UNITS[m], "workloads": [name]}
                           for m in READERS]
    r = harness.run_cell(
        bench, smoke.cell(name), smoke.config(), smoke.closed_mix(),
        seed=2 ** 31 + 77, seconds=2.0, trace=True,
        t_start=time.perf_counter(), require_tpu=False,
        peak=smoke.STAND_IN_PEAK)
    assert r["correct"], r["checks"]
    m = r["metrics"]
    assert 0 < m["page_state_ms"]["value"] < m["tick_host_ms"]["value"]
    # the pool is kept full, so growing a page evicts by score
    assert m["score_pulls_per_step"]["value"] > 0
    assert {m[k]["unit"] for k in READERS} == {"ms", "pulls"}
