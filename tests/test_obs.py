"""Telemetry subsystem tests: tracer schema + round-trip, metrics
registry semantics, percentile math, request timelines, and the
engine-integration contracts from ISSUE 6 — trace spans nest with
monotonic timestamps, counters stay monotonic across preempt/shed
scenarios, spatial traces carry shard tags, and DISABLED telemetry costs
<5% on the conformance workload.

Pure-python tests (no jax) run first; the engine integration reuses the
pressured/shed scenario shapes from tests/engine_core_scenarios.py.
"""

import dataclasses
import json
import pathlib
import subprocess
import sys
import time

import numpy as np
import pytest

from repro import obs
from repro.obs import (NULL_TELEMETRY, MetricsRegistry, NullTracer,
                       RequestTimeline, Telemetry, Tracer, aggregate,
                       load_trace, percentile, phase_summary)

import engine_core_scenarios as scen

TOOLS = pathlib.Path(__file__).resolve().parent.parent / "tools"


# ---------------------------------------------------------------- tracer

class TestTracer:
    def test_spans_nest_with_monotonic_timestamps(self):
        tr = Tracer()
        with tr.span("tick", n=0):
            with tr.span("phase.prefill"):
                with tr.span("prefill.dispatch", wave=0):
                    pass
            with tr.span("phase.decode"):
                pass
        with tr.span("tick", n=1):
            pass
        # inner spans close (and are appended) before outer ones
        names = [e["name"] for e in tr.events]
        assert names == ["prefill.dispatch", "phase.prefill",
                         "phase.decode", "tick", "tick"]
        dispatch, prefill, decode, tick0, _ = tr.events
        # containment: child interval inside parent interval
        for c, p in ((dispatch, prefill), (prefill, tick0),
                     (decode, tick0)):
            assert p["ts"] <= c["ts"]
            assert c["ts"] + c["dur"] <= p["ts"] + p["dur"] + 1e-6, \
                (c["name"], p["name"])
        ticks = [e for e in tr.events if e["name"] == "tick"]
        assert ticks[0]["ts"] + ticks[0]["dur"] <= ticks[1]["ts"]
        assert ticks[0]["args"] == {"n": 0}

    def test_span_args_mutable_until_exit(self):
        tr = Tracer()
        with tr.span("prefill.pack") as sp:
            sp.args["waves"] = 3
        assert tr.events[0]["args"] == {"waves": 3}

    def test_instant_event_schema(self):
        tr = Tracer()
        tr.instant("need_pages", tid=2, slot=1, shard=0)
        (ev,) = tr.events
        assert ev["ph"] == "i" and ev["s"] == "t" and ev["tid"] == 2
        assert ev["args"] == {"slot": 1, "shard": 0}

    def test_chrome_round_trip(self, tmp_path):
        tr = Tracer({"backend": "paged"})
        tr.name_track(1, "shard 0")
        with tr.span("tick"):
            tr.instant("admit", rid=7)
        path = str(tmp_path / "t.json")
        tr.export_chrome(path)
        doc = json.load(open(path))
        assert doc["otherData"] == {"backend": "paged"}
        meta = [e for e in doc["traceEvents"] if e["ph"] == "M"]
        assert {"process_name", "thread_name"} <= {e["name"] for e in meta}
        events = load_trace(path)
        assert [e["name"] for e in events if e["ph"] == "X"] == ["tick"]
        assert [e["name"] for e in events if e["ph"] == "i"] == ["admit"]

    def test_jsonl_round_trip(self, tmp_path):
        tr = Tracer({"run": "x"})
        with tr.span("tick"):
            pass
        tr.instant("admit")
        path = str(tmp_path / "t.jsonl")
        tr.export_jsonl(path)
        header = json.loads(open(path).readline())
        assert header == {"meta": {"run": "x"}}
        events = load_trace(path)
        span_events = [e for e in events if e["ph"] == "X"]
        assert len(span_events) == 1
        assert span_events[0]["name"] == "tick"
        # both formats load to the same span set
        chrome = str(tmp_path / "t.json")
        tr.export_chrome(chrome)
        assert [e for e in load_trace(chrome) if e["ph"] == "X"] \
            == span_events

    def test_clear_keeps_time_origin(self):
        tr = Tracer()
        with tr.span("tick"):
            pass
        t_before = tr.events[0]["ts"]
        tr.clear()
        assert tr.events == []
        with tr.span("tick"):
            pass
        assert tr.events[0]["ts"] >= t_before

    def test_null_tracer_is_inert(self):
        tr = NullTracer()
        assert tr.enabled is False
        with tr.span("tick", n=1) as sp:
            sp.args["x"] = 1          # goes nowhere, raises nothing
            tr.instant("admit")
        tr.name_track(1, "x")
        tr.clear()
        assert tr.events == []


# --------------------------------------------------------------- metrics

class TestMetrics:
    def test_counter_labels_and_negative_inc(self):
        reg = MetricsRegistry()
        c = reg.counter("engine_sheds_total", "sheds")
        c.inc()
        c.inc(2, sla="batch")
        assert c.value() == 1
        assert c.value(sla="batch") == 2
        with pytest.raises(ValueError):
            c.inc(-1)

    def test_get_or_create_and_type_mismatch(self):
        reg = MetricsRegistry()
        a = reg.counter("x")
        assert reg.counter("x") is a
        assert reg.get("x") is a
        with pytest.raises(ValueError):
            reg.gauge("x")

    def test_histogram_buckets_cumulative(self):
        reg = MetricsRegistry()
        h = reg.histogram("ttft", buckets=(0.1, 1.0))
        for v in (0.05, 0.5, 5.0):
            h.observe(v)
        s = h.value()
        assert s["counts"] == [1, 1, 1] and s["count"] == 3
        text = reg.render_prometheus()
        assert 'ttft_bucket{le="0.1"} 1' in text
        assert 'ttft_bucket{le="1"} 2' in text
        assert 'ttft_bucket{le="+Inf"} 3' in text
        assert "ttft_count 3" in text

    def test_prometheus_exposition_format(self):
        reg = MetricsRegistry()
        reg.counter("reqs_total", "requests").inc(3, sla="interactive")
        reg.gauge("pool_live").set(7, shard=1)
        text = reg.render_prometheus()
        assert "# HELP reqs_total requests" in text
        assert "# TYPE reqs_total counter" in text
        assert 'reqs_total{sla="interactive"} 3' in text
        assert 'pool_live{shard="1"} 7' in text
        assert text.endswith("\n")

    def test_snapshot_shapes(self):
        reg = MetricsRegistry()
        reg.counter("plain").inc(4)
        reg.counter("labeled").inc(1, sla="a")
        snap = reg.snapshot()
        assert snap["plain"] == 4
        assert snap["labeled"] == {'sla="a"': 1}


# ------------------------------------------------------------ percentile

class TestPercentile:
    def test_matches_numpy_linear_interpolation(self):
        rng = np.random.default_rng(0)
        for n in (1, 2, 5, 100):
            xs = rng.normal(size=n).tolist()
            for q in (0, 25, 50, 90, 95, 99, 100):
                assert percentile(xs, q) == pytest.approx(
                    float(np.percentile(xs, q)), abs=1e-12), (n, q)

    def test_empty_returns_none(self):
        assert percentile([], 50) is None

    def test_fixes_old_nearest_rank_bias(self):
        # the pre-obs metrics() used sorted[len//2]: for [1, 2, 3, 4]
        # that returns 3; the true interpolated median is 2.5
        assert percentile([1, 2, 3, 4], 50) == 2.5


# -------------------------------------------------------------- timeline

class TestTimeline:
    def test_derived_latencies(self):
        tl = RequestTimeline(0, sla="interactive", submit_t=10.0)
        tl.admit_t = 10.5
        tl.first_token_t = 11.0
        tl.token_ts = [11.0, 11.2, 11.3]
        tl.done_t = 11.3
        tl.n_tokens = 3
        tl.outcome = "done"
        assert tl.ttft == pytest.approx(1.0)
        assert tl.latency == pytest.approx(1.3)
        assert tl.tpots == pytest.approx([0.2, 0.1])
        names = [n for n, _ in tl.epochs()]
        assert names == ["submit", "admit", "first_token", "done"]

    def test_preempt_resume_epochs_sorted(self):
        tl = RequestTimeline(1, submit_t=0.0)
        tl.admit_t = 1.0
        tl.preempt_ts = [2.0]
        tl.resume_ts = [3.0]
        tl.done_t = 4.0
        assert [n for n, _ in tl.epochs()] == \
            ["submit", "admit", "preempt", "resume", "done"]

    def test_aggregate_surface(self):
        tls = []
        for i in range(4):
            tl = RequestTimeline(i, sla="batch" if i % 2 else "rt",
                                 submit_t=float(i))
            tl.first_token_t = i + 0.5
            tl.token_ts = [i + 0.5, i + 0.6]
            tl.done_t = i + 1.0
            tl.n_tokens = 2
            tls.append(tl)
        tls[0].preempt_ts = [0.7]
        agg = aggregate(tls)
        assert agg["requests"] == 4 and agg["completed"] == 4
        assert agg["preempted_requests"] == 1
        assert agg["ttft_ms"]["p50"] == pytest.approx(500.0)
        assert set(agg["ttft_ms"]) == {"p50", "p95", "p99", "mean"}
        assert set(agg["per_sla"]) == {"batch", "rt"}
        assert agg["per_sla"]["rt"]["goodput_tok_s"] is not None


# --------------------------------------------------------- phase summary

def test_phase_summary_buckets():
    events = [
        {"name": "tick", "ph": "X", "ts": 0, "dur": 10_000, "tid": 0},
        {"name": "phase.admit", "ph": "X", "ts": 0, "dur": 1_000,
         "tid": 0},
        {"name": "phase.prefill", "ph": "X", "ts": 1_000, "dur": 4_000,
         "tid": 0, "args": {}},
        {"name": "prefill.dispatch", "ph": "X", "ts": 1_500,
         "dur": 3_000, "tid": 0, "args": {"compile": True}},
        {"name": "phase.decode", "ph": "X", "ts": 5_000, "dur": 3_000,
         "tid": 0},
        {"name": "preempt", "ph": "X", "ts": 5_500, "dur": 500, "tid": 0},
        {"name": "admit", "ph": "i", "ts": 100, "tid": 0},
    ]
    s = phase_summary(events)
    assert s["ticks"] == 1 and s["wall_ms"] == 10.0
    assert s["totals_ms"]["admit"] == 1.0
    assert s["totals_ms"]["prefill"] == 4.0
    assert s["totals_ms"]["decode"] == 3.0
    assert s["totals_ms"]["swap"] == 0.5
    # host = tick - (admit + prefill + decode); swap nests inside phases
    assert s["totals_ms"]["host"] == pytest.approx(2.0)
    assert s["compile_ms"] == 3.0
    assert s["counts"]["swap"] == 1


# ------------------------------------------------------------- telemetry

class TestTelemetry:
    def test_timeline_get_or_create_backfills(self):
        tel = Telemetry()
        a = tel.timeline(3)
        # engine-first sight defaults submit_t to "now" so TTFT is never
        # None; a later lookup backfills the sla but keeps that stamp
        assert a.submit_t is not None
        b = tel.timeline(3, sla="rt", submit_t=1.0)
        assert a is b and b.sla == "rt" and b.submit_t == a.submit_t

    def test_null_telemetry_is_inert(self):
        assert NULL_TELEMETRY.enabled is False
        tl = NULL_TELEMETRY.timeline(5)
        tl.admit_t = 1.0                        # throwaway object
        assert NULL_TELEMETRY.timeline(5) is not tl
        assert NULL_TELEMETRY.tracer.events == []


# --------------------------------------------------- engine integration

@pytest.fixture(scope="module")
def smoke_lm():
    import jax

    from repro.configs import get_smoke_config
    from repro.models import lm
    cfg = dataclasses.replace(get_smoke_config("olmo_1b"), star=None)
    params = lm.init(jax.random.PRNGKey(1), cfg)
    return cfg, params


def _paged_llm(cfg, params, *, pages, hot, scfg, telemetry,
               max_batch=2, recent=2):
    from repro.serving import LLM, PagedEngineCfg, PagedServingEngine
    return LLM(PagedServingEngine(cfg, params, PagedEngineCfg(
        max_batch=max_batch, page_size=16, n_pages=pages, hot_pages=hot,
        recent_pages=recent, eos_id=-1), scfg), telemetry=telemetry)


def _tick_all(llm, prompts, max_tokens=5, max_steps=4000):
    """Submit + drive tick-by-tick, returning per-tick registry
    snapshots (for monotonicity checks)."""
    for i, p in enumerate(prompts):
        llm.submit(p, max_tokens=max_tokens, rid=i)
    snaps = []
    steps = 0
    while llm.has_work() and steps < max_steps:
        llm.tick()
        snaps.append(llm.tel.metrics.snapshot())
        steps += 1
    assert not llm.has_work(), "pressured run did not drain"
    return snaps


def _flatten_counters(snap):
    out = {}
    for name, v in snap.items():
        if not name.endswith("_total"):
            continue
        if isinstance(v, dict):
            for label, val in v.items():
                out[f"{name}{{{label}}}"] = val
        else:
            out[name] = v
    return out


class TestEngineIntegration:
    @pytest.fixture(scope="class")
    def pressured(self, smoke_lm):
        """One pressured paged run (preempt/swap churn) with telemetry:
        the trace + per-tick counter snapshots every check below reads."""
        from repro.serving import SchedulerCfg
        cfg, params = smoke_lm
        tel = Telemetry({"backend": "paged"})
        llm = _paged_llm(
            cfg, params, max_batch=4,
            pages=scen.BACKEND_PARAMS["paged"]["pressure_pages"], hot=4,
            scfg=SchedulerCfg(chunk_pages=1, prefill_tokens=64,
                              swap=True),
            telemetry=tel)
        snaps = _tick_all(llm, scen._prompts(cfg, scen.PRESSURE_LENGTHS),
                          max_tokens=20)
        return llm, tel, snaps

    def test_trace_schema_and_nesting(self, pressured):
        _, tel, _ = pressured
        events = tel.tracer.events
        spans = [e for e in events if e["ph"] == "X"]
        assert spans, "no spans traced"
        for e in spans:
            assert {"name", "ph", "ts", "dur", "pid", "tid"} <= set(e), e
            assert e["dur"] >= 0
        # spans on one track must nest: sort by (start, -end); every
        # span either contains or is disjoint from its successor
        for tid in {e["tid"] for e in spans}:
            track = sorted((e for e in spans if e["tid"] == tid),
                           key=lambda e: (e["ts"], -(e["ts"] + e["dur"])))
            stack = []
            for e in track:
                end = e["ts"] + e["dur"]
                while stack and e["ts"] >= stack[-1] - 1e-6:
                    stack.pop()
                if stack:
                    assert end <= stack[-1] + 1e-6, \
                        f"span {e['name']} crosses its parent boundary"
                stack.append(end)
        ticks = [e for e in spans if e["name"] == "tick"]
        ts = [e["ts"] for e in ticks]
        assert ts == sorted(ts) and len(ticks) > 1
        # the pressured run must show swap activity in the trace
        names = {e["name"] for e in events}
        assert {"phase.admit", "phase.prefill", "phase.decode",
                "preempt", "swap_out", "swap_in", "admit"} <= names, names

    def test_counters_monotonic_per_tick(self, pressured):
        _, _, snaps = pressured
        prev = {}
        for i, snap in enumerate(snaps):
            cur = _flatten_counters(snap)
            for key, val in prev.items():
                assert cur.get(key, 0) >= val, \
                    f"counter {key} decreased at tick {i}"
            prev = cur

    def test_final_counters_match_sched_stats(self, pressured):
        llm, tel, _ = pressured
        st = llm.stats()["sched"]
        assert st.preemptions > 0, "workload was not pressured"
        reg = tel.metrics
        assert reg.get("engine_preemptions_total").value() \
            == st.preemptions
        assert reg.get("engine_swap_outs_total").value() == st.swap_outs
        assert reg.get("engine_resumes_total").value() == st.resumes
        assert reg.get("engine_pages_swapped_total").value(
            dir="out", kind="preempt") > 0
        assert reg.get("engine_requests_finished_total") is not None
        n_req = len(scen.PRESSURE_LENGTHS)
        snap = reg.get("engine_requests_finished_total").snapshot()
        total = snap if isinstance(snap, (int, float)) \
            else sum(snap.values())
        assert total == n_req

    def test_request_timelines_stamped(self, pressured):
        llm, _, _ = pressured
        recs = list(llm.records.values())
        assert all(r.done_t is not None and r.outcome == "done"
                   for r in recs)
        assert all(r.admit_t is not None and r.ttft is not None
                   for r in recs)
        preempted = [r for r in recs if r.preempt_ts]
        assert preempted, "no request recorded a preemption epoch"
        for r in preempted:
            assert len(r.resume_ts) == len(r.preempt_ts)
        m = llm.metrics()
        for key in ("ttft_p50_ms", "ttft_p95_ms", "ttft_p99_ms",
                    "tpot_p50_ms"):
            assert m[key] is not None and m[key] > 0
        agg = llm.tel.aggregate()
        assert agg["completed"] == len(recs)
        assert agg["preempted_requests"] == len(preempted)

    def test_shed_counters(self, smoke_lm):
        from repro.serving import SchedulerCfg
        cfg, params = smoke_lm
        p = scen.BACKEND_PARAMS["paged"]["shed"]
        tel = Telemetry()
        llm = _paged_llm(cfg, params, pages=p["pages"], hot=p["hot"],
                         scfg=SchedulerCfg(chunk_pages=1, swap=True,
                                           lazy_swap=True),
                         telemetry=tel)
        for i in range(2):
            llm.submit((np.arange(p["prompt_len"], dtype=np.int32) + i)
                       % cfg.vocab, max_tokens=p["gen"], rid=i)
        done = llm.run_until_done(max_steps=8000)
        assert all(len(v) == p["gen"] for v in done.values())
        st = llm.stats()["sched"]
        assert st.sheds > 0 and st.preemptions == 0
        assert tel.metrics.get("engine_sheds_total").value() == st.sheds
        assert tel.metrics.get("engine_pages_swapped_total").value(
            dir="out", kind="shed") > 0
        assert tel.metrics.get("engine_preemptions_total") is None

    def test_disabled_telemetry_overhead_under_5pct(self, smoke_lm):
        """The acceptance bound: serving with the default NULL telemetry
        must not run measurably slower than... anything. We compare it
        against the ENABLED path on identical warmed engines: disabled
        must come in at or under 1.05x the enabled wall time (on a quiet
        host it is strictly faster; the margin absorbs CPU noise)."""
        from repro.serving import SchedulerCfg
        cfg, params = smoke_lm

        def build(telemetry):
            return _paged_llm(
                cfg, params, pages=24, hot=4,
                scfg=SchedulerCfg(chunk_pages=1, prefill_tokens=48),
                telemetry=telemetry)

        def run_pass(llm, rid0):
            for i, l in enumerate(scen.MIXED_LENGTHS):
                llm.submit((np.arange(l, dtype=np.int32) + rid0)
                           % cfg.vocab, max_tokens=8, rid=rid0 + i)
            t0 = time.perf_counter()
            llm.run_until_done(max_steps=8000)
            dt = time.perf_counter() - t0
            llm.clear_finished()
            return dt

        llm_off = build(None)
        llm_on = build(Telemetry())
        run_pass(llm_off, 0)          # warmup: compiles
        run_pass(llm_on, 0)
        assert llm_off.tel is NULL_TELEMETRY
        # interleave the two engines and alternate which goes first, so
        # host drift during the run lands on both sides alike
        offs, ons = [], []
        for k in range(5):
            order = ((offs, llm_off, 100), (ons, llm_on, 1000))
            for times, llm, base in order[::1 if k % 2 == 0 else -1]:
                times.append(run_pass(llm, base * (k + 1)))
        best_off, best_on = min(offs), min(ons)
        llm_on.tel.tracer.clear()
        assert best_off <= 1.05 * best_on, \
            f"disabled telemetry slower than enabled: " \
            f"{best_off:.4f}s vs {best_on:.4f}s"


# ------------------------------------------------------- spatial + tools

def test_spatial_trace_shard_tags(tmp_path):
    """2-shard fake-device run (subprocess): the exported trace must be
    loadable and carry shard-tagged events."""
    trace_path = str(tmp_path / "spatial_trace.json")
    out = subprocess.run(
        [sys.executable, str(TOOLS / "smoke_spatial_prog.py"),
         "--trace", trace_path],
        capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, \
        f"spatial trace prog failed:\n{out.stdout}\n{out.stderr[-2000:]}"
    assert "SPATIAL_TRACE_OK" in out.stdout
    events = load_trace(trace_path)
    shards = {(e.get("args") or {}).get("shard") for e in events}
    assert {0, 1} <= shards, f"expected both shard tags, got {shards}"
    ticks = [e["ts"] for e in events if e.get("name") == "tick"]
    assert ticks == sorted(ticks) and ticks


def test_trace_summary_tool(tmp_path, capsys):
    tr = Tracer()
    with tr.span("tick"):
        with tr.span("phase.decode"):
            pass
    path = str(tmp_path / "t.jsonl")
    tr.export_jsonl(path)
    sys.path.insert(0, str(TOOLS))
    try:
        import trace_summary
    finally:
        sys.path.pop(0)
    assert trace_summary.main([path]) == 0
    out = capsys.readouterr().out
    assert "1 ticks" in out and "decode" in out
    assert trace_summary.main([]) == 2
