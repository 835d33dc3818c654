"""Sparsity-efficiency observability tests (ISSUE 8): the per-tick KV
accounting conservation invariant under pressure, exact audit recall at
the unbounded hot width, the refcount watchdog catching an injected
leak, the debug bundle surface, the ``--accounting`` table, and the
bench regression gate's pass/fail behaviour.

Pure-python gate/table tests run without jax; the engine tests reuse the
pressured scenario shapes from tests/engine_core_scenarios.py.
"""

import dataclasses
import json
import pathlib
import sys

import numpy as np
import pytest

from repro.obs import (AuditCfg, DlzsAuditor, Telemetry,
                       conservation_error, reconcile_refs)

import engine_core_scenarios as scen

REPO = pathlib.Path(__file__).resolve().parent.parent
TOOLS = REPO / "tools"


def _tool(name):
    sys.path.insert(0, str(TOOLS))
    try:
        return __import__(name)
    finally:
        sys.path.pop(0)


@pytest.fixture(scope="module")
def smoke_lm():
    import jax

    from repro.configs import get_smoke_config
    from repro.models import lm
    cfg = dataclasses.replace(get_smoke_config("olmo_1b"), star=None)
    params = lm.init(jax.random.PRNGKey(1), cfg)
    return cfg, params


def _paged_llm(cfg, params, *, pages, hot, scfg, telemetry,
               max_batch=4, recent=2):
    from repro.serving import LLM, PagedEngineCfg, PagedServingEngine
    return LLM(PagedServingEngine(cfg, params, PagedEngineCfg(
        max_batch=max_batch, page_size=16, n_pages=pages, hot_pages=hot,
        recent_pages=recent, eos_id=-1), scfg), telemetry=telemetry)


# ----------------------------------------------------- conservation

@pytest.fixture(scope="module")
def pressured_snaps(smoke_lm):
    """Drive the preempt/swap pressure scenario collecting the engine's
    accounting snapshot after EVERY tick."""
    from repro.serving import SchedulerCfg
    cfg, params = smoke_lm
    llm = _paged_llm(
        cfg, params,
        pages=scen.BACKEND_PARAMS["paged"]["pressure_pages"], hot=4,
        scfg=SchedulerCfg(chunk_pages=1, prefill_tokens=64, swap=True),
        telemetry=Telemetry({"backend": "paged"}))
    for i, p in enumerate(scen._prompts(cfg, scen.PRESSURE_LENGTHS)):
        llm.submit(p, max_tokens=20, rid=i)
    snaps = []
    steps = 0
    while llm.has_work() and steps < 4000:
        llm.tick()
        snaps.append(llm.engine.accounting_snapshot())
        steps += 1
    assert not llm.has_work(), "pressured run did not drain"
    return llm, snaps


class TestConservation:
    def test_every_tick_conserves_pages(self, pressured_snaps):
        """allocated == hot + cold + shed + swapped at every tick of a
        run that preempts and swaps — no page class double-counts or
        leaks through any scheduler decision."""
        _, snaps = pressured_snaps
        for snap in snaps:
            assert conservation_error(snap) == 0, snap["pages"]

    def test_scenario_actually_pressures(self, pressured_snaps):
        llm, snaps = pressured_snaps
        assert llm.stats()["sched"].preemptions > 0
        assert any(s["pages"]["swapped"] > 0 for s in snaps), \
            "pressure scenario never parked pages off-device"

    def test_fragmentation_bounded(self, pressured_snaps):
        _, snaps = pressured_snaps
        for snap in snaps:
            frac = snap["fragmentation"]["frac"]
            assert 0.0 <= frac <= 1.0
            assert snap["fragmentation"]["token_slack"] <= \
                snap["fragmentation"]["token_capacity"] or \
                snap["fragmentation"]["token_capacity"] == 0

    def test_watchdog_clean_on_healthy_run(self, pressured_snaps):
        llm, _ = pressured_snaps
        snap = llm.tel.metrics.snapshot()
        assert "engine_watchdog_violations_total" not in snap

    def test_accounting_folds_into_registry(self, pressured_snaps):
        llm, snaps = pressured_snaps
        snap = llm.tel.metrics.snapshot()
        states = snap["engine_kv_pages"]
        assert {'state="allocated"', 'state="hot"', 'state="cold"',
                'state="shed"', 'state="swapped"'} <= set(states)
        assert snap["engine_kv_conservation_error"] == 0


# ------------------------------------------------------------ audit

def test_audit_recall_exact_when_unbounded(smoke_lm):
    """With ``decode_hot_width=None`` the gather covers every resident
    page, so the audited attention-mass recall of the 'hot set' must be
    exactly 1.0 on every probe — the auditor's calibration check."""
    from repro.serving import SchedulerCfg
    cfg, params = smoke_lm
    llm = _paged_llm(cfg, params, pages=24, hot=4,
                     scfg=SchedulerCfg(chunk_pages=1, prefill_tokens=16),
                     telemetry=Telemetry())
    eng = llm.engine
    eng.auditor = DlzsAuditor(AuditCfg(every_ticks=2))
    for i, l in enumerate((24, 40, 33)):
        llm.submit((np.arange(l, dtype=np.int32) + i) % cfg.vocab,
                   max_tokens=12, rid=i)
    llm.run_until_done(max_steps=4000)
    assert eng.auditor.runs >= 3, \
        f"auditor barely ran: {eng.auditor.runs} runs, " \
        f"{eng.auditor.skipped} skipped"
    for entry in eng.auditor.reports:
        assert entry["recall_min"] == pytest.approx(1.0, abs=1e-5), entry
        assert entry["pages_hot"] == entry["pages_resident"]
    snap = llm.tel.metrics.snapshot()
    assert snap["engine_audit_recall"]['stat="min"'] == \
        pytest.approx(1.0, abs=1e-5)


def test_audit_disabled_is_inert(smoke_lm):
    from repro.serving import SchedulerCfg
    cfg, params = smoke_lm
    llm = _paged_llm(cfg, params, pages=24, hot=4,
                     scfg=SchedulerCfg(chunk_pages=1, prefill_tokens=16),
                     telemetry=Telemetry())
    llm.engine.auditor = DlzsAuditor(AuditCfg(every_ticks=0))
    llm.submit(np.arange(20, dtype=np.int32) % cfg.vocab,
               max_tokens=8, rid=0)
    llm.run_until_done(max_steps=2000)
    assert llm.engine.auditor.runs == 0
    assert "engine_audit_runs_total" not in llm.tel.metrics.snapshot()


# --------------------------------------------------------- watchdog

def test_watchdog_catches_injected_refcount_leak(smoke_lm):
    """Bump a live page's refcount behind the engine's back: the next
    tick's reconciliation must flag it and bump the violation counter
    (and a healthy engine must reconcile clean right before)."""
    from repro.serving import SchedulerCfg
    cfg, params = smoke_lm
    llm = _paged_llm(cfg, params, pages=24, hot=4,
                     scfg=SchedulerCfg(chunk_pages=1, prefill_tokens=16),
                     telemetry=Telemetry())
    eng = llm.engine
    for i in range(2):
        llm.submit((np.arange(40, dtype=np.int32) + i) % cfg.vocab,
                   max_tokens=64, rid=i)
    for _ in range(6):                       # get pages on the books
        llm.tick()
    assert eng.active, "requests finished before the leak injection"
    wd = reconcile_refs(eng._expected_refs(), eng.backend.pool_refs())
    assert wd.ok, wd.describe()

    (_, pid), _ = next(iter(eng.backend.pool_refs().items()))
    eng.backend.pool.incref(pid)             # the leak
    wd = reconcile_refs(eng._expected_refs(), eng.backend.pool_refs())
    assert not wd.ok and wd.violations >= 1
    assert str(pid) in wd.describe()

    llm.tick()                               # engine-side detection
    snap = llm.tel.metrics.snapshot()
    assert snap["engine_watchdog_violations_total"] >= 1
    events = [e for e in llm.tel.recorder.events()
              if e["kind"] == "watchdog"]
    assert events and events[-1]["violations"] >= 1


# ------------------------------------------- debug bundle + table

def test_debug_bundle_and_accounting_table(pressured_snaps, tmp_path,
                                           capsys):
    llm, _ = pressured_snaps
    out = llm.debug_bundle(str(tmp_path / "bundle"))
    names = {p.name for p in pathlib.Path(out).iterdir()}
    assert {"recorder.jsonl", "trace.json", "metrics.json",
            "metrics.prom", "accounting.json", "audit.json",
            "timelines.json", "config.json"} <= names
    acct = json.loads((pathlib.Path(out) / "accounting.json").read_text())
    assert conservation_error(acct) == 0
    recorder_kinds = {json.loads(line)["kind"] for line in
                      (pathlib.Path(out) / "recorder.jsonl")
                      .read_text().splitlines()}
    assert "admit" in recorder_kinds
    assert {"preempt", "swap_in"} & recorder_kinds, recorder_kinds

    trace_summary = _tool("trace_summary")
    assert trace_summary.main(["--accounting", out]) == 0
    table = capsys.readouterr().out
    assert "pages by state" in table
    assert "conservation err : 0" in table
    assert "swap traffic" in table and "out:" in table
    assert trace_summary.main(["--accounting"]) == 2


# ------------------------------------------------------- bench gate

BASE = {
    "schema": "bench-serving/v1",
    "decode_sparse": {
        "dense": {"decode_tok_s": 1000.0, "hot_width": 24,
                  "pages_skipped_frac": 0.0},
        "width_16": {"agreement": 1.0, "decode_tok_s": 1100.0,
                     "decode_speedup_vs_dense": 1.1, "hot_width": 16},
        "page_rich": {"pages_skipped_frac": 0.22,
                      "bytes_not_gathered": 9000000},
    },
    "engine_core": {"decode_compiles": 1, "requests": 6,
                    "preemptions": 2, "wall_s": 3.2},
}


class TestBenchGate:
    def test_identical_passes(self):
        gate = _tool("bench_gate")
        v = gate.diff(BASE, json.loads(json.dumps(BASE)))
        assert v["verdict"] == "pass" and not v["failures"]
        assert v["checked"] > 0

    def test_committed_baseline_self_diff_passes(self):
        gate = _tool("bench_gate")
        doc = json.loads((REPO / "BENCH_serving.json").read_text())
        v = gate.diff(doc, doc)
        assert v["verdict"] == "pass", v["failures"]

    def test_injected_regressions_fail(self):
        gate = _tool("bench_gate")
        fresh = json.loads(json.dumps(BASE))
        fresh["decode_sparse"]["width_16"]["agreement"] = 0.5   # tight
        fresh["engine_core"]["decode_compiles"] = 2             # strict
        fresh["decode_sparse"]["dense"]["decode_tok_s"] = 100.0  # timing
        v = gate.diff(BASE, fresh)
        assert v["verdict"] == "fail"
        joined = "\n".join(v["failures"])
        assert "agreement" in joined and "decode_compiles" in joined
        assert "decode_tok_s" in joined

    def test_tolerated_drift_passes_with_warnings(self):
        gate = _tool("bench_gate")
        fresh = json.loads(json.dumps(BASE))
        fresh["engine_core"]["preemptions"] = 4      # count band (abs 3)
        fresh["decode_sparse"]["dense"]["decode_tok_s"] = 700.0  # <2x
        fresh["engine_core"]["wall_s"] = 99.0        # skip tier
        v = gate.diff(BASE, fresh)
        assert v["verdict"] == "pass", v["failures"]

    def test_one_sided_keys_skip_instead_of_fail(self):
        """A leaf present on only one side — a suite scoped out of the
        fresh run, or a new metric not yet baselined — is a SKIP-tier
        verdict entry, never a failure: adding a bench entry must not
        break the gate in the PR that introduces it."""
        gate = _tool("bench_gate")
        fresh = json.loads(json.dumps(BASE))
        del fresh["decode_sparse"]["page_rich"]          # baseline-only
        fresh["engine_core"]["new_metric_frac"] = 0.5    # fresh-only
        fresh["robustness"] = {"goodput_tok_s": 12.0}    # new suite
        v = gate.diff(BASE, fresh)
        assert v["verdict"] == "pass", v["failures"]
        joined = "\n".join(v["skips"])
        assert "page_rich" in joined and "new_metric_frac" in joined
        assert "robustness" in joined
        # skipped leaves are not counted as checked
        assert v["checked"] == len(gate.leaves(BASE)) - 2

    def test_cli_exit_codes(self, tmp_path):
        gate = _tool("bench_gate")
        base = tmp_path / "base.json"
        base.write_text(json.dumps(BASE))
        same = tmp_path / "same.json"
        same.write_text(json.dumps(BASE))
        verdict = tmp_path / "verdict.json"
        assert gate.main(["--baseline", str(base), "--fresh", str(same),
                          "--out", str(verdict)]) == 0
        assert json.loads(verdict.read_text())["verdict"] == "pass"
        bad = json.loads(json.dumps(BASE))
        bad["engine_core"]["requests"] = 7
        badf = tmp_path / "bad.json"
        badf.write_text(json.dumps(bad))
        assert gate.main(["--baseline", str(base), "--fresh", str(badf),
                          "--out", str(verdict)]) == 1
        assert json.loads(verdict.read_text())["verdict"] == "fail"
        assert gate.main(["--baseline", str(base)]) == 2
