"""Serving smoke for CI: every backend end-to-end through the unified
``LLM`` front door on a tiny LM.

Run:  PYTHONPATH=src python tools/smoke_serve.py
      PYTHONPATH=src python tools/smoke_serve.py --trace [DIR]

Scenarios (~30s each on CPU):

1. Basic: a small mixed-length batch through dense AND paged backends
   via ``LLM`` — every request completes with valid tokens, variable-
   length admission compiled decode exactly once, prefix sharing kicked
   in, metrics() reports the run.
2. Overload: queued demand ~4x pool capacity (benchmarks.serving.overload)
   — the chunked-prefill + preemption scheduler must finish every request
   with ZERO rejections, swapping under pressure. Refreshes the
   ``overload`` entry of BENCH_serving.json.
3. EngineCore front door (benchmarks.serving.engine_core): a mixed
   long/short workload through ``LLM`` must hold prefill + decode
   throughput within 5% of the directly-driven engine, and the
   ``prefill_tokens="auto"`` budget controller must match or beat the
   fixed-budget short-TTFT p50. Refreshes the ``engine_core`` entry of
   BENCH_serving.json.
4. Spatial: the sequence-sharded backend on a 2-shard fake-device mesh
   in a subprocess (tools/smoke_spatial_prog.py): front-door parity with
   the paged backend, the ultra-long admit, lazy cold-page shedding on
   the sharded pools, and front-door throughput within 5% of the direct
   engine (merged into the ``engine_core`` entry).

``--trace [DIR]`` runs ONLY the telemetry smoke instead: a small traced
workload per backend (dense + paged in-process, spatial in a 2-shard
subprocess), each exported as a Perfetto-loadable Chrome trace into DIR
(default: a temp dir) and summarized with tools/trace_summary.py.

``--bundle DIR`` runs ONLY a pressured paged workload with the audit
sampler on and dumps ``LLM.debug_bundle()`` into DIR — CI uploads this
as the failure artifact of the bench-gate job.

Exits non-zero on any failure.
"""

from __future__ import annotations

import argparse
import dataclasses
import pathlib
import re
import subprocess
import sys
import tempfile
import time

import jax
import numpy as np

REPO = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))          # for the benchmarks package
sys.path.insert(0, str(REPO / "tools"))  # for trace_summary

from repro.configs import get_smoke_config
from repro.models import lm
from repro.serving import LLM, PagedEngineCfg, PagedServingEngine  # noqa: F401


def basic(cfg, params) -> bool:
    t0 = time.time()
    llm = LLM.from_config(cfg, backend="paged", params=params,
                          engine_cfg=PagedEngineCfg(
                              max_batch=2, page_size=16, n_pages=24,
                              hot_pages=3, eos_id=-1))
    system = np.arange(16, dtype=np.int32)          # one shared full page
    for i in range(5):
        llm.submit(np.concatenate(
            [system, np.arange(2 + 3 * i, dtype=np.int32) + i]),
            max_tokens=4, rid=i)
    done = llm.run_until_done()

    st = llm.stats()
    m = llm.metrics()
    # the dense backend answers through the same front door
    dense = LLM.from_config(cfg, backend="dense", params=params)
    d = dense.submit(np.arange(12, dtype=np.int32), max_tokens=4).result()
    ok = (set(done) == {0, 1, 2, 3, 4}
          and all(len(v) == 4 for v in done.values())
          and all(0 <= t < cfg.vocab for v in done.values() for t in v)
          and st["decode_compiles"] == 1
          and st["pool"].shared_hits >= 4
          and m["requests"] == 5 and m["tokens"] == 20
          and len(d) == 4)
    dt = time.time() - t0
    print(f"smoke_serve[basic]: {len(done)} requests, "
          f"{sum(len(v) for v in done.values())} tokens via LLM, "
          f"{st['pool'].peak_live} peak pages, "
          f"{st['pool'].shared_hits} prefix hits, "
          f"{st['decode_compiles']} decode compile(s), "
          f"dense={len(d)} tokens, {dt:.1f}s "
          f"-> {'PASS' if ok else 'FAIL'}")
    return ok


def overload(cfg, params) -> bool:
    from benchmarks import serving as bench_serving
    t0 = time.time()
    try:
        m = bench_serving.overload(cfg, params, oversubscribe=4)
    except AssertionError as e:
        print(f"smoke_serve[overload]: FAIL ({e})")
        return False
    ok = (m["rejected"] == 0 and m["preemptions"] > 0
          and m["swap_ins"] == m["swap_outs"])
    if ok:      # never let a failing run overwrite the committed baseline
        bench_serving.write_json(str(REPO / "BENCH_serving.json"),
                                 {"overload": m})
    dt = time.time() - t0
    print(f"smoke_serve[overload]: {m['requests']} requests at "
          f"{m['oversubscription']}x capacity, 0 rejected, "
          f"{m['preemptions']} preemptions "
          f"({m['swap_outs']} swap-outs, {m['resumes']} resumes), "
          f"{dt:.1f}s -> {'PASS' if ok else 'FAIL'}")
    return ok


def engine_core(cfg, params) -> dict | None:
    """The unified-API no-regression check (see benchmarks.serving
    .engine_core): LLM front door within 5% of the directly-driven
    engine, auto budget controller matches/beats fixed TTFT p50."""
    from benchmarks import serving as bench_serving
    t0 = time.time()
    try:
        m = bench_serving.engine_core(cfg, params)
    except AssertionError as e:
        print(f"smoke_serve[engine_core]: FAIL ({e})")
        return None
    dt = time.time() - t0
    print(f"smoke_serve[engine_core]: LLM {m['fixed']['tok_s']} tok/s "
          f"(gap {m['vs_direct_gap']}x vs direct), auto-budget "
          f"{m['auto']['tok_s']} tok/s / "
          f"{m['auto']['ttft_p50_short_ms']}ms TTFT p50 (fixed "
          f"{m['fixed']['ttft_p50_short_ms']}ms, budget "
          f"{m['auto']['budget_tokens']} tokens), {dt:.1f}s -> PASS")
    return m


def spatial() -> dict | None:
    """2-shard subprocess smoke; returns the direct-vs-LLM throughput
    numbers for the ``engine_core`` entry (None on failure)."""
    t0 = time.time()
    prog = pathlib.Path(__file__).parent / "smoke_spatial_prog.py"
    out = subprocess.run([sys.executable, str(prog)],
                         capture_output=True, text=True, timeout=900)
    ok = out.returncode == 0 and "SPATIAL_OK" in out.stdout
    dt = time.time() - t0
    detail = out.stdout.strip().splitlines()[-1] if out.stdout.strip() \
        else out.stderr[-300:]
    print(f"smoke_serve[spatial]: {detail} ({dt:.1f}s) "
          f"-> {'PASS' if ok else 'FAIL'}")
    if not ok:
        return None
    match = re.search(r"SPATIAL_TOKS direct=([\d.]+) llm=([\d.]+)",
                      out.stdout)
    if not match:
        return None
    direct, llm = float(match.group(1)), float(match.group(2))
    return {"direct_tok_s": direct, "llm_tok_s": llm,
            "gap": round(direct / max(llm, 1e-9), 3)}


def _check_trace(events: list[dict], backend: str,
                 want_shards: bool = False) -> None:
    """The Perfetto-loadability contract every traced backend must meet."""
    spans = [e for e in events if e.get("ph") == "X"]
    assert spans, f"{backend}: no spans in trace"
    for e in spans:
        assert {"name", "ts", "dur", "pid", "tid"} <= set(e), e
    ticks = [e for e in spans if e["name"] == "tick"]
    assert ticks, f"{backend}: no tick spans"
    ts = [e["ts"] for e in ticks]
    assert ts == sorted(ts), f"{backend}: tick timestamps not monotonic"
    if want_shards:
        tagged = [e for e in events
                  if (e.get("args") or {}).get("shard") is not None]
        assert tagged, f"{backend}: no shard-tagged events"


def trace_smoke(cfg, params, out_dir: pathlib.Path) -> bool:
    """A traced run per backend, each exported as Chrome trace JSON that
    loads back cleanly (ui.perfetto.dev-compatible) + a phase table."""
    import trace_summary
    from repro import obs

    out_dir.mkdir(parents=True, exist_ok=True)
    ok = True
    t0 = time.time()

    # dense + paged in-process
    for backend in ("dense", "paged"):
        tel = obs.Telemetry({"backend": backend})
        kw = {}
        if backend == "paged":
            from repro.serving import SchedulerCfg
            kw = dict(engine_cfg=PagedEngineCfg(
                max_batch=2, page_size=16, n_pages=24, hot_pages=4,
                eos_id=-1), sched_cfg=SchedulerCfg(chunk_pages=1,
                                                   prefill_tokens=48))
        llm = LLM.from_config(cfg, backend=backend, params=params,
                              telemetry=tel, **kw)
        for i, n in enumerate((6, 18, 35)):
            llm.submit((np.arange(n, dtype=np.int32) * 5 + i) % cfg.vocab,
                       max_tokens=4, rid=i)
        done = llm.run_until_done()
        assert all(len(v) == 4 for v in done.values()), (backend, done)
        path = out_dir / f"trace_{backend}.json"
        tel.tracer.export_chrome(str(path))
        events = obs.load_trace(str(path))
        try:
            _check_trace(events, backend)
        except AssertionError as e:
            print(f"smoke_serve[trace:{backend}]: FAIL ({e})")
            ok = False
            continue
        print(trace_summary.format_table(obs.phase_summary(events),
                                         title=backend))
        print(f"smoke_serve[trace:{backend}]: {path} "
              f"({len(events)} events) -> PASS")

    # spatial: 2-shard fake-device mesh needs its own process
    prog = pathlib.Path(__file__).parent / "smoke_spatial_prog.py"
    sp_path = out_dir / "trace_spatial.json"
    out = subprocess.run(
        [sys.executable, str(prog), "--trace", str(sp_path)],
        capture_output=True, text=True, timeout=900)
    sp_ok = out.returncode == 0 and "SPATIAL_TRACE_OK" in out.stdout
    if sp_ok:
        from repro import obs
        events = obs.load_trace(str(sp_path))
        try:
            _check_trace(events, "spatial", want_shards=True)
        except AssertionError as e:
            print(f"smoke_serve[trace:spatial]: FAIL ({e})")
            sp_ok = False
        else:
            import trace_summary
            print(trace_summary.format_table(obs.phase_summary(events),
                                             title="spatial"))
            print(f"smoke_serve[trace:spatial]: {sp_path} "
                  f"({len(events)} events) -> PASS")
    else:
        tail = out.stdout.strip().splitlines()[-1:] or [out.stderr[-300:]]
        print(f"smoke_serve[trace:spatial]: FAIL ({tail[0]})")
    ok = ok and sp_ok
    print(f"smoke_serve[trace]: all backends in {time.time() - t0:.1f}s "
          f"-> {'PASS' if ok else 'FAIL'}")
    return ok


def bundle_smoke(cfg, params, out_dir: pathlib.Path) -> bool:
    """One pressured paged run with full telemetry + the DLZS audit
    sampler, dumped as an ``LLM.debug_bundle()`` — the artifact CI
    uploads when the bench regression gate fails, and the smoke that
    the whole bundle surface stays dumpable."""
    import json

    import trace_summary
    from repro import obs
    from repro.serving import SchedulerCfg

    tel = obs.Telemetry({"backend": "paged"})
    llm = LLM.from_config(
        cfg, backend="paged", params=params, telemetry=tel,
        engine_cfg=PagedEngineCfg(max_batch=4, page_size=16, n_pages=10,
                                  hot_pages=4, eos_id=-1),
        sched_cfg=SchedulerCfg(chunk_pages=1, prefill_tokens=64,
                               swap=True),
        audit_cfg=obs.AuditCfg(every_ticks=4))
    for i, n in enumerate((16, 33, 16, 40)):
        llm.submit((np.arange(n, dtype=np.int32) * 3 + i) % cfg.vocab,
                   max_tokens=16, rid=i)
    llm.run_until_done(max_steps=8000)
    out = llm.debug_bundle(str(out_dir))
    want = {"recorder.jsonl", "trace.json", "metrics.json",
            "metrics.prom", "accounting.json", "audit.json",
            "timelines.json", "config.json"}
    have = {p.name for p in pathlib.Path(out).iterdir()}
    missing = want - have
    if missing:
        print(f"smoke_serve[bundle]: FAIL (missing {sorted(missing)})")
        return False
    with open(pathlib.Path(out) / "metrics.json") as f:
        print(trace_summary.accounting_table(json.load(f), title=out))
    print(f"smoke_serve[bundle]: {out} ({len(have)} artifacts) -> PASS")
    return True


def bundle_disagg_smoke(cfg, params, out_dir: pathlib.Path) -> bool:
    """One disaggregated (paged prefill -> paged decode) run with full
    telemetry, dumped as ``DisaggRouter.debug_bundle()`` — the artifact
    the CI disagg job uploads on failure, and the smoke that the router
    adds the fabric artifacts (transfer.json, accounting_prefill.json)
    on top of the base bundle."""
    from repro import obs
    from repro.serving import (DisaggRouter, PagedServingEngine,
                               SchedulerCfg)

    tel = obs.Telemetry({"backend": "paged", "disagg": True})
    llm = DisaggRouter(
        PagedServingEngine(cfg, params,
                           PagedEngineCfg(max_batch=2, page_size=16,
                                          n_pages=32, hot_pages=4,
                                          eos_id=-1),
                           SchedulerCfg(chunk_pages=1,
                                        prefill_tokens=48)),
        PagedServingEngine(cfg, params,
                           PagedEngineCfg(max_batch=4, page_size=16,
                                          n_pages=64, hot_pages=4,
                                          eos_id=-1),
                           SchedulerCfg(chunk_pages=1, prefill_tokens=16)),
        telemetry=tel)
    for i, n in enumerate((16, 33, 16, 40)):
        llm.submit((np.arange(n, dtype=np.int32) * 3 + i) % cfg.vocab,
                   max_tokens=16, rid=i)
    llm.run_until_done(max_steps=8000)
    out = llm.debug_bundle(str(out_dir))
    want = {"recorder.jsonl", "trace.json", "metrics.json",
            "metrics.prom", "accounting.json", "accounting_prefill.json",
            "transfer.json", "timelines.json", "config.json"}
    have = {p.name for p in pathlib.Path(out).iterdir()}
    missing = want - have
    if missing:
        print(f"smoke_serve[bundle-disagg]: FAIL "
              f"(missing {sorted(missing)})")
        return False
    tr = llm.transfer.stats()
    if tr["n_transfers"] == 0 or tr["in_flight"]:
        print(f"smoke_serve[bundle-disagg]: FAIL (fabric stats {tr})")
        return False
    print(f"smoke_serve[bundle-disagg]: {out} ({len(have)} artifacts, "
          f"{tr['n_transfers']} transfers) -> PASS")
    return True


def main() -> int:
    ap = argparse.ArgumentParser(description="serving smoke")
    ap.add_argument("--trace", nargs="?", const="", metavar="DIR",
                    default=None,
                    help="run ONLY the telemetry smoke; export Perfetto "
                         "traces for all three backends into DIR")
    ap.add_argument("--bundle", metavar="DIR", default=None,
                    help="run ONLY a pressured paged workload and dump "
                         "an LLM.debug_bundle() into DIR")
    ap.add_argument("--bundle-disagg", metavar="DIR", default=None,
                    help="run ONLY a disaggregated (prefill -> decode) "
                         "workload and dump a DisaggRouter."
                         "debug_bundle() into DIR")
    args = ap.parse_args()

    from benchmarks import serving as bench_serving
    cfg = dataclasses.replace(get_smoke_config("olmo_1b"), star=None)
    params = lm.init(jax.random.PRNGKey(0), cfg)

    if args.trace is not None:
        out_dir = pathlib.Path(args.trace) if args.trace \
            else pathlib.Path(tempfile.mkdtemp(prefix="repro_traces_"))
        return 0 if trace_smoke(cfg, params, out_dir) else 1
    if args.bundle is not None:
        return 0 if bundle_smoke(cfg, params,
                                 pathlib.Path(args.bundle)) else 1
    if args.bundle_disagg is not None:
        return 0 if bundle_disagg_smoke(
            cfg, params, pathlib.Path(args.bundle_disagg)) else 1

    ok = basic(cfg, params)
    ok = overload(cfg, params) and ok
    core = engine_core(cfg, params)
    ok = (core is not None) and ok
    sp = spatial()
    ok = (sp is not None) and ok
    if core is not None and sp is not None:
        core["spatial"] = sp
        bench_serving.write_json(str(REPO / "BENCH_serving.json"),
                                 {"engine_core": core})
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
