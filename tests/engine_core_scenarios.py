"""Backend-conformance scenarios for the shared EngineCore executor.

One scenario set, driven ONLY through the ``repro.serving.api.LLM``
front door, that every pool-backed serving backend must pass:

* admission + token parity with the dense oracle (one chunk per tick,
  a multi-chunk token budget, and the ``prefill_tokens="auto"``
  budget controller) with the one-compile invariants;
* pool pressure: preempt/swap/page-in keeps token parity with an
  unpressured run of the same backend (batched path);
* recompute-mode preemption parity;
* lazy cold-page shedding: under pressure with ``lazy_swap`` victims
  park DLZS-cold ref-1 pages and KEEP decoding — sheds happen, full
  preemptions do not, every request completes;
* decode-time DLZS sparsity + int8 cold tier
  (``decode_hot_width`` / ``kv_quant``): swap round-trips restore
  quantized pages + tracker flags (token parity under preemption), and
  the tier coexists with lazy shedding;
* max_tokens=1 and submit-time capacity rejection semantics.

Runners supply a ``make_llm(max_batch, pages, hot, scfg, ...)`` factory
(``pages``/``hot`` are per-pool-shard for sharded backends — the same
numbers the per-engine tests historically used) plus a params dict from
``BACKEND_PARAMS``. ``tests/test_engine_core.py`` runs the paged backend
in-process; ``tests/spatial_progs/conformance_prog.py`` runs the spatial
backend on a fake-device mesh in a subprocess.
"""

from __future__ import annotations

import numpy as np

from repro.serving import EngineCfg, LLM, SchedulerCfg, ServingEngine
from repro.serving import parity

MIXED_LENGTHS = (5, 8, 17, 33, 40)
PRESSURE_LENGTHS = (16, 17, 16, 18)

# scenario sizing per backend kind (pages are per pool shard).
# ``tie_ulps``: None demands exact tokens where a scenario replays a
# request; a sharded backend sums attention in another order than its own
# replay under other batch shapes, so there a divergence is admitted at an
# audited bf16 argmax tie (``_greedy_tie``)
BACKEND_PARAMS = {
    "paged": {
        "pressure_pages": 7,
        "shed": dict(pages=9, hot=3, prompt_len=40, gen=48),
        "sparse_width": 2,
        "tie_ulps": None,
    },
    "spatial2": {
        "pressure_pages": 5,
        "shed": dict(pages=6, hot=2, prompt_len=80, gen=48),
        "sparse_width": 2,
        "tie_ulps": parity.TIE_ULPS,
    },
    "spatial4": {
        "pressure_pages": 3,
        "shed": dict(pages=6, hot=2, prompt_len=160, gen=64),
        "sparse_width": 2,
        "tie_ulps": parity.TIE_ULPS,
    },
}


def _one_chunk(**kw) -> SchedulerCfg:
    """One 1-page chunk of one prompt per tick: a prefill token budget of
    exactly one chunk at page size 16."""
    return SchedulerCfg(chunk_pages=1, prefill_tokens=16, **kw)


def _prompts(cfg, lengths):
    return [(np.arange(l, dtype=np.int32) * 7 + i) % cfg.vocab
            for i, l in enumerate(lengths)]


def _run_llm(llm: LLM, prompts, max_tokens=5, max_steps=4000):
    handles = [llm.submit(p, max_tokens=max_tokens, rid=i)
               for i, p in enumerate(prompts)]
    done = llm.run_until_done(max_steps=max_steps)
    assert all(h.done for h in handles), "run_until_done left work behind"
    return done


def _dense_oracle(cfg, params, prompts, max_tokens=5):
    dense = LLM(ServingEngine(cfg, params,
                              EngineCfg(max_batch=2, max_len=64,
                                        eos_id=-1)))
    return _run_llm(dense, prompts, max_tokens)


def scenario_parity_sequential(make_llm, cfg, params, bp) -> str:
    """Mixed-length chunked prefill through LLM, one chunk of one prompt
    per tick, == dense oracle, token-for-token, with one prefill and one
    decode compilation."""
    prompts = _prompts(cfg, MIXED_LENGTHS)
    want = _dense_oracle(cfg, params, prompts)
    llm = make_llm(max_batch=2, pages=32, hot=4, scfg=_one_chunk())
    got = _run_llm(llm, prompts)
    assert got == want, f"one-chunk parity broke:\n{got}\n{want}"
    st = llm.stats()
    assert st["prefill_batch_compiles"] == 1, st["prefill_batch_compiles"]
    assert st["decode_compiles"] == 1, st["decode_compiles"]
    return "parity-sequential"


def scenario_parity_batched(make_llm, cfg, params, bp) -> str:
    """Batched varlen chunk prefill (one token-budget dispatch per tick)
    == dense oracle, with ONE batched-prefill compile and one decode
    compile."""
    prompts = _prompts(cfg, MIXED_LENGTHS)
    want = _dense_oracle(cfg, params, prompts)
    llm = make_llm(max_batch=2, pages=32, hot=4,
                   scfg=SchedulerCfg(chunk_pages=1, prefill_tokens=48))
    got = _run_llm(llm, prompts)
    assert got == want, f"batched parity broke:\n{got}\n{want}"
    st = llm.stats()
    assert st["prefill_batch_compiles"] == 1, st["prefill_batch_compiles"]
    assert st["decode_compiles"] == 1, st["decode_compiles"]
    return "parity-batched"


def scenario_parity_auto_budget(make_llm, cfg, params, bp) -> str:
    """``prefill_tokens="auto"``: the EMA budget controller must stay
    compile-safe (one batched compile) and keep first-token parity with
    the fixed-budget path on every request."""
    prompts = _prompts(cfg, MIXED_LENGTHS)
    want = _dense_oracle(cfg, params, prompts)
    llm = make_llm(max_batch=2, pages=32, hot=4,
                   scfg=SchedulerCfg(chunk_pages=1, prefill_tokens="auto"))
    got = _run_llm(llm, prompts)
    assert set(got) == set(want)
    for rid in want:
        assert len(got[rid]) == len(want[rid])
        assert got[rid][0] == want[rid][0], f"rid {rid} first token"
    assert got == want, f"auto-budget parity broke:\n{got}\n{want}"
    st = llm.stats()
    assert st["prefill_batch_compiles"] == 1, st["prefill_batch_compiles"]
    ctl = llm.engine.sched.budget_ctl
    assert ctl is not None and ctl.lo <= ctl.budget <= ctl.hi
    return "parity-auto-budget"


def scenario_pressure_swap(make_llm, cfg, params, bp) -> str:
    """Batched prefill under pool pressure: preemption (swap + page-in,
    including pending-chunk rollback) keeps token parity with an
    unpressured run of the same backend."""
    prompts = _prompts(cfg, PRESSURE_LENGTHS)
    scfg = lambda: SchedulerCfg(chunk_pages=1, prefill_tokens=64,
                                swap=True)
    big = make_llm(max_batch=4, pages=64, hot=4, scfg=scfg())
    want = _run_llm(big, prompts, max_tokens=20)
    tiny = make_llm(max_batch=4, pages=bp["pressure_pages"], hot=4,
                    scfg=scfg())
    got = _run_llm(tiny, prompts, max_tokens=20)
    st = tiny.stats()
    assert got == want, f"pressure parity broke:\n{got}\n{want}"
    assert st["sched"].preemptions > 0, "pool pressure never hit"
    assert st["swap"].swap_ins == st["swap"].swap_outs
    assert st["swap"].entries == 0, "payload left behind"
    assert tiny.metrics()["preemptions"] == st["sched"].preemptions
    return f"pressure-swap ({st['sched'].preemptions} preemptions)"


def scenario_recompute(make_llm, cfg, params, bp) -> str:
    """Recompute-mode preemption (drop pages, replay prompt + emitted
    tokens) keeps token parity — greedy replay is exact (up to audited
    bf16 argmax ties where ``bp["tie_ulps"]`` admits them)."""
    prompts = _prompts(cfg, PRESSURE_LENGTHS)
    big = make_llm(max_batch=4, pages=64, hot=4,
                   scfg=_one_chunk(swap=False))
    want = _run_llm(big, prompts, max_tokens=20)
    tiny = make_llm(max_batch=4, pages=bp["pressure_pages"], hot=4,
                    scfg=_one_chunk(swap=False))
    got = _run_llm(tiny, prompts, max_tokens=20)
    st = tiny.stats()
    ties = _assert_parity_or_tie(cfg, params, prompts, got, want,
                                 "recompute", bp["tie_ulps"])
    assert st["sched"].preemptions > 0
    assert st["sched"].recomputes == st["sched"].preemptions
    assert st["swap"].swap_outs == 0
    return (f"recompute ({st['sched'].recomputes} replays, "
            f"{ties} tie-audited)")


def scenario_shed(make_llm, cfg, params, bp) -> str:
    """Lazy cold-page swap: under decode-time pool pressure with
    ``lazy_swap`` victims park only DLZS-cold ref-1 pages (pages the
    hot-set gather was already skipping) and KEEP decoding — requests
    finish with sheds instead of full preemptions, and the shed payloads
    are dropped at finish."""
    sp = bp["shed"]
    llm = make_llm(max_batch=2, pages=sp["pages"], hot=sp["hot"],
                   scfg=_one_chunk(swap=True, lazy_swap=True))
    prompts = [(np.arange(sp["prompt_len"], dtype=np.int32) + i)
               % cfg.vocab for i in range(2)]
    done = _run_llm(llm, prompts, max_tokens=sp["gen"])
    st = llm.stats()
    assert all(len(v) == sp["gen"] for v in done.values()), done
    assert st["sched"].sheds > 0, "nothing was shed"
    assert st["sched"].preemptions == 0, \
        f"shedding should have avoided full preemption " \
        f"({st['sched'].preemptions} preemptions)"
    assert st["swap"].entries == 0   # shed payloads dropped at finish
    pool = st.get("pool")
    live = pool.live if pool is not None else st["pools"]["live"]
    assert live == 0
    return f"shed ({st['sched'].sheds} sheds, 0 preemptions)"


def scenario_decode_sparse_pressure(make_llm, cfg, params, bp) -> str:
    """Decode-time DLZS sparsity + int8 cold tier under pool pressure.

    Part 1 — preempt/swap round-trip: with ``decode_hot_width`` and
    ``kv_quant="int8"`` on, a pressured run (preemptions, swap-out /
    swap-in) must keep token parity with an unpressured run of the SAME
    sparse config. The swap payload carries the int8 tier rows and
    ``upload_park`` re-derives the QuantTracker flags from the parked
    scales — losing either would change which pages re-quantize and what
    the bounded gather reads, breaking parity.

    Part 2 — lazy shed interplay: long sequences, tiny pool,
    ``lazy_swap`` sheds. Cold pages quantize (events observed), shed
    victims park without full preemption, every request still finishes,
    and no payload survives the run.
    """
    w = bp["sparse_width"]
    scfg = lambda: SchedulerCfg(chunk_pages=1, prefill_tokens=64,
                                swap=True, decode_hot_width=w,
                                kv_quant="int8")
    prompts = _prompts(cfg, PRESSURE_LENGTHS)
    big = make_llm(max_batch=4, pages=64, hot=4, scfg=scfg())
    want = _run_llm(big, prompts, max_tokens=20)
    tiny = make_llm(max_batch=4, pages=bp["pressure_pages"], hot=4,
                    scfg=scfg())
    got = _run_llm(tiny, prompts, max_tokens=20)
    st = tiny.stats()
    assert got == want, f"sparse+quant swap parity broke:\n{got}\n{want}"
    assert st["sched"].preemptions > 0, "pool pressure never hit"
    assert st["swap"].swap_ins == st["swap"].swap_outs
    assert st["swap"].entries == 0, "payload left behind"
    assert st["decode_compiles"] == 1, st["decode_compiles"]
    assert st["hot_width"] == w, st["hot_width"]

    sp = bp["shed"]
    # recent=1: the sphere selector pins every shard's sink page hot on
    # top of the keep_recent window (recent * n_shards global pages), so
    # the stock shed sizing leaves nothing sheddable on sharded
    # backends; a 1-page local window restores shed candidates.
    llm = make_llm(max_batch=2, pages=sp["pages"], hot=sp["hot"],
                   recent=1,
                   scfg=_one_chunk(swap=True, lazy_swap=True,
                                   decode_hot_width=w, kv_quant="int8"))
    long_prompts = [(np.arange(sp["prompt_len"], dtype=np.int32) + i)
                    % cfg.vocab for i in range(2)]
    done = _run_llm(llm, long_prompts, max_tokens=sp["gen"])
    st2 = llm.stats()
    assert all(len(v) == sp["gen"] for v in done.values()), done
    assert st2["sched"].sheds > 0, "nothing was shed"
    assert st2["kv_quant"]["quantize_events"] > 0, \
        "cold pages never quantized"
    assert st2["kv_quant"]["effective_capacity_pages"] >= \
        st2["kv_quant"]["pages_quantized_live"]  # sane accounting
    assert st2["swap"].entries == 0
    return (f"decode-sparse-pressure "
            f"({st['sched'].preemptions} preemptions, "
            f"{st2['sched'].sheds} sheds, "
            f"{st2['kv_quant']['quantize_events']} quantize events)")


def scenario_admission(make_llm, cfg, params, bp) -> str:
    """max_tokens=1 finishes at prefill without a decode step (pages
    released); an impossible request is rejected at submit; max_len <=
    prompt is rejected."""
    llm = make_llm(max_batch=2, pages=32, hot=4,
                   scfg=_one_chunk())
    want = _dense_oracle(cfg, params,
                         [np.arange(5, dtype=np.int32)], max_tokens=1)
    done = _run_llm(llm, [np.arange(5, dtype=np.int32)], max_tokens=1)
    assert done == want and len(done[0]) == 1
    st = llm.stats()
    pool = st.get("pool")
    live = pool.live if pool is not None else st["pools"]["live"]
    assert live == 0, "pages not released at prefill-finish"
    try:
        llm.submit(np.arange(8, dtype=np.int32), max_tokens=10_000_000)
        raise AssertionError("over-capacity request was admitted")
    except ValueError:
        pass
    try:
        llm.submit(np.arange(32, dtype=np.int32), max_tokens=4,
                   max_len=16)
        raise AssertionError("max_len <= prompt was admitted")
    except ValueError:
        pass
    return "admission"


def scenario_streaming(make_llm, cfg, params, bp) -> str:
    """RequestHandle streaming: iterating a handle yields exactly the
    request's tokens while co-resident requests keep being served, and
    metrics() reports the run."""
    llm = make_llm(max_batch=2, pages=32, hot=4,
                   scfg=_one_chunk())
    h0 = llm.submit(np.arange(20, dtype=np.int32), max_tokens=6,
                    sla="interactive")
    h1 = llm.submit(np.arange(9, dtype=np.int32), max_tokens=4,
                    sla="batch")
    streamed = list(h0)
    assert streamed == h0.tokens and len(streamed) == 6
    assert h1.result() == h1.tokens and len(h1.tokens) == 4
    m = llm.metrics()
    assert m["requests"] == 2 and m["tokens"] == 10
    assert set(m["per_sla"]) == {"interactive", "batch"}
    assert m["ttft_p50_ms"] > 0 and m["tok_s"] > 0
    assert m["occupancy"] is not None
    return "streaming"


SCENARIOS = (
    scenario_parity_sequential,
    scenario_parity_batched,
    scenario_parity_auto_budget,
    scenario_pressure_swap,
    scenario_recompute,
    scenario_shed,
    scenario_decode_sparse_pressure,
    scenario_admission,
    scenario_streaming,
)


def run_all(make_llm, cfg, params, bp, log=print) -> None:
    for scenario in SCENARIOS:
        log(f"conformance[{scenario.__name__}]: "
            f"{scenario(make_llm, cfg, params, bp)} OK")


# ---------------------------------------------------------------------------
# Chaos conformance: fault injection + lifecycle. Kept out of SCENARIOS /
# run_all (the CI ``chaos`` job runs these via run_chaos) so the tier-1
# scenario wall time is unchanged.
# ---------------------------------------------------------------------------

CHAOS_SEED = 1234


def _attach_tel(llm):
    """Wire live telemetry into an already-built LLM so chaos runs can
    assert recorder events and fault counters (the make_llm factories
    default to NULL_TELEMETRY)."""
    from repro import obs
    tel = obs.Telemetry()
    llm.engine.attach_telemetry(tel)
    llm.tel = tel
    return tel


def _drive_checked(llm, max_steps=4000):
    """Tick to idle, asserting the page-conservation identity AND the
    refcount watchdog after EVERY tick — the chaos invariant: no fault,
    retry, cancellation or quarantine may leak or double-free a page."""
    from repro.obs import conservation_error, reconcile_refs
    eng = llm.engine
    steps = 0
    while llm.has_work() and steps < max_steps:
        llm.tick()
        err = conservation_error(eng.accounting_snapshot())
        assert err == 0, f"conservation broke at tick {steps}: {err}"
        wd = reconcile_refs(eng._expected_refs(), eng.backend.pool_refs())
        assert wd.ok, f"watchdog at tick {steps}: {wd.describe()}"
        steps += 1
    assert steps < max_steps, "chaos run never drained"


def _greedy_tie(cfg, params, prompt, got, want, ulps=0) -> bool:
    """Audit the first divergence between a recomputed request's tokens
    and the fault-free baseline: recompute-replay is exact under greedy
    decode *up to argmax ties*. Prefill and decode run under different
    batch shapes, so XLA's reduction order differs by an epsilon that
    breaks a bit-equal bf16 logit tie arbitrarily. Returns True when the
    two diverging tokens lie within ``ulps`` bf16 ulps of the top logit
    at the divergence point (0: bit-equal) — a legitimate replay
    outcome, not a state bug (``repro.serving.parity``)."""
    return parity.is_greedy_tie(params, cfg, prompt, got, want, ulps=ulps)


def _assert_parity_or_tie(cfg, params, prompts, got, want, what,
                          ulps) -> int:
    """Every request's tokens equal the reference's, or — when ``ulps``
    is not None — diverge first at an audited greedy tie within ``ulps``
    bf16 ulps (``_greedy_tie``). Returns the tie count."""
    if ulps is None:
        assert got == want, f"{what} parity broke:\n{got}\n{want}"
        return 0
    assert set(got) == set(want), f"{what}: rids differ"
    ties = 0
    for rid in want:
        if got[rid] == want[rid]:
            continue
        assert _greedy_tie(cfg, params, prompts[rid], got[rid],
                           want[rid], ulps), \
            f"{what} parity broke (rid {rid}):\n{got}\n{want}"
        ties += 1
    return ties


def chaos_scenario_faults(make_llm, cfg, params, bp) -> str:
    """Deterministic fault storm mid-run: a dispatch exception on the
    first batched wave, an injected pool exhaustion, a corrupt swap
    page-in, and fused-decode failures. Zero unhandled exceptions, every
    request reaches a terminal state, conservation + watchdog hold every
    tick, and requests that survive retry-with-recompute keep token
    parity with an unpressured fault-free run (modulo greedy argmax
    ties, audited per divergence by ``_greedy_tie``)."""
    from repro.serving import FaultPlan, FaultyBackend
    prompts = _prompts(cfg, PRESSURE_LENGTHS)
    scfg = lambda: SchedulerCfg(chunk_pages=1, prefill_tokens=64,
                                swap=True)
    big = make_llm(max_batch=4, pages=64, hot=4, scfg=scfg())
    want = _run_llm(big, prompts, max_tokens=20)

    plan = FaultPlan(schedule={
        "dispatch": {1},       # first batched wave dies mid-prefill
        "alloc": {3},          # injected pool exhaustion
        "swap_corrupt": {1},   # first page-in payload is corrupt
        "decode": {4, 9},      # fused decode dispatch failures
    })
    llm = make_llm(max_batch=4, pages=bp["pressure_pages"], hot=4,
                   scfg=scfg())
    tel = _attach_tel(llm)
    llm.engine.backend = FaultyBackend(llm.engine.backend, plan)
    handles = [llm.submit(p, max_tokens=20, rid=i)
               for i, p in enumerate(prompts)]
    _drive_checked(llm)

    for seam in ("dispatch", "alloc", "decode"):
        assert plan.fired((seam,)) > 0, f"{seam} fault never fired"
    # The swap seam only exists when pressure actually forces a
    # park+resume cycle; early quarantines can relieve pressure below the
    # swap threshold on the sharded backends. Strict where reachable —
    # the paged sizing always parks, so the corrupt-payload path is
    # exercised there every run.
    if plan.calls.get("swap_corrupt", 0):
        assert plan.fired(("swap_corrupt",)) > 0, "swap fault never fired"
    outcomes = {h.rid: h.outcome for h in handles}
    assert all(o in ("done", "failed") for o in outcomes.values()), outcomes
    ties = 0
    ulps = bp["tie_ulps"] or 0     # bit-equal ties on a single pool
    for h in handles:          # recompute replay is exact (modulo ties)
        if h.outcome != "done" or h.tokens == want[h.rid]:
            continue
        assert _greedy_tie(cfg, params, prompts[h.rid], h.tokens,
                           want[h.rid], ulps), f"rid {h.rid} lost parity"
        ties += 1
    st = llm.stats()
    assert st["sched"].faults > 0
    assert st["sched"].fault_retries > 0
    pool = st.get("pool")
    live = pool.live if pool is not None else st["pools"]["live"]
    assert live == 0, "pages leaked after chaos run"
    assert st["swap"].entries == 0, "payload left behind"
    kinds = {e["kind"] for e in tel.recorder.events()}
    assert "fault_injected" in kinds and "retry" in kinds, kinds
    n_failed = sum(1 for o in outcomes.values() if o == "failed")
    assert n_failed == st["sched"].quarantines
    return (f"chaos-faults ({plan.fired()} injected, "
            f"{st['sched'].faults} faults, "
            f"{st['sched'].fault_retries} retries, "
            f"{n_failed} quarantined, {ties} tie-audited)")


def chaos_scenario_seeded_storm(make_llm, cfg, params, bp) -> str:
    """Seeded randomized storm across every seam (slow-tick stalls
    included): same hard guarantees — no unhandled exception, all
    requests terminal, per-tick conservation + watchdog — without
    pinning which seams fire."""
    from repro.serving import FaultPlan, FaultyBackend
    plan = FaultPlan.seeded(CHAOS_SEED, alloc=2, page_in=2,
                            swap_corrupt=2, dispatch=2, decode=3,
                            stall=2, window=24, stall_s=0.001)
    llm = make_llm(max_batch=4, pages=bp["pressure_pages"], hot=4,
                   scfg=SchedulerCfg(chunk_pages=1, prefill_tokens=64,
                                     swap=True))
    _attach_tel(llm)
    llm.engine.backend = FaultyBackend(llm.engine.backend, plan)
    prompts = _prompts(cfg, PRESSURE_LENGTHS)
    handles = [llm.submit(p, max_tokens=20, rid=i)
               for i, p in enumerate(prompts)]
    _drive_checked(llm)
    assert plan.fired() > 0, "seeded plan never fired"
    outcomes = [h.outcome for h in handles]
    assert all(o in ("done", "failed") for o in outcomes), outcomes
    st = llm.stats()
    pool = st.get("pool")
    live = pool.live if pool is not None else st["pools"]["live"]
    assert live == 0 and st["swap"].entries == 0
    return f"chaos-seeded ({plan.fired()} injected, outcomes={outcomes})"


def chaos_scenario_lifecycle(make_llm, cfg, params, bp) -> str:
    """Cancellation + deadlines through the front door: cancelling a
    prefix-sharing request mid-flight frees only its solely-owned pages
    (the survivor keeps decoding to dense parity), a zero deadline
    expires before admission, and terminal states land in the recorder,
    timelines and per-SLA metrics."""
    llm = make_llm(max_batch=4, pages=32, hot=4,
                   scfg=_one_chunk())
    tel = _attach_tel(llm)
    shared = (np.arange(40, dtype=np.int32) * 3) % cfg.vocab
    want = _dense_oracle(cfg, params, [shared], max_tokens=12)
    h0 = llm.submit(shared, max_tokens=12, rid=0)
    h1 = llm.submit(shared, max_tokens=12, rid=1)     # prefix sharer
    h2 = llm.submit(np.arange(24, dtype=np.int32), max_tokens=12, rid=2)
    h3 = llm.submit(np.arange(9, dtype=np.int32), max_tokens=12, rid=3,
                    deadline_ms=0.0)                  # expires immediately
    for _ in range(3):
        llm.tick()
    assert h1.cancel(), "cancel of a live request returned False"
    assert not h1.cancel(), "double-cancel must return False"
    assert h2.cancel()
    _drive_checked(llm)
    assert h0.outcome == "done" and (
        h0.tokens == want[0]
        or (bp["tie_ulps"] is not None
            and _greedy_tie(cfg, params, shared, h0.tokens, want[0],
                            bp["tie_ulps"]))), \
        "survivor lost parity after sharer cancel"
    assert h1.outcome == "cancelled" and h1.done
    assert h2.outcome == "cancelled"
    assert h3.outcome == "expired" and h3.tokens == []
    st = llm.stats()
    pool = st.get("pool")
    live = pool.live if pool is not None else st["pools"]["live"]
    assert live == 0, "cancel/expiry leaked pages"
    kinds = {e["kind"] for e in tel.recorder.events()}
    assert "cancel" in kinds and "deadline_expired" in kinds, kinds
    m = llm.metrics()
    sla = m["per_sla"]["default"]
    assert sla["outcomes"] == {"done": 1, "cancelled": 2, "expired": 1}
    assert sla["deadline_miss_rate"] == 0.25
    return "chaos-lifecycle"


CHAOS_SCENARIOS = (
    chaos_scenario_faults,
    chaos_scenario_seeded_storm,
    chaos_scenario_lifecycle,
)


def run_chaos(make_llm, cfg, params, bp, log=print) -> None:
    for scenario in CHAOS_SCENARIOS:
        log(f"chaos[{scenario.__name__}]: "
            f"{scenario(make_llm, cfg, params, bp)} OK")
