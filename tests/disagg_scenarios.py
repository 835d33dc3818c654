"""Shared disaggregation scenarios: router parity, COW transfer-once,
and the transfer-seam chaos drive, parameterized over the instance pair.

Runners supply ``make_router(**kw)`` building a ``DisaggRouter`` over a
fresh (prefill, decode) instance pair — ``tests/test_disagg.py`` runs
paged↔paged in-process; ``tests/spatial_progs/disagg_prog.py`` runs a
2-shard spatial prefill instance into a paged decode instance in a
subprocess (fake-device mesh). The chaos drive asserts the
cross-instance conservation invariant: page conservation AND the
refcount watchdog on BOTH pools after every router tick, with staged
fabric payloads holding host bytes only (never device references)."""

from __future__ import annotations

import numpy as np

from repro.obs import conservation_error, reconcile_refs

MIXED_LENGTHS = (5, 8, 17, 33, 40)


def prompts_for(cfg, lengths=MIXED_LENGTHS):
    return [(np.arange(l, dtype=np.int32) * 7 + i) % cfg.vocab
            for i, l in enumerate(lengths)]


def drive_checked_disagg(router, max_steps=4000):
    """Tick the router to idle, asserting conservation + the refcount
    watchdog on BOTH instances after EVERY tick — no handoff, fault,
    cancellation or recompute may leak or double-free a page on either
    pool, and the fabric may never retain device references."""
    steps = 0
    while router.has_work() and steps < max_steps:
        router.tick()
        for name, eng in (("prefill", router.prefill),
                          ("decode", router.engine)):
            err = conservation_error(eng.accounting_snapshot())
            assert err == 0, \
                f"{name} conservation broke at tick {steps}: {err}"
            wd = reconcile_refs(eng._expected_refs(),
                                eng.backend.pool_refs())
            assert wd.ok, f"{name} watchdog at tick {steps}: " \
                          f"{wd.describe()}"
        steps += 1
    assert steps < max_steps, "disagg run never drained"
    assert not router.transfer.in_flight(), "transfer left in flight"
    assert len(router.transfer.staging) == 0, "payload left staged"


def run_router(router, prompts, max_tokens=12, rid0=0):
    handles = [router.submit(p, max_tokens=max_tokens, rid=rid0 + i)
               for i, p in enumerate(prompts)]
    drive_checked_disagg(router)
    assert all(h.done for h in handles), "router left work behind"
    return handles


def assert_drained(router):
    """Both pools empty, swap areas empty, fabric idle."""
    for name, eng in (("prefill", router.prefill),
                      ("decode", router.engine)):
        st = eng.stats()
        pool = st.get("pool")
        live = pool.live if pool is not None else st["pools"]["live"]
        assert live == 0, f"{name} pool leaked {live} pages"
        assert st["swap"].entries == 0, f"{name} payload left behind"


def scenario_disagg_parity(make_router, make_single, cfg,
                           greedy_tie=None) -> str:
    """Disaggregated serving keeps token parity with a single instance
    of the decode backend, and every multi-token request crossed the
    fabric exactly once with its pages. ``greedy_tie`` (prompt, got,
    want) -> bool admits a divergence at an audited argmax tie — for a
    prefill backend whose reduction order differs from the decode
    backend's (sharded prefill); None demands exact tokens."""
    prompts = prompts_for(cfg)
    single = make_single()
    handles = [single.submit(p, max_tokens=12, rid=i)
               for i, p in enumerate(prompts)]
    single.run_until_done()
    want = {h.rid: h.tokens for h in handles}
    router = make_router()
    got = {h.rid: h.tokens for h in run_router(router, prompts)}
    for rid in want:
        assert got[rid] == want[rid] or (
            greedy_tie is not None
            and greedy_tie(prompts[rid], got[rid], want[rid])), \
            f"disagg parity broke (rid {rid}):\n{got}\n{want}"
    tr = router.transfer
    assert tr.n_transfers == len(prompts), \
        f"expected one handoff per request, got {tr.n_transfers}"
    assert tr.n_faults == 0 and tr.n_recompute == 0
    assert tr.bytes_total > 0, "no payload bytes crossed the fabric"
    assert_drained(router)
    return f"disagg-parity ({tr.n_transfers} handoffs, " \
           f"{tr.bytes_total} bytes)"


def scenario_disagg_chaos(make_router, make_single, cfg,
                          greedy_tie=None) -> str:
    """Faults at the ``transfer`` seam: the payload is lost on the hop,
    the request recovers through decode-side recompute replay, both
    pools stay conserved every tick, and recovered requests keep token
    parity with the fault-free run (modulo greedy argmax ties when the
    runner supplies an auditor)."""
    from repro.serving import FaultPlan

    prompts = prompts_for(cfg)
    want = {h.rid: h.tokens
            for h in run_router(make_router(), prompts)}
    # explicit schedule: seeded windows start at call index 1, but a
    # short run only makes len(prompts) transfer calls — pin the first
    # two hops to fail so the recompute path is always exercised
    plan = FaultPlan(schedule={"transfer": {0, 1}})
    router = make_router(fault_plan=plan)
    handles = run_router(router, prompts)
    assert plan.fired(("transfer",)) == 2, "transfer faults never fired"
    assert router.transfer.n_faults == 2
    ties = 0
    for h in handles:
        assert h.outcome == "done", f"rid {h.rid}: {h.outcome}"
        if h.tokens == want[h.rid]:
            continue
        assert greedy_tie is not None and \
            greedy_tie(prompts[h.rid], h.tokens, want[h.rid]), \
            f"rid {h.rid} lost parity after transfer fault"
        ties += 1
    assert_drained(router)
    return f"disagg-chaos (2 hop faults recovered, {ties} tie-audited)"
