"""Prefill/decode disaggregation: wire-format round-trips, dual-instance
router parity, COW transfer-once, and transfer-seam chaos.

The wire tests pin the flat-payload contract both fabric ends validate
(``kvcache.wire``) and prove an exported request resumes bit-exact on a
fresh instance — fp and int8 KV tiers, parked quant scales, advisory
DLZS scores, COW-shared prefix pages. The router tests drive the
``DisaggRouter`` front door: token parity with a single instance,
shared prefixes crossing the fabric once, recompute recovery from
faults injected at the ``transfer`` seam with page conservation and a
clean refcount watchdog on BOTH instances after every tick. The
spatial↔paged pair runs on a fake-device mesh in a subprocess
(tests/spatial_progs/disagg_prog.py)."""

import dataclasses
import pathlib
import subprocess
import sys

import jax
import numpy as np
import pytest

from repro import obs
from repro.configs import get_smoke_config
from repro.kvcache import quant
from repro.kvcache.wire import payload_bytes, validate_payload
from repro.models import lm
from repro.serving import (DisaggRouter, FaultPlan, LLM, PagedEngineCfg,
                           PagedServingEngine, SchedulerCfg, parity)

import disagg_scenarios as dscen
import engine_core_scenarios as scen

PROGS = pathlib.Path(__file__).parent / "spatial_progs"


@pytest.fixture(scope="module")
def smoke_lm():
    cfg = dataclasses.replace(get_smoke_config("olmo_1b"), star=None)
    params = lm.init(jax.random.PRNGKey(1), cfg)
    return cfg, params


def _paged(cfg, params, *, max_batch=2, pages=32, hot=4, scfg=None):
    return PagedServingEngine(
        cfg, params,
        PagedEngineCfg(max_batch=max_batch, page_size=16, n_pages=pages,
                       hot_pages=hot, eos_id=-1),
        scfg or SchedulerCfg(chunk_pages=1, prefill_tokens=16))


def _router_factory(cfg, params):
    def make_router(*, fault_plan=None, staging="device",
                    transfer_retries=2, tel=None, decode_scfg=None):
        pre = _paged(cfg, params, max_batch=2, pages=32,
                     scfg=SchedulerCfg(chunk_pages=1, prefill_tokens=48))
        dec = _paged(cfg, params, max_batch=4, pages=64,
                     scfg=decode_scfg or SchedulerCfg(chunk_pages=1,
                                                      prefill_tokens=16))
        return DisaggRouter(pre, dec, telemetry=tel,
                            fault_plan=fault_plan, staging=staging,
                            transfer_retries=transfer_retries)
    return make_router


def _single_factory(cfg, params):
    # same shapes as the router's decode instance — the parity reference
    return lambda: LLM(_paged(cfg, params, max_batch=4, pages=64))


# ------------------------------------------------------------- wire format

def _fake_payload(n_park=2, n_kept=0, kind="decode", page=4):
    rows = {"k": np.zeros((2, n_park, page, 1, 3), np.float32),
            "scale": np.zeros((2, n_park), np.float32)} \
        if n_park else None
    p = {"rows": rows, "park": list(range(n_park)),
         "kept": [(n_park + i, 7 + i) for i in range(n_kept)],
         "n_pages": n_park + n_kept, "lookup_toks": None, "kind": kind}
    if kind == "decode":
        p.update(length=9, last_token=3, budget=5)
    else:
        p.update(prompt=np.arange(9), toks=np.arange(9), spans=[],
                 chunk=0, sharing=None, suppress_first=False)
    return p


def test_wire_validate_contract():
    validate_payload(_fake_payload(), page_size=4)
    validate_payload(_fake_payload(kind="prefill"), page_size=4)
    validate_payload(_fake_payload(n_kept=1), page_size=4)

    with pytest.raises(ValueError, match="missing keys"):
        p = _fake_payload()
        del p["n_pages"]
        validate_payload(p)
    with pytest.raises(ValueError, match="missing keys"):
        p = _fake_payload()
        del p["budget"]
        validate_payload(p)
    with pytest.raises(ValueError, match="kind"):
        validate_payload(_fake_payload(kind="weird"))
    with pytest.raises(ValueError, match="covers"):
        p = _fake_payload()
        p["n_pages"] = 3         # coverage hole
        validate_payload(p)
    with pytest.raises(ValueError, match="overlap"):
        p = _fake_payload(n_park=2)
        p["kept"] = [(1, 7)]     # page 1 both parked and kept
        p["n_pages"] = 2
        validate_payload(p)
    with pytest.raises(ValueError, match="page axis"):
        p = _fake_payload()
        p["park"] = [0]          # rows carry 2 pages, park says 1
        p["n_pages"] = 1
        validate_payload(p)
    with pytest.raises(ValueError, match="page width"):
        validate_payload(_fake_payload(page=5), page_size=4)
    with pytest.raises(ValueError, match="scores"):
        p = _fake_payload()
        p["scores"] = [1.0]
        validate_payload(p)
    # cross-instance rule: device page ids never travel
    with pytest.raises(ValueError, match="do not travel"):
        validate_payload(_fake_payload(n_kept=1), transfer=True)
    # the scale leaf (ndim < 5) is exempt from the page-width check
    assert payload_bytes(_fake_payload()) > 0
    assert payload_bytes({"rows": None}) == 0


# --------------------------------------------------- export/adopt round-trip

@pytest.mark.parametrize("tier", ["fp", "int8"])
def test_wire_roundtrip(smoke_lm, tier):
    """Export mid-decode, validate the payload, adopt on a fresh
    instance: the resumed run is token-exact with an undisturbed
    reference of the same config; the int8 tier's parked scales restore
    the quant flags on the peer."""
    cfg, params = smoke_lm
    scfg = lambda: SchedulerCfg(
        chunk_pages=1, prefill_tokens=16,
        decode_hot_width=2 if tier == "int8" else None,
        kv_quant="int8" if tier == "int8" else None)
    prompt = (np.arange(40, dtype=np.int32) * 3) % cfg.vocab

    ref = LLM(_paged(cfg, params, scfg=scfg()))
    want = ref.submit(prompt, max_tokens=16, rid=0).result()

    src = LLM(_paged(cfg, params, scfg=scfg()))
    h = src.submit(prompt, max_tokens=16, rid=0)
    while len(h.tokens) < 4:                 # into decode phase
        src.tick()
    found = src.engine.export_request(0)
    assert found is not None
    req, payload = found
    validate_payload(payload, page_size=16, transfer=True)
    assert payload["kind"] == "decode" and payload["kept"] == []
    assert len(payload["scores"]) == len(payload["park"])
    assert payload["register_prefix"] is True
    scale = quant.find_scale(payload["rows"])
    if tier == "int8":
        assert scale is not None and float(np.max(scale)) > 0.0, \
            "int8 payload lost its parked scales"
    else:
        assert scale is None or float(np.max(scale)) == 0.0
    # src side is closed: no pages, no payloads, nothing in flight
    assert src.engine.stats()["pool"].live == 0
    assert not src.engine.active and not src.engine.queue

    dst = _paged(cfg, params, scfg=scfg())
    dst.adopt(req, payload)
    for _ in range(500):
        dst.step()
        if not (dst.queue or dst.active):
            break
    assert req.out == want, f"round-trip lost parity:\n{req.out}\n{want}"
    if tier == "int8":
        acct = dst.backend.page_accounting()
        assert acct["quantize_events"] >= 0    # tracker restored, sane
    assert dst.stats()["pool"].live == 0


def test_adopt_recompute_replay(smoke_lm):
    """Adopt with no payload replays prompt + emitted tokens through
    chunked prefill — exact under greedy decode up to a bf16 argmax tie:
    the replay sums the emitted tokens' attention in prefill's order,
    not decode's, so the audit admits ``parity.TIE_ULPS``."""
    cfg, params = smoke_lm
    prompt = np.arange(24, dtype=np.int32) % cfg.vocab
    ref = LLM(_paged(cfg, params))
    want = ref.submit(prompt, max_tokens=10, rid=0).result()

    src = LLM(_paged(cfg, params))
    h = src.submit(prompt, max_tokens=10, rid=0)
    while len(h.tokens) < 3:
        src.tick()
    req, _payload = src.engine.export_request(0)
    emitted = list(req.out)
    dst = _paged(cfg, params)
    dst.adopt(req)                           # payload lost: recompute
    for _ in range(500):
        dst.step()
        if not (dst.queue or dst.active):
            break
    assert req.out[:len(emitted)] == emitted, "replay rewrote history"
    assert req.out == want or scen._greedy_tie(
        cfg, params, prompt, req.out, want, parity.TIE_ULPS)


# ---------------------------------------------------------------- the router

def test_disagg_parity(smoke_lm):
    cfg, params = smoke_lm
    msg = dscen.scenario_disagg_parity(
        _router_factory(cfg, params), _single_factory(cfg, params), cfg)
    assert msg.startswith("disagg-parity")


def test_disagg_observability(smoke_lm):
    """With live telemetry the handoff is visible end to end: transfer
    byte counters, recorder transfer_out/transfer_in events, timeline
    epochs, and the debug bundle's transfer + prefill-side artifacts."""
    cfg, params = smoke_lm
    tel = obs.Telemetry()
    router = _router_factory(cfg, params)(tel=tel)
    handles = dscen.run_router(router, dscen.prompts_for(cfg)[:3])
    snap = tel.metrics.snapshot()
    key = next((k for k in snap if "kv_transfer_bytes" in k), None)
    assert key is not None, f"no transfer bytes counter in {list(snap)}"
    kinds = {e["kind"] for e in tel.recorder.events()}
    assert {"transfer_out", "transfer_in"} <= kinds, kinds
    ep = [k for k, _ in handles[0].timeline.epochs()]
    assert "transfer_out" in ep and "transfer_in" in ep, ep
    assert ep.index("transfer_out") < ep.index("transfer_in")
    m = router.metrics()
    assert m["requests"] == 3 and m["ttft_p50_ms"] is not None
    assert m["engine"]["transfer"]["n_transfers"] == 3

    out = router.debug_bundle("disagg_bundle_test")
    try:
        names = {p.name for p in pathlib.Path(out).iterdir()}
        assert {"transfer.json", "accounting_prefill.json",
                "accounting.json", "recorder.jsonl"} <= names, names
    finally:
        import shutil
        shutil.rmtree(out, ignore_errors=True)


def test_disagg_host_staging_parity(smoke_lm):
    """The host-staged fabric mode (deep-copied leaves — a
    serialization boundary) lands the same tokens as device staging."""
    cfg, params = smoke_lm
    prompts = dscen.prompts_for(cfg)[:3]
    make = _router_factory(cfg, params)
    dev = {h.rid: h.tokens
           for h in dscen.run_router(make(), prompts)}
    host = {h.rid: h.tokens
            for h in dscen.run_router(make(staging="host"), prompts)}
    assert dev == host


def test_disagg_cow_shared_prefix(smoke_lm):
    """Identical prompts cross the fabric once: the first import
    uploads and prefix-registers its full pages, the second COW-shares
    them on the decode pool instead of re-uploading."""
    cfg, params = smoke_lm
    router = _router_factory(cfg, params)()
    prompt = (np.arange(40, dtype=np.int32) * 3) % cfg.vocab
    h0 = router.submit(prompt, max_tokens=12, rid=0)
    h1 = router.submit(prompt, max_tokens=12, rid=1)
    shared_seen = 0
    steps = 0
    while router.has_work() and steps < 4000:
        router.tick()
        shared_seen = max(
            shared_seen,
            router.engine.backend.page_accounting()["shared"])
        steps += 1
    assert h0.done and h1.done
    assert h0.tokens == h1.tokens and len(h0.tokens) == 12
    assert router.transfer.n_transfers == 2
    assert shared_seen > 0, \
        "identical prefixes never COW-shared on the decode pool"
    dscen.assert_drained(router)


def test_disagg_transfer_chaos(smoke_lm):
    cfg, params = smoke_lm

    def tie(prompt, got, want):
        return scen._greedy_tie(cfg, params, prompt, got, want)

    msg = dscen.scenario_disagg_chaos(
        _router_factory(cfg, params), _single_factory(cfg, params), cfg,
        greedy_tie=tie)
    assert msg.startswith("disagg-chaos")


def test_disagg_transfer_quarantine(smoke_lm):
    """Past the retry budget a transfer-faulted request is quarantined
    FAILED on the decode side; co-resident requests are undisturbed and
    neither pool leaks."""
    cfg, params = smoke_lm
    plan = FaultPlan(schedule={"transfer": {0}})
    router = _router_factory(cfg, params)(fault_plan=plan,
                                          transfer_retries=0)
    prompts = dscen.prompts_for(cfg)[:3]
    handles = [router.submit(p, max_tokens=10, rid=i)
               for i, p in enumerate(prompts)]
    dscen.drive_checked_disagg(router)
    outcomes = sorted(h.outcome for h in handles)
    assert outcomes.count("failed") == 1, outcomes
    assert outcomes.count("done") == 2, outcomes
    dscen.assert_drained(router)


def test_disagg_cancel_and_deadline(smoke_lm):
    """cancel() works wherever the request is — still prefilling, or
    decoding on the far instance — and a zero deadline expires without
    ever crossing the fabric; no pages leak on either side."""
    cfg, params = smoke_lm
    router = _router_factory(cfg, params)()
    long_p = (np.arange(40, dtype=np.int32) * 5) % cfg.vocab
    h0 = router.submit(long_p, max_tokens=16, rid=0)
    h1 = router.submit(np.arange(8, dtype=np.int32), max_tokens=16,
                       rid=1)
    h2 = router.submit(np.arange(6, dtype=np.int32), max_tokens=16,
                       rid=2, deadline_ms=0.0)
    router.tick()                    # h1 likely mid/post prefill
    assert h0.cancel(), "cancel on the prefill side failed"
    while not h1.tokens and router.has_work():
        router.tick()                # h1 lands on the decode side
    assert h1.cancel(), "cancel on the decode side failed"
    assert not h1.cancel(), "double-cancel must return False"
    dscen.drive_checked_disagg(router)
    assert h0.outcome == "cancelled"
    assert h1.outcome == "cancelled"
    assert h2.outcome == "expired" and h2.tokens == []
    dscen.assert_drained(router)


def test_disagg_from_config(smoke_lm):
    """The one-call constructor builds a working pair around shared
    params."""
    cfg, params = smoke_lm
    router = DisaggRouter.from_config(cfg, params=params)
    h = router.submit(np.arange(10, dtype=np.int32), max_tokens=6)
    dscen.drive_checked_disagg(router)
    assert h.outcome == "done" and len(h.tokens) == 6
    assert router.transfer.n_transfers == 1
    dscen.assert_drained(router)


def test_spatial_to_paged_disagg():
    """Spatial(2-shard) prefill into paged decode — the backend-uniform
    wire format crossing backend kinds — on a fake-device mesh in a
    subprocess."""
    out = subprocess.run(
        [sys.executable, str(PROGS / "disagg_prog.py"), "2"],
        capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, \
        f"disagg_prog failed:\nSTDOUT:{out.stdout}\n" \
        f"STDERR:{out.stderr[-3000:]}"
    assert "DISAGG_OK" in out.stdout
