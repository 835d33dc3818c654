"""DisaggRouter: the prefill/decode-disaggregated serving front door.

Serving mixes two phase profiles that want opposite tunings: prefill is
compute-bound and batches wide token budgets; decode is memory-bound
and wants a big batch over a deep pool with a narrow DLZS hot set. A
single instance compromises both — and long prefills stall co-resident
decodes behind the shared dispatch. ``DisaggRouter`` runs two engine
instances instead and moves each request across at the phase boundary:

    submit ──▶ prefill instance (large ``prefill_tokens`` budget)
                  │  first token emitted (prefill complete)
                  ▼
               KVTransfer.begin/complete  (flat-payload page handoff)
                  │
                  ▼
               decode instance (big ``max_batch``, deep pool,
               ``decode_hot_width`` sparsity) ──▶ finished

It IS an ``LLM`` — same ``submit()/tick()/metrics()/debug_bundle()``
surface — overriding only the three engine touch-points the base class
exposes (``_submit_engine``/``_step_engines``/``_cancel_engine``). One
``obs.Telemetry`` is shared by both instances, so a request has a
single timeline stamped across its whole journey (admit on the prefill
side, ``transfer_out``/``transfer_in`` at the hop, per-token stamps on
the decode side).

Handoff state machine (per request)::

    PREFILLING ──prefill done──▶ ELIGIBLE ──begin──▶ STAGED
       │                            │                  │ complete
       │ preempted to decode-kind   │ export fault     ▼
       │ payload / recompute mode   ▼                LANDED (decode)
       └──────▶ ELIGIBLE         RECOMPUTE ──adopt(None)──▶ decode
                                    │ retries exhausted
                                    └──▶ FAILED (terminal)

Eligibility is checked after every prefill tick: a bound slot past its
prefill (``slot not in _pf``), a swapped waiting entry whose parked
payload is decode-kind, or a recompute-mode waiting entry that already
emitted tokens. Requests still mid-prefill — including those preempted
with prefill-kind payloads — stay on the prefill instance.

Conservation holds across BOTH pools plus the fabric every tick:
export closes the source side (no ``kept`` refs travel), staged
payloads hold host bytes only, and adopt re-enters the destination
through the audited swap-in path. A transfer fault therefore loses
bytes, never pages: the retained request replays prompt + emitted
tokens through decode-side chunked prefill (exact under greedy
decode), gated by a ``RetryGovernor``.
"""

from __future__ import annotations

from typing import Optional

from repro.serving.api import LLM
from repro.serving.disagg.transfer import KVTransfer
from repro.serving.engine import Request
from repro.serving.swap_policy import RetryGovernor


class DisaggRouter(LLM):
    """Front door over a (prefill, decode) instance pair.

    ``prefill_engine``/``decode_engine`` are ``EngineCore`` instances
    (any swap-format backend; they need not match — spatial prefill
    into paged decode works). ``fault_plan`` injects at the
    ``transfer`` seam; ``staging`` picks the fabric mode (see
    ``KVTransfer``). The decode instance is ``self.engine`` — the base
    class serves records, metrics and bundles from it."""

    def __init__(self, prefill_engine, decode_engine, *, telemetry=None,
                 fault_plan=None, staging: str = "device",
                 transfer_retries: int = 2):
        super().__init__(decode_engine, telemetry=telemetry)
        self.prefill = prefill_engine
        # one telemetry identity across both instances: the engines
        # stamp the SAME timeline objects the router's records wrap
        if hasattr(prefill_engine, "attach_telemetry"):
            prefill_engine.attach_telemetry(self.tel)
        self.transfer = KVTransfer(prefill_engine, decode_engine,
                                   plan=fault_plan, telemetry=self.tel,
                                   staging=staging)
        self.governor = RetryGovernor(max_retries=transfer_retries)

    # -- construction --------------------------------------------------------

    @classmethod
    def from_config(cls, model_cfg, *, backend: str = "paged",
                    prefill_backend: Optional[str] = None,
                    params=None, shards: int = 2,
                    prefill_engine_cfg=None, decode_engine_cfg=None,
                    prefill_sched_cfg=None, decode_sched_cfg=None,
                    rng=None, telemetry=None, fault_plan=None,
                    staging: str = "device") -> "DisaggRouter":
        """Build the instance pair around ONE set of params.

        ``backend`` picks the decode instance ("paged" or "spatial");
        ``prefill_backend`` the prefill side (default: same as
        ``backend``). Default tunings encode the disaggregation split:
        the prefill instance runs a small batch with the "auto" prefill
        token budget; the decode instance runs the full batch with
        decode-width sparsity and no prefill budget (its only prefills
        are recompute fallbacks)."""
        import jax

        from repro.models import lm
        from repro.serving.paged import PagedEngineCfg, PagedServingEngine
        from repro.serving.scheduler import SchedulerCfg

        rng = rng if rng is not None else jax.random.PRNGKey(0)
        if params is None:
            params = lm.init(rng, model_cfg)

        def build(kind, engine_cfg, sched_cfg):
            if kind == "paged":
                return PagedServingEngine(
                    model_cfg, params, engine_cfg or PagedEngineCfg(),
                    sched_cfg, rng=rng)
            if kind == "spatial":
                from repro.spatial.engine import (SpatialEngineCfg,
                                                  SpatialServingEngine)
                return SpatialServingEngine(
                    model_cfg, params,
                    engine_cfg or SpatialEngineCfg(n_shards=shards),
                    sched_cfg, rng=rng)
            raise ValueError(f"unknown disagg backend {kind!r}: "
                             "choose from ('paged', 'spatial')")

        pre = build(prefill_backend or backend, prefill_engine_cfg,
                    prefill_sched_cfg or SchedulerCfg())
        dec = build(backend, decode_engine_cfg,
                    decode_sched_cfg or SchedulerCfg())
        return cls(pre, dec, telemetry=telemetry, fault_plan=fault_plan,
                   staging=staging)

    # -- the LLM engine seam -------------------------------------------------

    def _submit_engine(self, req: Request) -> None:
        self.prefill.submit(req)

    def _cancel_engine(self, rid: int, *, reason: str) -> bool:
        if self.prefill.cancel(rid, reason=reason):
            return True
        req = self.transfer.drop(rid)
        if req is not None:
            # mid-hop: no pages are held anywhere — stamp terminal on
            # the decode side so the finished stream surfaces it
            self.engine.exec_abort(req, "cancelled", reason)
            return True
        return self.engine.cancel(rid, reason=reason)

    def _step_engines(self) -> list[Request]:
        finished = list(self.prefill.step() or ())
        for rid in self._handoff_candidates():
            self._handoff(rid)
        finished += self.engine.step() or []
        return finished

    # -- handoff -------------------------------------------------------------

    def _handoff_candidates(self) -> list[int]:
        """Requests done with prefill on the prefill instance: decoding
        in a slot, parked with a decode-kind payload, or waiting in
        recompute mode with tokens already emitted."""
        pre = self.prefill
        rids = [req.rid for slot, req in pre.active.items()
                if slot not in pre._pf]
        for w in pre.sched.waiting:
            if w.swapped:
                payload = pre.swap_area.peek(w.req.rid)
                if payload is not None and payload.get("kind") == "decode":
                    rids.append(w.req.rid)
            elif w.req.out:
                rids.append(w.req.rid)
        return rids

    def _handoff(self, rid: int) -> None:
        try:
            summary = self.transfer.begin(rid)
        except Exception:
            req = self.transfer.drop(rid)
            if req is None:
                return
            # the payload is gone; the only retry is a decode-side
            # recompute replay (backoff is meaningless for a one-way
            # hop, so the governor only gates the attempt count)
            if self.governor.record_fault(rid) is None:
                self.engine.exec_abort(req, "failed", "transfer")
            else:
                self.engine.adopt(req)
            return
        if summary is None:     # finished/cancelled under our feet
            return
        self.transfer.complete(rid)
        self.governor.forget(rid)

    # -- surface -------------------------------------------------------------

    def has_work(self) -> bool:
        pre = self.prefill
        return bool(pre.queue or pre.active
                    or getattr(pre, "_terminal", ())
                    or self.transfer.in_flight()
                    or super().has_work())

    def stats(self) -> dict:
        # decode-side pool/sched stay top-level: base-class metrics()
        # reads occupancy and preemptions from there
        st = self.engine.stats()
        st["prefill"] = self.prefill.stats()
        st["transfer"] = self.transfer.stats()
        return st

    def debug_bundle(self, out_dir: Optional[str] = None) -> str:
        import json
        import os

        out = super().debug_bundle(out_dir)
        if hasattr(self.prefill, "accounting_snapshot"):
            with open(os.path.join(out, "accounting_prefill.json"),
                      "w") as f:
                json.dump(self.prefill.accounting_snapshot(), f,
                          indent=2, default=repr)
                f.write("\n")
        with open(os.path.join(out, "transfer.json"), "w") as f:
            json.dump(self.transfer.stats(), f, indent=2, default=repr)
            f.write("\n")
        return out
