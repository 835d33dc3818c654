"""Scheduler policy unit tests against a fake executor (no device work),
plus SwapArea bookkeeping and paged-attention backend dispatch."""

import numpy as np
import pytest

from repro.kvcache import SwapArea
from repro.kvcache import paged_attention as pa
from repro.serving import Request
from repro.serving.scheduler import (AUTO_PREFILL_CHUNKS, AdmissionCfg,
                                     BudgetController, ExecFault, NeedPages,
                                     Scheduler, SchedulerCfg,
                                     resolve_prefill_tokens, sla_priority)
from repro.serving.swap_policy import RetryGovernor


# one fake chunk's budget width: a ``prefill_tokens`` of ONE_CHUNK packs
# exactly one chunk per tick
ONE_CHUNK = 16


class FakeEngine:
    """Host-only executor: a page budget instead of a device pool.

    Each sequence costs pages only while running; prefill takes
    ``chunks`` chunks of one page each, decode grows one page per step
    until ``decode_steps`` run out. NeedPages fires exactly like the real
    engine: when the next page would exceed capacity. Prefill speaks the
    batched varlen protocol: every chunk costs ONE_CHUNK budget tokens
    (overridable per rid via ``widths``) and one capacity page, and a
    batch allocates all-or-nothing like the real engine's phase A.
    """

    def __init__(self, capacity: int, slots: int, chunks: dict,
                 decode_steps: dict, widths=None):
        self.capacity = capacity
        self.widths = widths or {}
        self.free = list(range(slots))
        self.chunks = chunks                  # rid -> prefill chunk count
        self.decode_steps = decode_steps      # rid -> decode steps to run
        self.pages: dict[int, int] = {}       # slot -> pages held
        self.state: dict[int, dict] = {}      # slot -> progress
        self.swapped: dict[int, dict] = {}    # rid -> parked progress
        self.log: list = []
        self.preempt_held: list[int] = []     # pages each victim held

    def _used(self):
        return sum(self.pages.values())

    def free_slot_available(self):
        return bool(self.free)

    def exec_admit(self, req):
        slot = self.free.pop(0)
        self.pages[slot] = 0
        self.state[slot] = {"req": req, "chunk": 0,
                            "dec": self.decode_steps[req.rid]}
        self.log.append(("admit", req.rid))
        return slot

    def prefill_chunks_left(self, slot):
        st = self.state[slot]
        return self.chunks[st["req"].rid] - st["chunk"]

    def held_pages(self, slot, shard=None):
        return self.pages.get(slot, 0)

    def pending_chunk_widths(self, slot):
        w = self.widths.get(self.state[slot]["req"].rid, ONE_CHUNK)
        return [w] * self.prefill_chunks_left(slot)

    def exec_prefill_chunk_batch(self, batch):
        if self._used() + sum(n for _, n in batch) > self.capacity:
            raise NeedPages(batch[0][0])
        self.log.append(("batch", sorted(
            self.state[s]["req"].rid for s, _ in batch)))
        done = []
        for slot, n in batch:
            st = self.state[slot]
            n = max(1, min(n, self.prefill_chunks_left(slot)))
            self.pages[slot] += n
            st["chunk"] += n
            for _ in range(n):
                self.log.append(("chunk", st["req"].rid))
            if self.prefill_chunks_left(slot) == 0:
                done.append(slot)
        return done

    def exec_decode(self):
        decode = [s for s in self.state
                  if self.prefill_chunks_left(s) == 0]
        for slot in decode:                   # grow before the step —
            st = self.state[slot]             # idempotent across retries,
            if not st.get("grown"):           # like the real block table
                if self._used() + 1 > self.capacity:
                    raise NeedPages(slot)
                self.pages[slot] += 1
                st["grown"] = True
        finished = []
        for slot in decode:
            st = self.state[slot]
            st["grown"] = False
            st["dec"] -= 1
            if st["dec"] <= 0:
                self.pages.pop(slot)
                self.state.pop(slot)
                self.free.append(slot)
                finished.append((slot, st["req"]))
        self.log.append(("decode", sorted(st["req"].rid for st in
                                          self.state.values())))
        return finished

    def exec_preempt(self, slot, swap):
        st = self.state.pop(slot)
        held = self.pages.pop(slot)
        self.free.append(slot)
        self.preempt_held.append(held)
        self.log.append(("preempt", st["req"].rid, swap))
        if swap:
            self.swapped[st["req"].rid] = {"st": st, "pages": held}
            return True
        return False

    def exec_swap_in(self, req):
        parked = self.swapped[req.rid]
        if self._used() + parked["pages"] > self.capacity:
            return None
        slot = self.free.pop(0)
        parked = self.swapped.pop(req.rid)
        self.pages[slot] = parked["pages"]
        self.state[slot] = parked["st"]
        self.log.append(("swap_in", req.rid))
        return slot


class SheddingFakeEngine(FakeEngine):
    """FakeEngine with lazy cold-page swap: everything but one hot (tail)
    page of a decoding sequence is sheddable, one page per call."""

    def __init__(self, *a, **kw):
        super().__init__(*a, **kw)
        self.shed_log: list[int] = []

    def exec_shed_cold(self, slot, shard=None):
        if self.prefill_chunks_left(slot) > 0:       # mid-prefill: no
            return 0                                 # past pages may leave
        if self.pages.get(slot, 0) <= 1:
            return 0
        self.pages[slot] -= 1
        self.shed_log.append(self.state[slot]["req"].rid)
        return 1


def _req(rid, priority=0):
    return Request(rid=rid, prompt=np.arange(4, dtype=np.int32),
                   priority=priority, out=[])


def _drain(sched, ex, max_ticks=200):
    done = []
    for _ in range(max_ticks):
        if not sched.has_work():
            return done
        done += sched.tick(ex)
    raise AssertionError("scheduler did not drain (deadlock?)")


def test_scheduler_prefill_interleaves_with_decode():
    """A long prefill advances one chunk per tick while an admitted short
    request decodes — decode never waits for the whole prompt."""
    ex = FakeEngine(capacity=100, slots=2,
                    chunks={0: 6, 1: 1}, decode_steps={0: 2, 1: 6})
    sched = Scheduler(SchedulerCfg(prefill_tokens=ONE_CHUNK))
    sched.submit(_req(0))                        # long prompt, first in line
    sched.submit(_req(1))
    done = _drain(sched, ex)
    assert {r.rid for r in done} == {0, 1}
    # request 1 (one chunk) decoded while request 0 was still prefilling
    first_decode = next(i for i, e in enumerate(ex.log)
                        if e[0] == "decode" and 1 in e[1])
    later_chunks = [e for e in ex.log[first_decode:] if e == ("chunk", 0)]
    assert later_chunks, "long prefill should still be running"


def test_scheduler_shortest_prefill_first():
    """Within a priority level the prompt with fewer remaining chunks
    prefills first (bounds short-request TTFT)."""
    ex = FakeEngine(capacity=100, slots=2,
                    chunks={0: 5, 1: 1}, decode_steps={0: 1, 1: 1})
    sched = Scheduler(SchedulerCfg(prefill_tokens=ONE_CHUNK))
    sched.submit(_req(0))
    sched.submit(_req(1))
    sched.tick(ex)
    assert ("chunk", 1) in ex.log                # short one went first
    assert ("chunk", 0) not in ex.log


def test_scheduler_aging_unstarves_long_prefill():
    """SJF alone would park a long prompt behind a stream of short ones;
    aging forces a chunk of the long prefill through every
    ``starvation_ticks`` ticks."""
    chunks = {0: 6}
    decode = {0: 1}
    for rid in range(1, 9):                      # sustained short stream
        chunks[rid] = 1
        decode[rid] = 1
    ex = FakeEngine(capacity=100, slots=3, chunks=chunks,
                    decode_steps=decode)
    sched = Scheduler(SchedulerCfg(prefill_tokens=ONE_CHUNK,
                                   starvation_ticks=2))
    for rid in sorted(chunks):
        sched.submit(_req(rid))
    done = _drain(sched, ex)
    assert {r.rid for r in done} == set(chunks)
    # the long prompt's chunks interleave with the short stream instead of
    # all trailing it: at least one lands before the last short's chunk
    chunk_rids = [e[1] for e in ex.log if e[0] == "chunk"]
    last_short = max(i for i, r in enumerate(chunk_rids) if r != 0)
    assert any(r == 0 for r in chunk_rids[:last_short]), \
        "long prefill was starved until the short stream drained"


def test_scheduler_token_budget_batches_prefill():
    """With ``prefill_tokens`` set, ONE batched dispatch per tick advances
    every prefilling sequence that packs under the budget — not one
    dispatch per sequence — and everything still completes."""
    ex = FakeEngine(capacity=100, slots=4,
                         chunks={0: 2, 1: 2, 2: 2, 3: 2},
                         decode_steps={r: 2 for r in range(4)})
    sched = Scheduler(SchedulerCfg(chunk_pages=1, prefill_tokens=48))
    for rid in range(4):
        sched.submit(_req(rid))
    done = _drain(sched, ex)
    assert {r.rid for r in done} == {0, 1, 2, 3}
    batches = [e[1] for e in ex.log if e[0] == "batch"]
    assert batches, "no batched dispatch was issued"
    # 48-token budget = 3 chunks per dispatch: the first tick packs 3
    # sequences into one dispatch
    assert len(batches[0]) == 3
    # one dispatch per tick: #batches < #chunks issued
    n_chunks = sum(len(b) for b in batches)
    assert len(batches) < n_chunks


def test_scheduler_budget_head_chunk_always_advances():
    """A chunk wider than the whole budget still makes progress — it is
    dispatched alone (the flat buffer is sized to hold any single
    chunk)."""
    ex = FakeEngine(capacity=100, slots=2, chunks={0: 1, 1: 1},
                         decode_steps={0: 1, 1: 1},
                         widths={0: 128, 1: 16})
    sched = Scheduler(SchedulerCfg(chunk_pages=1, prefill_tokens=32))
    sched.submit(_req(0))
    sched.submit(_req(1))
    done = _drain(sched, ex)
    assert {r.rid for r in done} == {0, 1}
    batches = [e[1] for e in ex.log if e[0] == "batch"]
    # the 128-wide chunk went alone; the 16-wide one got its own dispatch
    assert [1] in batches and [0] in batches


def test_scheduler_batched_prefill_pressure_preempts_and_finishes():
    """NeedPages from a batched dispatch picks a victim and retries with a
    re-packed batch; overload degrades, never deadlocks."""
    ex = FakeEngine(capacity=4, slots=3,
                         chunks={0: 1, 1: 1, 2: 1},
                         decode_steps={0: 3, 1: 3, 2: 3})
    sched = Scheduler(SchedulerCfg(chunk_pages=1, prefill_tokens=64,
                                   swap=True))
    for rid in range(3):
        sched.submit(_req(rid))
    done = _drain(sched, ex)
    assert {r.rid for r in done} == {0, 1, 2}
    assert sched.stats.preemptions > 0


def test_scheduler_lazy_shed_keeps_victim_running():
    """Pressure relief via lazy cold-page swap: with ``lazy_swap`` the
    scheduler first asks victims to shed cold pages — sequences keep
    decoding on their hot sets, nobody is stopped, and the shed counter
    (not the preemption counter) moves."""
    ex = SheddingFakeEngine(capacity=4, slots=2, chunks={0: 1, 1: 1},
                            decode_steps={0: 4, 1: 4})
    sched = Scheduler(SchedulerCfg(prefill_tokens=ONE_CHUNK, swap=True,
                                   lazy_swap=True))
    sched.submit(_req(0))
    sched.submit(_req(1))
    done = _drain(sched, ex)
    assert {r.rid for r in done} == {0, 1}
    assert sched.stats.sheds > 0
    assert sched.stats.preemptions == 0
    assert not [e for e in ex.log if e[0] == "preempt"]
    assert ex.shed_log                       # pages actually left victims


def test_scheduler_lazy_shed_falls_back_to_preemption():
    """When nothing is sheddable (every page hot), lazy mode must still
    fall back to ordinary preemption rather than spin."""
    ex = FakeEngine(capacity=4, slots=3,
                    chunks={0: 1, 1: 1, 2: 1},
                    decode_steps={0: 3, 1: 3, 2: 3})
    ex.exec_shed_cold = lambda slot, shard=None: 0
    sched = Scheduler(SchedulerCfg(prefill_tokens=ONE_CHUNK, swap=True,
                                   lazy_swap=True))
    for rid in range(3):
        sched.submit(_req(rid))
    done = _drain(sched, ex)
    assert {r.rid for r in done} == {0, 1, 2}
    assert sched.stats.sheds == 0
    assert sched.stats.preemptions > 0


def test_scheduler_preempts_lowest_priority_newest():
    # per-sequence worst case (1 prefill + 3 decode pages) fits capacity —
    # the invariant the real engine's submit() enforces
    ex = FakeEngine(capacity=4, slots=3,
                    chunks={0: 1, 1: 1, 2: 1},
                    decode_steps={0: 3, 1: 3, 2: 3})
    sched = Scheduler(SchedulerCfg(prefill_tokens=ONE_CHUNK, swap=True))
    sched.submit(_req(0, priority=1))
    sched.submit(_req(1, priority=0))            # victim: low prio...
    sched.submit(_req(2, priority=0))            # ...and newest
    done = _drain(sched, ex)
    assert {r.rid for r in done} == {0, 1, 2}
    victims = [e[1] for e in ex.log if e[0] == "preempt"]
    assert victims and 0 not in victims          # high priority never evicted
    # page-aware victim selection: preempting a page-less slot frees
    # nothing, so every victim must have held pages
    assert all(h > 0 for h in ex.preempt_held)
    assert sched.stats.preemptions == len(victims)
    assert sched.stats.resumes >= 1              # swapped work came back


def test_scheduler_low_priority_arrival_cannot_evict_high():
    """A low-priority request that cannot get pages defers itself; it
    must never preempt a strictly higher-priority running sequence."""
    # rid 0 (priority 5) needs the whole pool; rid 1 (priority 0) arrives
    # while it runs and cannot fit until it finishes
    ex = FakeEngine(capacity=4, slots=2, chunks={0: 1, 1: 1},
                    decode_steps={0: 3, 1: 3})
    sched = Scheduler(SchedulerCfg(prefill_tokens=ONE_CHUNK, swap=True))
    sched.submit(_req(0, priority=5))
    sched.submit(_req(1, priority=0))
    done = _drain(sched, ex)
    assert {r.rid for r in done} == {0, 1}
    victims = [e[1] for e in ex.log if e[0] == "preempt"]
    assert victims and 0 not in victims          # rid 1 defers itself


def test_scheduler_recompute_mode_requeues():
    ex = FakeEngine(capacity=3, slots=2, chunks={0: 1, 1: 1},
                    decode_steps={0: 2, 1: 2})
    sched = Scheduler(SchedulerCfg(prefill_tokens=ONE_CHUNK, swap=False))
    sched.submit(_req(0))
    sched.submit(_req(1))
    done = _drain(sched, ex)
    assert {r.rid for r in done} == {0, 1}
    assert sched.stats.recomputes == sched.stats.preemptions > 0
    assert sched.stats.swap_outs == 0


def test_scheduler_blocked_swap_in_holds_the_line():
    """A preempted sequence resumes before any later arrival of the same
    priority is admitted — even across ticks where the swap-in does not
    fit yet but the fresh request would (no starvation of swapped work)."""
    ex = FakeEngine(capacity=4, slots=2, chunks={0: 2, 1: 1, 2: 1},
                    decode_steps={0: 2, 1: 3, 2: 1})
    sched = Scheduler(SchedulerCfg(prefill_tokens=ONE_CHUNK, swap=True))
    for rid in (0, 1, 2):
        sched.submit(_req(rid))
    done = _drain(sched, ex)
    assert {r.rid for r in done} == {0, 1, 2}
    assert ("preempt", 1, True) in ex.log        # rid 1 was swapped out...
    assert ex.log.index(("swap_in", 1)) < ex.log.index(("admit", 2))


def test_scheduler_sla_classes_map_to_priority():
    """The external QoS input: an SLA class on the request becomes a
    scheduler priority at submit — interactive outranks standard outranks
    batch — and an explicit priority is what preemption ranks by."""
    assert sla_priority("interactive") > sla_priority("standard") \
        > sla_priority("batch")
    with pytest.raises(ValueError, match="SLA"):
        sla_priority("platinum")
    # batch traffic is the preemption victim; interactive never is
    ex = FakeEngine(capacity=4, slots=3,
                    chunks={0: 1, 1: 1, 2: 1},
                    decode_steps={0: 3, 1: 3, 2: 3})
    sched = Scheduler(SchedulerCfg(prefill_tokens=ONE_CHUNK, swap=True))
    sched.submit(Request(rid=0, prompt=np.arange(4, dtype=np.int32),
                         sla="interactive", out=[]))
    sched.submit(Request(rid=1, prompt=np.arange(4, dtype=np.int32),
                         sla="batch", out=[]))
    sched.submit(Request(rid=2, prompt=np.arange(4, dtype=np.int32),
                         sla="batch", out=[]))
    done = _drain(sched, ex)
    assert {r.rid for r in done} == {0, 1, 2}
    victims = [e[1] for e in ex.log if e[0] == "preempt"]
    assert victims and 0 not in victims


class ShardedFakeEngine(FakeEngine):
    """FakeEngine with two page shards: even slots hold pages on shard 0,
    odd slots on shard 1 (a stand-in for the spatial engine's striping)."""

    def __init__(self, *a, **kw):
        super().__init__(*a, **kw)
        self.last_need_shard = None
        self.victim_shards_ok: list[bool] = []

    def held_pages(self, slot, shard=None):
        if shard is not None and slot % 2 != shard:
            return 0
        return self.pages.get(slot, 0)

    def exec_decode(self):
        decode = [s for s in self.state
                  if self.prefill_chunks_left(s) == 0]
        for slot in decode:        # growth raises with the slot's shard;
            st = self.state[slot]  # super() then sees grown=True and skips
            if not st.get("grown"):
                if self._used() + 1 > self.capacity:
                    self.last_need_shard = slot % 2
                    raise NeedPages(slot, shard=slot % 2)
                self.pages[slot] += 1
                st["grown"] = True
        return super().exec_decode()

    def exec_preempt(self, slot, swap):
        if self.last_need_shard is not None:
            self.victim_shards_ok.append(slot % 2 == self.last_need_shard)
        return super().exec_preempt(slot, swap)


def test_scheduler_shard_tagged_pressure_picks_shard_victim():
    """A NeedPages tagged with a shard must evict a victim that frees
    pages on THAT shard — evicting elsewhere would not unblock the needy
    sequence (the spatial engine's per-shard pools)."""
    # per-sequence worst case (1 prefill + 4 decode pages) fits capacity
    ex = ShardedFakeEngine(capacity=5, slots=3,
                           chunks={0: 1, 1: 1, 2: 1},
                           decode_steps={0: 4, 1: 4, 2: 4})
    sched = Scheduler(SchedulerCfg(prefill_tokens=ONE_CHUNK, swap=True))
    for rid in (0, 1, 2):
        sched.submit(_req(rid))
    done = _drain(sched, ex)
    assert {r.rid for r in done} == {0, 1, 2}
    assert sched.stats.preemptions > 0
    assert all(h > 0 for h in ex.preempt_held)
    # every shard-tagged preemption freed pages on the starved shard
    assert ex.victim_shards_ok and all(ex.victim_shards_ok)


def test_budget_controller_tracks_tick_times():
    """The ``prefill_tokens="auto"`` EMA controller: fast ticks grow the
    packing budget toward the compiled buffer width, slow ticks shrink
    it toward one chunk — always quantized and inside [lo, hi]."""
    ctl = BudgetController(lo=32, hi=256, quantum=16, target_s=0.1)
    assert ctl.budget == 256                 # optimistic start
    for _ in range(8):                       # very slow hardware:
        ctl.observe(1.0, 64)                 # 1 s for 64 tokens
        assert ctl.lo <= ctl.budget <= ctl.hi
        assert ctl.budget % 16 == 0
    assert ctl.budget == 32                  # clamped to the floor
    for _ in range(16):                      # very fast hardware
        ctl.observe(0.0001, 64)
    assert ctl.budget == 256                 # back to the ceiling
    # EMA smooths: one 100x OS-stall outlier must not collapse the budget
    ctl.observe(0.01, 64)
    assert ctl.budget == 256
    # degenerate observations are ignored
    b = ctl.budget
    ctl.observe(0.5, 0)
    ctl.observe(-1.0, 64)
    assert ctl.budget == b


def test_budget_controller_steers_to_target():
    """At a stable per-token cost the budget converges to ~target_s
    worth of tokens (quantized)."""
    ctl = BudgetController(lo=16, hi=4096, quantum=16, target_s=0.1)
    for _ in range(32):
        ctl.observe(0.001 * ctl.budget, ctl.budget)   # 1 ms per token
    assert ctl.budget == 96                  # 0.1 s / 1 ms -> 100 -> 96


def test_prefill_tokens_auto_resolution_and_scheduler_wiring():
    """"auto" resolves to an AUTO_PREFILL_CHUNKS-chunk buffer; the
    scheduler self-installs a controller (with placeholder bounds until
    the engine attaches real ones) and a full fake-engine run completes
    with the controller live."""
    assert resolve_prefill_tokens(
        SchedulerCfg(chunk_pages=2, prefill_tokens="auto"), 16) \
        == AUTO_PREFILL_CHUNKS * 2 * 16
    assert resolve_prefill_tokens(
        SchedulerCfg(chunk_pages=2, prefill_tokens=48), 16) == 48

    ex = FakeEngine(capacity=100, slots=4,
                         chunks={0: 2, 1: 2, 2: 2, 3: 2},
                         decode_steps={r: 2 for r in range(4)})
    sched = Scheduler(SchedulerCfg(chunk_pages=1, prefill_tokens="auto"))
    assert sched.budget_ctl is not None
    sched.attach_budget(lo=16, hi=64, quantum=16)
    assert sched.prefill_budget() == 64
    for rid in range(4):
        sched.submit(_req(rid))
    done = _drain(sched, ex)
    assert {r.rid for r in done} == {0, 1, 2, 3}
    # the controller saw real tick observations and stayed in bounds
    assert 16 <= sched.budget_ctl.budget <= 64


class AbortLogFakeEngine(FakeEngine):
    """FakeEngine recording the terminal aborts the scheduler issues
    (quarantines and admission sheds)."""

    def __init__(self, *a, **kw):
        super().__init__(*a, **kw)
        self.aborts: list[tuple[int, str, str]] = []

    def exec_abort(self, req, outcome, reason):
        self.aborts.append((req.rid, outcome, reason))


class FailingSwapInFakeEngine(AbortLogFakeEngine):
    """Swap-in fails ``fail_swap_ins`` times; the parked payload is
    discarded on failure (the real engine's rollback contract), so the
    scheduler's only road back is recompute-from-prompt."""

    def __init__(self, *a, fail_swap_ins=1, **kw):
        super().__init__(*a, **kw)
        self.fail_swap_ins = fail_swap_ins

    def exec_swap_in(self, req):
        if self.fail_swap_ins > 0:
            self.fail_swap_ins -= 1
            self.swapped.pop(req.rid)          # payload already discarded
            self.log.append(("swap_in_fault", req.rid))
            raise ExecFault([], RuntimeError("payload corrupt"),
                            "swap_in", rid=req.rid)
        return super().exec_swap_in(req)


class DecodeFaultFakeEngine(AbortLogFakeEngine):
    """Decode always dies on ``bad_rid``'s slot — the unrecoverable-
    request case that must exhaust the retry budget and quarantine."""

    def __init__(self, *a, bad_rid=0, **kw):
        super().__init__(*a, **kw)
        self.bad_rid = bad_rid

    def exec_decode(self):
        for slot, st in self.state.items():
            if (st["req"].rid == self.bad_rid
                    and self.prefill_chunks_left(slot) == 0):
                raise ExecFault([slot], RuntimeError("nan"), "decode")
        return super().exec_decode()


def test_retry_governor_budget_and_backoff():
    """The fault budget is exact: ``max_retries`` linearly-backed-off
    retries, then None (quarantine); a clean finish resets the count."""
    gov = RetryGovernor(max_retries=2, backoff_ticks=3)
    assert gov.record_fault(7) == 3              # attempt 1
    assert gov.attempts(7) == 1
    assert gov.record_fault(7) == 6              # attempt 2
    assert gov.record_fault(7) is None           # budget spent
    gov.forget(7)
    assert gov.attempts(7) == 0
    assert gov.record_fault(7) == 3              # budget restored


def test_scheduler_failed_swap_in_falls_back_to_recompute_once():
    """A failed page-in consumes exactly one retry: the request re-enters
    as a recompute (fresh admit, page table rebuilt from the prompt),
    completes, and no page or parked payload leaks."""
    # the blocked-swap-in topology: rid 1 is swapped out under pressure
    # and must come back — here its one page-in attempt fails
    ex = FailingSwapInFakeEngine(capacity=4, slots=2,
                                 chunks={0: 2, 1: 1, 2: 1},
                                 decode_steps={0: 2, 1: 3, 2: 1},
                                 fail_swap_ins=1)
    sched = Scheduler(SchedulerCfg(prefill_tokens=ONE_CHUNK, swap=True,
                                   fault_retries=2))
    for rid in (0, 1, 2):
        sched.submit(_req(rid))
    done = _drain(sched, ex)
    assert {r.rid for r in done} == {0, 1, 2}
    assert ("preempt", 1, True) in ex.log        # parked under pressure...
    assert ("swap_in_fault", 1) in ex.log        # ...page-in failed...
    admits = [e for e in ex.log if e == ("admit", 1)]
    assert len(admits) == 2                      # ...recompute re-admit
    assert sched.stats.faults == 1
    assert sched.stats.fault_retries == 1        # exactly one retry spent
    assert sched.stats.quarantines == 0 and not ex.aborts
    # watchdog clean: nothing running, parked, or holding pages
    assert not ex.pages and not ex.state and not ex.swapped
    assert not sched._retry.counts               # clean finish forgets


def test_scheduler_fault_budget_exhaustion_quarantines():
    """An unrecoverable request gets exactly ``fault_retries`` recompute
    retries, then quarantines into FAILED via exec_abort — co-resident
    requests finish undisturbed and no pages leak."""
    ex = DecodeFaultFakeEngine(capacity=100, slots=2,
                               chunks={0: 1, 1: 1},
                               decode_steps={0: 2, 1: 4}, bad_rid=0)
    sched = Scheduler(SchedulerCfg(prefill_tokens=ONE_CHUNK, fault_retries=2,
                                   fault_backoff_ticks=1))
    sched.submit(_req(0))
    sched.submit(_req(1))
    done = _drain(sched, ex)
    assert {r.rid for r in done} == {1}          # survivor unaffected
    admits = [e for e in ex.log if e == ("admit", 0)]
    assert len(admits) == 1 + 2                  # initial + retry budget
    assert sched.stats.fault_retries == 2
    assert sched.stats.quarantines == 1
    assert ex.aborts == [(0, "failed", "decode:RuntimeError")]
    # the fault path drops pages via the recompute preemption (not
    # counted as a scheduler preemption) — nothing leaks
    assert sched.stats.preemptions == 0
    assert not ex.pages and not ex.state and not ex.swapped


def test_scheduler_admission_shedding_hysteresis():
    """Backlog over the high watermark sheds fresh best-effort arrivals
    (newest first) down to the low watermark; between the watermarks the
    gate stays open — no flapping — and standard traffic is never shed."""
    ex = AbortLogFakeEngine(capacity=100, slots=1,
                            chunks={r: 1 for r in range(8)},
                            decode_steps={r: 2 for r in range(8)})
    sched = Scheduler(SchedulerCfg(
        prefill_tokens=ONE_CHUNK, admission=AdmissionCfg(
            high_watermark=4, low_watermark=2, shed_below_priority=0)))
    sched.submit(_req(0))                        # admitted immediately
    fins = sched.tick(ex)
    for rid in (1, 2):
        sched.submit(_req(rid, priority=-10))    # batch backlog
    sched.submit(_req(3))                        # standard backlog
    fins += sched.tick(ex)
    assert sched.stats.admission_sheds == 0      # 3 < high watermark
    for rid in (4, 5):
        sched.submit(_req(rid, priority=-10))
    fins += sched.tick(ex)                       # backlog 5 >= 4: shed
    # newest batch arrivals go first, down to the low watermark of 2
    assert sched.stats.admission_sheds == 3
    assert [a[:2] for a in ex.aborts] == [(5, "cancelled"),
                                          (4, "cancelled"),
                                          (2, "cancelled")]
    assert all(a[2] == "admission_shed" for a in ex.aborts)
    # recovered to the low watermark: the gate reopens, so a fresh batch
    # arrival is admitted, not shed — hysteresis, no flapping
    sched.submit(_req(6, priority=-10))
    fins += _drain(sched, ex)
    assert {r.rid for r in fins} == {0, 1, 3, 6}
    assert sched.stats.admission_sheds == 3


def test_swap_area_bookkeeping():
    area = SwapArea()
    area.put(7, {"x": 1}, 100)
    area.put(9, {"y": 2}, 50)
    assert 7 in area and len(area) == 2
    assert area.peek(7) == {"x": 1}
    assert area.stats().bytes == 150 and area.stats().peak_bytes == 150
    assert area.take(7) == {"x": 1}
    assert 7 not in area and area.stats().bytes == 50
    assert area.stats().swap_outs == 2 and area.stats().swap_ins == 1
    with pytest.raises(AssertionError):
        area.put(9, {}, 1)                       # double-park is a bug


def test_paged_backend_dispatch(monkeypatch):
    monkeypatch.delenv("REPRO_PAGED_BACKEND", raising=False)
    import jax
    want = "pallas" if jax.default_backend() == "tpu" else "xla"
    assert pa.default_backend() == want
    monkeypatch.setenv("REPRO_PAGED_BACKEND", "pallas")
    assert pa.default_backend() == "pallas"
    monkeypatch.setenv("REPRO_PAGED_BACKEND", "xla")
    assert pa.default_backend() == "xla"
    monkeypatch.setenv("REPRO_PAGED_BACKEND", "mosaic")
    with pytest.raises(ValueError, match="REPRO_PAGED_BACKEND"):
        pa.default_backend()
    from repro.kernels import resolve_interpret
    assert resolve_interpret() == (jax.default_backend() != "tpu")
    assert resolve_interpret(True) and not resolve_interpret(False)