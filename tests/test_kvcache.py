"""Paged KV-cache subsystem: pool mechanics, paged attention numerics,
DLZS retention policy, and paged-engine specifics (prefix-sharing
internals, swap occupancy, priority preemption). The engine-level
parity/pressure/shed scenarios every backend must pass moved to the
shared conformance suite in tests/test_engine_core.py."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.kvcache import (SCRATCH, PagePool, PagedAllocator, PoolExhausted,
                           bucketing, metrics)
from repro.kvcache import paged_attention as pa
from repro.models import lm
from repro.serving import (PagedEngineCfg, PagedServingEngine, Request,
                           SchedulerCfg)

jax.config.update("jax_enable_x64", False)


# -- page pool ----------------------------------------------------------------

def test_pool_alloc_free_refcount():
    pool = PagePool(6, page_size=4)          # 5 usable (page 0 = scratch)
    a, b = pool.alloc(), pool.alloc()
    assert a != SCRATCH and b != SCRATCH and a != b
    assert pool.ref(a) == 1
    pool.incref(a)
    assert pool.ref(a) == 2
    pool.decref(a)
    assert pool.ref(a) == 1
    pool.decref(a)                           # unindexed ref-0 page is freed
    assert pool.ref(a) == 0
    assert pool.free_pages() == 4
    for _ in range(4):
        pool.alloc()
    with pytest.raises(PoolExhausted):
        pool.alloc()
    st = pool.stats()
    assert st.live == 5 and st.peak_live == 5 and st.free == 0


def test_pool_prefix_share_and_cached_eviction():
    pool = PagePool(5, page_size=4)
    key = (1, 2, 3, 4)
    pid = pool.alloc()
    pool.register(key, pid)
    # sharing: lookup bumps the refcount of the SAME page — no duplicate
    assert pool.lookup(key) == pid
    assert pool.ref(pid) == 2
    assert pool.stats().shared_hits == 1
    # releasing all refs caches (not frees) an indexed page
    pool.decref(pid)
    pool.decref(pid)
    assert pool.evictable() == [pid]
    # a cached page revives through the index
    assert pool.lookup(key) == pid
    assert pool.ref(pid) == 1
    pool.decref(pid)
    pool.evict(pid)
    assert pool.lookup(key) is None          # evicted: index entry gone
    assert pool.stats().evictions == 1


def test_pool_cow_detaches_shared_page():
    pool = PagePool(5, page_size=4)
    pid = pool.alloc()
    pool.register((0, 0, 0, 0), pid)
    pool.lookup((0, 0, 0, 0))                # second reference
    alloc = PagedAllocator(pool)
    pages = [pid]
    src, dst = alloc.ensure_owned(pages, 0)
    assert src == pid and dst != pid
    assert pages[0] == dst
    assert pool.ref(pid) == 1 and pool.ref(dst) == 1
    assert pool.stats().cow_copies == 1
    # private pages are left alone
    assert alloc.ensure_owned(pages, 0) is None


def test_allocator_admit_shares_full_pages_only():
    pool = PagePool(10, page_size=4)
    alloc = PagedAllocator(pool)
    p1, fresh1, sh1 = alloc.admit(list(range(10)))       # 2 full + 1 partial
    assert len(p1) == 3 and sh1 == 0 and fresh1 == p1
    alloc.register_prompt_pages(list(range(10)), p1, fresh1)
    # same 8-token prefix, different tail: the 2 full pages are shared
    prompt2 = list(range(8)) + [99, 98, 97]
    p2, fresh2, sh2 = alloc.admit(prompt2)
    assert sh2 == 2
    assert p2[:2] == p1[:2]                  # NOT duplicated
    assert p2[2] not in p1
    assert pool.ref(p1[0]) == 2


def test_allocator_select_hot_prefers_dlzs_scores():
    pool = PagePool(12, page_size=4)
    alloc = PagedAllocator(pool, recent_pages=1)
    pages = [pool.alloc() for _ in range(6)]
    scores = np.zeros(12)
    scores[pages[1]] = 90.0                  # hottest cold page
    scores[pages[3]] = 80.0
    phys, logical = alloc.select_hot(pages, 3, scores)
    # newest page always kept; two slots left for top-scored cold pages
    assert list(logical) == [1, 3, 5]
    assert list(phys) == [pages[1], pages[3], pages[5]]
    # under capacity: identity mapping, -1 padded
    phys, logical = alloc.select_hot(pages[:2], 4, scores)
    assert list(logical) == [0, 1, -1, -1]
    assert list(phys) == pages[:2] + [-1, -1]


def test_allocator_eviction_lowest_score_first():
    pool = PagePool(4, page_size=4)          # 3 usable
    alloc = PagedAllocator(pool)
    pids = [pool.alloc() for _ in range(3)]
    for i, pid in enumerate(pids):
        pool.register((i,), pid)
        pool.decref(pid)                     # all cached
    scores = np.zeros(4)
    scores[pids[0]], scores[pids[1]], scores[pids[2]] = 5.0, 1.0, 9.0
    got = alloc.extend(scores)               # evicts pids[1] (lowest score)
    assert got == pids[1]
    assert pool.lookup((1,)) is None
    assert pool.lookup((0,)) is not None     # higher-scored pages survive


def test_bucketing():
    assert bucketing.bucket_pages(1, 16) == 1
    assert bucketing.bucket_pages(17, 16) == 2
    assert bucketing.bucket_pages(33, 16, pow2=True) == 4
    assert bucketing.bucket_pages(33, 16, pow2=False) == 3
    padded = bucketing.pad_tokens(np.arange(5), 8)
    assert list(padded) == [0, 1, 2, 3, 4, 0, 0, 0]


def test_chunk_spans():
    # monolithic: one span at the bucketed width
    assert bucketing.chunk_spans(33, 16, None) == [(0, 33, 64)]
    assert bucketing.chunk_spans(33, 16, None, pow2=False) == [(0, 33, 48)]
    # short prompt: chunking never pads beyond the monolithic bucket
    assert bucketing.chunk_spans(8, 16, 4) == [(0, 8, 16)]
    # long prompt: full chunks then a bucketed remainder, page-aligned
    spans = bucketing.chunk_spans(100, 16, 2)
    assert spans == [(0, 32, 32), (32, 64, 32), (64, 96, 32), (96, 100, 16)]
    assert all(s % 16 == 0 for s, _, _ in spans)
    with pytest.raises(ValueError):
        bucketing.chunk_spans(0, 16, 2)
    with pytest.raises(ValueError, match="chunk_pages"):
        bucketing.chunk_spans(100, 16, 0)


def test_budget_tokens_and_pack_budget():
    # page-aligned, and never narrower than the widest single chunk
    assert bucketing.budget_tokens(64, 16, 2) == 64
    assert bucketing.budget_tokens(40, 16, 2) == 48
    # chunk_pages=3: a bucketed final remainder can round up to 4 pages
    assert bucketing.budget_tokens(16, 16, 3) == 64
    # greedy first-fit in priority order
    assert bucketing.pack_budget(
        [("a", [32]), ("b", [32]), ("c", [32])], 64) == [("a", 1),
                                                         ("b", 1)]
    # the head candidate always advances, even alone over budget
    assert bucketing.pack_budget(
        [("a", [128]), ("b", [16])], 64) == [("a", 1)]
    # packing stops at the first non-fit: priority order is never bypassed
    assert bucketing.pack_budget(
        [("a", [32]), ("b", [64]), ("c", [16])], 64) == [("a", 1)]
    # leftover budget deepens packed sequences round-robin (consecutive
    # chunks merge into one varlen span)
    assert bucketing.pack_budget(
        [("a", [16, 16, 16]), ("b", [16])], 64) == [("a", 3), ("b", 1)]
    assert bucketing.pack_budget([], 64) == []


def test_bucket_count():
    assert bucketing.bucket_count(0) == 1
    assert bucketing.bucket_count(3) == 4
    assert bucketing.bucket_count(4) == 4
    assert bucketing.bucket_count(5, pow2=False) == 5


def test_allocator_admit_chunk_incremental_sharing():
    pool = PagePool(12, page_size=4)
    alloc = PagedAllocator(pool)
    prompt = list(range(10))                     # 2 full + 1 partial page
    p1, f1, _, _ = alloc.admit_chunk(prompt, 0, 2, sharing=True)
    alloc.register_prompt_pages(prompt, p1, f1, 0)
    p2, f2, _, _ = alloc.admit_chunk(prompt, 2, 1, sharing=False)
    alloc.register_prompt_pages(prompt, p2, f2, 2)
    # a second admission of the same prompt shares chunk-by-chunk
    q1, fr1, sh1, sharing = alloc.admit_chunk(prompt, 0, 2, sharing=True)
    assert q1 == p1 and sh1 == 2 and not fr1 and sharing
    q2, fr2, sh2, sharing = alloc.admit_chunk(prompt, 2, 1, sharing=sharing)
    assert sh2 == 0 and len(fr2) == 1 and not sharing
    assert q2[0] not in p1 + p2                  # partial page never shared


# -- paged attention numerics -------------------------------------------------

def _paged_inputs(seed=0, B=2, nh=4, nkv=2, d=8, P=9, page=4, W=3):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (B, nh, d), jnp.float32)
    kp = jax.random.normal(ks[1], (P, page, nkv, d), jnp.float32)
    vp = jax.random.normal(ks[2], (P, page, nkv, d), jnp.float32)
    phys = jnp.array([[1, 4, 2], [5, 3, -1]], jnp.int32)
    logical = jnp.array([[0, 1, 2], [0, 1, -1]], jnp.int32)
    kv_len = jnp.array([10, 7], jnp.int32)
    return q, kp, vp, phys, logical, kv_len, nkv, page


def test_paged_gather_decode_matches_dense_oracle():
    q, kp, vp, phys, logical, kv_len, nkv, page = _paged_inputs()
    out = pa.paged_gather_decode(q, kp, vp, phys, logical, kv_len, n_kv=nkv)
    B, nh, d = q.shape
    rep = nh // nkv
    for b in range(B):
        rows_k = np.concatenate(
            [np.asarray(kp[int(p)]) for p, l in zip(phys[b], logical[b])
             if int(l) >= 0], axis=0)[:int(kv_len[b])]
        rows_v = np.concatenate(
            [np.asarray(vp[int(p)]) for p, l in zip(phys[b], logical[b])
             if int(l) >= 0], axis=0)[:int(kv_len[b])]
        for h in range(nh):
            g = h // rep
            sc = rows_k[:, g] @ np.asarray(q[b, h]) / np.sqrt(d)
            p_ = np.exp(sc - sc.max())
            p_ /= p_.sum()
            np.testing.assert_allclose(np.asarray(out[b, h]),
                                       p_ @ rows_v[:, g],
                                       rtol=1e-5, atol=1e-5)


def test_paged_pallas_kernel_matches_fallback():
    q, kp, vp, phys, logical, kv_len, nkv, _ = _paged_inputs(seed=3)
    o_xla = pa.paged_decode(q, kp, vp, phys, logical, kv_len, n_kv=nkv,
                            backend="xla")
    o_pl = pa.paged_decode(q, kp, vp, phys, logical, kv_len, n_kv=nkv,
                           backend="pallas")
    np.testing.assert_allclose(np.asarray(o_xla), np.asarray(o_pl),
                               rtol=1e-5, atol=1e-5)


def test_page_scores_reduce_lz_codes():
    from repro.core import dlzs
    k = jnp.zeros((2, 5, 4, 3, 8), jnp.bfloat16)     # [L,P,page,nkv,dh]
    k = k.at[1, 2, 0, 0, 0].set(64.0)                # exponent 6 in page 2
    k = k.at[0, 4, 1, 2, 3].set(0.25)                # exponent -2 in page 4
    tree = {"b0": {"attn": {"k": k, "k_lz": dlzs.lz_pack(k)}}}
    s = np.asarray(metrics.page_scores(tree))
    assert s.shape == (5,)
    assert s[2] == 64 + 6 and s[4] == 64 - 2 and s[0] == 0


# -- engine-level ------------------------------------------------------------

@pytest.fixture(scope="module")
def smoke_lm():
    cfg = dataclasses.replace(get_smoke_config("olmo_1b"), star=None)
    params = lm.init(jax.random.PRNGKey(1), cfg)
    return cfg, params


def _reqs(cfg, lengths, max_tokens=5):
    return [Request(rid=i, prompt=(np.arange(l, dtype=np.int32) * 7 + i)
                    % cfg.vocab, max_tokens=max_tokens)
            for i, l in enumerate(lengths)]



def test_paged_engine_prefix_sharing_not_duplicated(smoke_lm):
    cfg, params = smoke_lm
    eng = PagedServingEngine(cfg, params, PagedEngineCfg(
        max_batch=2, page_size=16, n_pages=32, hot_pages=4, eos_id=-1))
    shared = np.arange(32, dtype=np.int32)           # 2 full pages
    reqs = [Request(rid=i, prompt=np.concatenate(
                [shared, np.full((4 + i,), 100 + i, np.int32)]),
                    max_tokens=6)
            for i in range(2)]
    for r in reqs:
        eng.submit(r)
    # two ticks: admission binds both slots; prefill advances one prompt
    # per tick (prefill_per_step=1)
    eng.step()
    eng.step()
    t0, t1 = eng.tables[0], eng.tables[1]
    assert t0[:2] == t1[:2], "shared prefix pages were duplicated"
    assert t0[2] != t1[2]
    assert eng.pool.ref(t0[0]) == 2
    assert eng.pool.stats().shared_hits == 2
    done = eng.run([])
    assert set(done) == {0, 1}
    # both sequences produced tokens despite physically shared prefix pages
    assert all(len(v) == 6 for v in done.values())


def test_paged_engine_per_request_max_len(smoke_lm):
    cfg, params = smoke_lm
    eng = PagedServingEngine(cfg, params, PagedEngineCfg(
        max_batch=2, page_size=16, n_pages=32, hot_pages=4, eos_id=-1))
    reqs = [Request(rid=0, prompt=np.arange(8, dtype=np.int32),
                    max_tokens=20, max_len=12),
            Request(rid=1, prompt=np.arange(6, dtype=np.int32),
                    max_tokens=4)]
    done = eng.run(reqs)
    assert len(done[0]) < 20                 # capped by its own max_len
    assert len(done[1]) == 4
    # a request that cannot ever fit the pool is rejected at submit
    with pytest.raises(ValueError, match="pages"):
        eng.submit(Request(rid=2, prompt=np.arange(8, dtype=np.int32),
                           max_tokens=31 * 16))
    # max_len <= prompt would break page-reservation accounting: rejected
    with pytest.raises(ValueError, match="no room"):
        eng.submit(Request(rid=3, prompt=np.arange(32, dtype=np.int32),
                           max_tokens=4, max_len=16))






def test_paged_engine_batched_prefill_shares_same_tick_prefixes(smoke_lm):
    """Same-prefix prompts packed into the SAME batched dispatch still
    share their prefix pages (the phase-A2 dedup registers fresh full
    pages before the dispatch), and outputs match the sequential path."""
    cfg, params = smoke_lm
    shared = np.arange(32, dtype=np.int32)            # 2 full pages
    mk = lambda: [Request(rid=i, prompt=np.concatenate(
                      [shared, np.full((4 + 3 * i,), 100 + i, np.int32)]),
                  max_tokens=4) for i in range(4)]
    seq = PagedServingEngine(cfg, params, PagedEngineCfg(
        max_batch=4, page_size=16, n_pages=32, hot_pages=8, eos_id=-1),
        SchedulerCfg(chunk_pages=1))
    want = seq.run(mk())
    bat = PagedServingEngine(cfg, params, PagedEngineCfg(
        max_batch=4, page_size=16, n_pages=32, hot_pages=8, eos_id=-1),
        SchedulerCfg(chunk_pages=1, prefill_tokens=64))
    got = bat.run(mk())
    assert got == want
    # 3 followers x 2 prefix pages shared despite same-tick admission
    assert bat.pool.stats().shared_hits >= 6





def test_paged_swap_stable_occupancy_same_prefix(smoke_lm):
    """Regression (shared-prefix-aware swap): repeated preempt/resume of
    same-prefix traffic must neither re-upload the shared prefix nor grow
    pool occupancy. Pages shared at swap-out keep the victim's reference
    (zero host bytes); parked ref-1 prompt pages revive through the
    prefix index on page-in instead of duplicating."""
    cfg, params = smoke_lm
    eng = PagedServingEngine(cfg, params, PagedEngineCfg(
        max_batch=2, page_size=16, n_pages=32, hot_pages=4, eos_id=-1))
    shared = np.arange(32, dtype=np.int32)       # 2 full prefix pages
    reqs = [Request(rid=i, prompt=np.concatenate(
                [shared, np.full((5 + i,), 90 + i, np.int32)]),
                    max_tokens=16)
            for i in range(2)]
    for r in reqs:
        eng.submit(r)
    for _ in range(4):                            # both slots decoding
        eng.step()
    assert len(eng._decode_slots()) == 2
    slot = 1
    rid = eng.active[slot].rid
    n_private = sum(1 for pid in eng.tables[slot]
                    if eng.pool.ref(pid) == 1)
    assert n_private > 0                          # tail pages are private
    live0, free0 = eng.pool.live_pages(), eng.pool.free_pages()
    per_page = eng.stats()["bytes_per_page"]
    st = eng.sched.running.pop(slot)
    for cycle in range(3):
        assert eng.exec_preempt(slot, True)
        # only the private (ref-1, non-revivable-by-index... the parked)
        # pages hit the host: the 2 prefix pages are shared with slot 0
        # and stay resident under the victim's kept reference
        assert eng.swap_area.stats().bytes == n_private * per_page
        slot = eng.exec_swap_in(st.req)
        assert slot is not None
        assert eng.pool.live_pages() == live0, f"cycle {cycle}: occupancy"
        assert eng.pool.free_pages() == free0, f"cycle {cycle}: leak"
    eng.sched.running[slot] = st
    done = eng.run([])                            # drain to completion
    assert set(done) == {0, 1}
    assert all(len(v) == 16 for v in done.values())
    assert eng.pool.stats().cow_copies == 0


def test_star_chunk_sparse_prefill_within_tolerance():
    """STAR inside later prefill chunks (satellite of the spatial PR):
    with the ``chunk_sparse`` flag the chunk's queries DLZS-predict over
    the gathered past pages and drop whole pages outside the SADS sphere.
    Pages with uniformly tiny keys are dropped — and the output stays
    within the sphere's error bound of the dense chunk path."""
    import dataclasses as dc

    from repro.core.star_attention import STARConfig
    from repro.models import attention

    rng = jax.random.PRNGKey(0)
    ks = jax.random.split(rng, 6)
    nkv, nh, dh, page, wp, c = 2, 4, 16, 8, 4, 8
    acfg = attention.AttentionCfg(
        d_model=64, n_heads=nh, n_kv=nkv, head_dim=dh, q_chunk=64,
        star=STARConfig(block_q=8, block_kv=8, radius=14.0),
        chunk_sparse=True, dtype=jnp.float32)
    params = attention.init(ks[0], acfg)
    # past pool: 3 near-zero pages + 1 dominant page. The sphere keeps
    # only the dominant page, and the dropped mass is bounded by
    # S_past * e^-radius of the total — the tolerance below. The dominant
    # page holds key pairs (k, -k), so every query row — not only the
    # row with the page's best score — sees a large positive score there
    half = jax.random.normal(ks[2], (page // 2, nkv, dh)) * 20.0
    kp = jax.random.normal(ks[1], (6, page, nkv, dh), jnp.float32) * 0.01
    kp = kp.at[4].set(jnp.concatenate([half, -half], axis=0))
    vp = jax.random.normal(ks[3], (6, page, nkv, dh), jnp.float32)
    from repro.core import dlzs
    cache = {"k": kp, "v": vp, "k_lz": dlzs.lz_pack(kp)}
    x = jax.random.normal(ks[4], (1, c, 64), jnp.float32)
    positions = (wp * page + jnp.arange(c))[None, :]
    past_phys = jnp.array([[1, 2, 4, 3]], jnp.int32)
    past_logical = jnp.array([[0, 1, 2, 3]], jnp.int32)
    past_len = jnp.array([wp * page], jnp.int32)

    run = lambda a: attention.apply_prefill_chunk(
        params, a, x, positions, cache, past_phys, past_logical,
        past_len)[0]
    dense = run(dc.replace(acfg, star=None, chunk_sparse=False))
    sparse = run(acfg)
    keep_all = run(dc.replace(
        acfg, star=dc.replace(acfg.star, radius=1e9)))
    # an infinite sphere keeps every page: exactly the dense path
    np.testing.assert_allclose(np.asarray(keep_all), np.asarray(dense),
                               rtol=1e-5, atol=1e-5)
    # the real radius drops the tiny pages: not identical, but within the
    # sphere's e^-radius relative-mass bound
    assert float(jnp.max(jnp.abs(sparse - dense))) > 1e-7
    np.testing.assert_allclose(np.asarray(sparse), np.asarray(dense),
                               atol=0.02)


def test_paged_engine_priority_preempts_low_first(smoke_lm):
    """Under pressure the low-priority request is the victim; the
    high-priority one is never preempted and still finishes exactly."""
    cfg, params = smoke_lm
    reqs = [Request(rid=0, prompt=(np.arange(16, dtype=np.int32) * 7)
                    % cfg.vocab, max_tokens=20, priority=0),
            Request(rid=1, prompt=(np.arange(17, dtype=np.int32) * 7 + 1)
                    % cfg.vocab, max_tokens=20, priority=5),
            Request(rid=2, prompt=(np.arange(16, dtype=np.int32) * 7 + 2)
                    % cfg.vocab, max_tokens=20, priority=0),
            Request(rid=3, prompt=(np.arange(18, dtype=np.int32) * 7 + 3)
                    % cfg.vocab, max_tokens=20, priority=0)]
    eng = PagedServingEngine(cfg, params, PagedEngineCfg(
        max_batch=4, page_size=16, n_pages=9, hot_pages=4, eos_id=-1),
        SchedulerCfg(chunk_pages=1, swap=True))
    victims = []
    orig = eng.exec_preempt
    def spy(slot, swap):
        victims.append(eng.active[slot].rid)
        return orig(slot, swap)
    eng.exec_preempt = spy
    done = eng.run(reqs, max_steps=500)
    assert set(done) == {0, 1, 2, 3}
    assert all(len(v) == 20 for v in done.values())
    assert victims and 1 not in victims          # high priority never evicted
