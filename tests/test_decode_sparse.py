"""Decode-time DLZS sparsity + int8 KV tier lockdown.

Four test families pin the PR's semantics:

* cross-backend greedy-parity matrix — dense oracle vs paged (in-process)
  vs 2-shard spatial (subprocess): ``decode_hot_width=None`` with the
  quant tier off must be token-identical; bounded widths must keep the
  first token exact (prefill is width-independent) and clear a greedy
  top-1 agreement floor that rises with width; a width covering every
  page of every sequence is exact again;
* int8 tier — per-page round-trip error bounds (``<= scale/2``),
  idempotency, untouched pages stay zeroed, QuantTracker lifecycle
  (alloc clears, cow inherits, swap-in restore re-derives flags from
  parked scales), and end-to-end: quantization at the minimal width
  (hot = {newest, sink}, never quantized, never re-gathered) changes no
  token while cold pages demonstrably quantize;
* sphere-rule properties (hypothesis, via _hypothesis_shim) —
  determinism, monotone-superset in width, newest page + sink always
  selected, fixed ``[width]`` int32 shapes for any score distribution;
* SHED regression — neither ``select_hot`` nor ``select_hot_sphere``
  (flat or sharded) may ever select a lazily-shed (negative sentinel)
  table entry, whatever the shed page's DLZS score would have been.

Agreement thresholds are pinned against fixed seeds (PRNGKey(1) params,
deterministic greedy decode), with margin below the measured values.
"""

import dataclasses
import pathlib
import subprocess
import sys
import types

from _hypothesis_shim import hypothesis, st
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.kvcache import QuantTracker, select_hot_sphere
from repro.kvcache import quant
from repro.kvcache.allocator import PagedAllocator
from repro.kvcache.pool import PagePool
from repro.models import lm
from repro.serving import (EngineCfg, LLM, PagedEngineCfg,
                           PagedServingEngine, SchedulerCfg, ServingEngine)
from repro.serving.paged import PagedBackend
from repro.spatial.sharded_pool import ShardedPagePools
from repro.spatial.topology import ShardTopology

PROGS = pathlib.Path(__file__).parent / "spatial_progs"

# mixed prompt lengths spanning 1..4 pages at page_size 16; + GEN decode
# tokens the longest sequence reaches 6 pages, so width 6 covers all
LENGTHS = (5, 21, 40, 64)
GEN = 24
FULL_WIDTH = 6


@pytest.fixture(scope="module")
def smoke_lm():
    cfg = dataclasses.replace(get_smoke_config("olmo_1b"), star=None)
    params = lm.init(jax.random.PRNGKey(1), cfg)
    return cfg, params


def _prompts(cfg):
    return [(np.arange(l, dtype=np.int32) * 7 + i) % cfg.vocab
            for i, l in enumerate(LENGTHS)]


def _run(llm, prompts, max_tokens=GEN):
    handles = [llm.submit(p, max_tokens=max_tokens, rid=i)
               for i, p in enumerate(prompts)]
    done = llm.run_until_done(max_steps=10_000)
    assert all(h.done for h in handles)
    return done


def _dense(cfg, params, prompts):
    llm = LLM(ServingEngine(cfg, params,
                            EngineCfg(max_batch=2, max_len=128, eos_id=-1)))
    return _run(llm, prompts)


def _paged(cfg, params, *, width=None, kv_quant=None):
    scfg = SchedulerCfg(chunk_pages=1, prefill_tokens=16,
                        decode_hot_width=width, kv_quant=kv_quant)
    return LLM(PagedServingEngine(cfg, params, PagedEngineCfg(
        max_batch=2, page_size=16, n_pages=48, hot_pages=8,
        recent_pages=2, eos_id=-1), scfg))


def _agreement(got, want):
    """Mean greedy top-1 agreement: per request, the longest common
    prefix fraction vs the oracle. After the first divergence the
    contexts differ, so positional comparison past it is meaningless —
    the prefix is exactly the span where both ran the same argmax."""
    fr = []
    for rid in want:
        n = 0
        for x, y in zip(got[rid], want[rid]):
            if x != y:
                break
            n += 1
        fr.append(n / max(len(want[rid]), 1))
    return sum(fr) / len(fr)


# -- cross-backend parity matrix ---------------------------------------------

def test_width_none_bit_identical(smoke_lm):
    """decode_hot_width=None + quant off: the sparse plumbing must be
    invisible — token-identical to the dense oracle."""
    cfg, params = smoke_lm
    prompts = _prompts(cfg)
    want = _dense(cfg, params, prompts)
    llm = _paged(cfg, params)
    got = _run(llm, prompts)
    assert got == want, f"width=None changed tokens:\n{got}\n{want}"
    st_ = llm.stats()
    assert st_["decode_compiles"] == 1
    assert st_["hot_width"] == 8          # pcfg.hot_pages passthrough
    assert "kv_quant" not in st_          # tier off => no tier stats


def test_bounded_width_agreement_floor(smoke_lm):
    """Bounded widths: first token exact (prefill is width-independent),
    agreement floor rises with width, and a width covering every page is
    exact. Measured (seeded): w3=0.615, w5=0.927, w6=1.0."""
    cfg, params = smoke_lm
    prompts = _prompts(cfg)
    want = _dense(cfg, params, prompts)
    agr = {}
    for width, floor in ((3, 0.5), (5, 0.85), (FULL_WIDTH, 1.0)):
        llm = _paged(cfg, params, width=width)
        got = _run(llm, prompts)
        for rid in want:
            assert got[rid][0] == want[rid][0], \
                f"width={width} rid={rid}: first token must come from " \
                f"the (dense, width-independent) prefill"
        agr[width] = _agreement(got, want)
        assert agr[width] >= floor, \
            f"width={width}: agreement {agr[width]:.3f} < {floor}"
        st_ = llm.stats()
        assert st_["decode_compiles"] == 1, "bounded width broke the " \
            "single decode compile"
        assert st_["hot_width"] == width
        if width == FULL_WIDTH:
            assert got == want, "full-coverage width must be exact"
    assert agr[3] <= agr[5], "agreement should not degrade with width"


def test_quant_minimal_width_token_exact(smoke_lm):
    """kv_quant at width=2: hot = {newest, sink} — never quantized and
    the only pages gathered — so the int8 tier must change NO token even
    though cold pages demonstrably quantize underneath."""
    cfg, params = smoke_lm
    prompts = _prompts(cfg)
    base = _run(_paged(cfg, params, width=2), prompts)
    llm = _paged(cfg, params, width=2, kv_quant="int8")
    got = _run(llm, prompts)
    assert got == base, "unread int8 tier perturbed the fp gather"
    kq = llm.stats()["kv_quant"]
    assert kq["quantize_events"] > 0, "no cold page ever quantized"
    assert kq["bytes_per_page_int8"] < kq["bytes_per_page_fp"]


def test_quant_bounded_width_agreement(smoke_lm):
    """kv_quant at a width where sphere-passing cold pages DO re-enter
    the hot set (int8 reads happen): bounded loss only — agreement vs
    the same width without quantization stays near-exact (measured 1.0
    at this scale)."""
    cfg, params = smoke_lm
    prompts = _prompts(cfg)
    base = _run(_paged(cfg, params, width=4), prompts)
    llm = _paged(cfg, params, width=4, kv_quant="int8")
    got = _run(llm, prompts)
    assert _agreement(got, base) >= 0.9
    assert llm.stats()["kv_quant"]["quantize_events"] > 0


def test_kv_quant_rejects_unknown_mode(smoke_lm):
    cfg, params = smoke_lm
    with pytest.raises(ValueError, match="kv_quant"):
        _paged(cfg, params, kv_quant="fp4")


def test_spatial_parity_subprocess():
    """The same matrix on a 2-shard fake-device mesh (spatial backend
    needs its own process: the parent's XLA device count is fixed at
    first jax init)."""
    out = subprocess.run(
        [sys.executable, str(PROGS / "decode_sparse_prog.py"), "2"],
        capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, \
        f"decode_sparse_prog failed:\nSTDOUT:{out.stdout}\n" \
        f"STDERR:{out.stderr[-3000:]}"
    assert "DECODE_SPARSE_OK" in out.stdout


# -- int8 tier: bounds + bookkeeping -----------------------------------------

def test_quant_roundtrip_bound_per_page():
    """Symmetric per-page absmax int8: round-trip error <= scale/2 per
    element, per page; pages outside ``phys`` keep zeroed scales; the
    transform is idempotent (re-quantizing quantized pages is a no-op,
    since the fp rows are left intact)."""
    key = jax.random.PRNGKey(0)
    k = jax.random.normal(key, (2, 8, 16, 2, 4)) * 3.0   # [L,P,pg,nkv,dh]
    layers = {"blk": {"k": k, "v": k * 0.5 + 1.0}}
    layers = quant.add_quant_slabs(layers)
    phys = jnp.asarray([1, 3, 6], jnp.int32)
    out = quant.quantize_pages(layers, phys)
    d = out["blk"]
    cold = [1, 3, 6]
    untouched = [p for p in range(8) if p not in cold]
    for src, qk, sk in (("k", "kq", "k_scale"), ("v", "vq", "v_scale")):
        scale = np.asarray(d[sk])
        deq = np.asarray(quant.dequantize_rows(d[qk], d[sk]))
        x = np.asarray(d[src])
        for p in cold:
            err = np.abs(deq[:, p] - x[:, p]).max(axis=(-1, -2, -3))
            assert np.all(err <= scale[:, p] / 2 + 1e-6), (src, p)
            assert np.all(scale[:, p] > 0)
        assert np.all(scale[:, untouched] == 0.0)
        assert np.array_equal(x, np.asarray(layers["blk"][src])), \
            "fp rows must stay intact"
    again = quant.quantize_pages(out, phys)
    for leaf_a, leaf_b in zip(jax.tree.leaves(out), jax.tree.leaves(again)):
        assert np.array_equal(np.asarray(leaf_a), np.asarray(leaf_b))


def test_quant_split_merge_roundtrip():
    layers = quant.add_quant_slabs(
        {"a": {"k": jnp.ones((1, 2, 4, 1, 2)), "v": jnp.ones((1, 2, 4, 1, 2)),
               "k_lz": jnp.zeros((1, 2, 4), jnp.int8)}})
    base, tier = quant.split_quant(layers)
    assert "kq" not in base["a"] and "k" not in tier["a"]
    assert "k_lz" in base["a"]            # non-tier extras stay in base
    merged = quant.merge_quant(base, tier)
    assert set(merged["a"]) == set(layers["a"])
    assert quant.has_quant(layers) and not quant.has_quant(base)
    assert quant.find_scale(base) is None


def test_quant_tracker_lifecycle():
    """alloc clears stale flags, mark counts one event per page, cow
    inherits (the device copy clones the int8 rows too), flags persist
    until the pid is re-allocated."""
    pool = PagePool(8, 16)
    a = pool.alloc()
    assert not pool.quant.is_quant(a)
    pool.quant.mark(a)
    pool.quant.mark(a)                     # second mark: no new event
    assert pool.quant.is_quant(a)
    assert pool.quant.stats().quantize_events == 1
    pool.incref(a)
    b = pool.cow(a)
    assert pool.quant.is_quant(b), "cow page must inherit the flag"
    pool.decref(a)
    pool.decref(b)
    # freed; flags only reset when the pid comes back off the free list
    fresh = [pool.alloc() for _ in range(7)]
    assert a in fresh and b in fresh
    assert not any(pool.quant.is_quant(p) for p in fresh)
    assert pool.quant.stats().quantized == 0
    assert not pool.quant.is_quant(-1)     # SHED sentinel: never quant


def test_restore_quant_flags_from_parked_scales():
    """Swap-in re-derives tracker flags from the payload: a parked page
    with any positive per-layer scale was quantized; an fp-only page
    carries the zero-initialized scale row and must NOT be marked."""
    fake = types.SimpleNamespace(
        pool=types.SimpleNamespace(quant=QuantTracker(8)))
    scales = np.zeros((2, 3), np.float32)          # [L, n_park]
    scales[1, 0] = 0.25                            # pos 0: quantized
    rows = {"k": np.zeros((2, 3, 4)), "v": np.zeros((2, 3, 4)),
            "kq": np.zeros((2, 3, 4), np.int8),
            "vq": np.zeros((2, 3, 4), np.int8),
            "k_scale": scales, "v_scale": scales.copy()}
    uploads = [(0, 4, 3), (1, 5, 6), (2, 6, 7)]    # (pos, logical, pid)
    PagedBackend._restore_quant_flags(fake, rows, uploads)
    tr = fake.pool.quant
    assert tr.is_quant(3)
    assert not tr.is_quant(6) and not tr.is_quant(7)
    # payload without a tier (kv_quant off): no-op
    PagedBackend._restore_quant_flags(fake, {"k": 0, "v": 0}, uploads)
    assert tr.stats().quantized == 1


# -- sphere-rule properties ---------------------------------------------------

_tables = st.integers(1, 12).flatmap(lambda n: st.tuples(
    st.just(n),
    st.lists(st.booleans(), min_size=n, max_size=n),        # SHED mask
    st.lists(st.floats(-100, 100, allow_nan=False,
                       allow_infinity=False),
             min_size=n + 1, max_size=n + 1)))               # scores by pid


def _mk_pages(n, shed):
    # pid j+1 for live slots (pid 0 is scratch), -1 for shed slots
    return [(-1 if shed[j] else j + 1) for j in range(n)]


@hypothesis.given(_tables, st.integers(1, 14),
                  st.one_of(st.none(), st.floats(0, 50)))
@hypothesis.settings(deadline=None, max_examples=200)
def test_sphere_rule_properties(tbl, width, radius):
    n, shed, scores = tbl
    pages = _mk_pages(n, shed)
    sc = np.asarray(scores, np.float64)
    sel_args = dict(recent=2, radius=radius)
    phys, logical = select_hot_sphere(pages, width, sc, **sel_args)
    # deterministic
    phys2, logical2 = select_hot_sphere(pages, width, sc, **sel_args)
    assert np.array_equal(phys, phys2) and np.array_equal(logical, logical2)
    # fixed [width] int32 shapes for ANY score distribution
    assert phys.shape == (width,) == logical.shape
    assert phys.dtype == np.int32 and logical.dtype == np.int32
    sel = [int(j) for j in logical if j >= 0]
    present = [j for j in range(n) if pages[j] >= 0]
    # selected entries map table slots; SHED never selected; -1 padding
    for k, j in enumerate(sel):
        assert pages[j] >= 0 and int(phys[k]) == pages[j]
    assert all(int(p) == -1 for p in phys[len(sel):])
    if present:
        assert sel == sorted(sel), "gather order must stay position-sorted"
        assert present[-1] in sel, "newest page must always be hot"
        if width >= 2 and present[0] != present[-1]:
            assert present[0] in sel, "sink page must always be hot"
    else:
        assert not sel
    # monotone: widening the cap only ever adds pages
    _, wider = select_hot_sphere(pages, width + 1, sc, **sel_args)
    assert set(sel) <= {int(j) for j in wider if j >= 0}


@hypothesis.given(_tables, st.integers(1, 8))
@hypothesis.settings(deadline=None, max_examples=100)
def test_sphere_rule_no_scores_recency_order(tbl, width):
    """scores=None (DLZS disabled): same guarantees, cold ranked by
    recency; still deterministic, fixed-shape, SHED-free."""
    n, shed, _ = tbl
    pages = _mk_pages(n, shed)
    phys, logical = select_hot_sphere(pages, width, None, recent=1)
    assert phys.shape == (width,) == logical.shape
    sel = [int(j) for j in logical if j >= 0]
    present = [j for j in range(n) if pages[j] >= 0]
    assert len(sel) == min(width, len(present))
    for k, j in enumerate(sel):
        assert pages[j] >= 0 and int(phys[k]) == pages[j]
    if present and width >= len(present):
        assert sel == present, "wide enough cap keeps every live page"


def test_sphere_radius_prunes_low_scores():
    """radius picks the SADS sphere: cold pages scored more than
    ``radius`` below the per-sequence max are cut even when the width
    cap has room; radius=None keeps pure bounded top-k."""
    pages = [1, 2, 3, 4, 5, 6]
    scores = np.asarray([0., 50., 10., 49., 9., 48., 50.])
    # width 6, radius 3: sphere keeps scores >= 50 - 3 -> slots j0 (50),
    # j2 (49), j4 (48), j5 (50); j1 and j3 (scores 10, 9) are pruned
    # even though the width cap has room for them
    _, logical = select_hot_sphere(pages, 6, scores, recent=1, radius=3.0)
    assert [int(j) for j in logical if j >= 0] == [0, 2, 4, 5]
    # radius=None: no sphere cut, width fills with best-scored cold
    _, logical = select_hot_sphere(pages, 6, scores, recent=1, radius=None)
    assert [int(j) for j in logical if j >= 0] == [0, 1, 2, 3, 4, 5]


# -- SHED sentinel regression -------------------------------------------------

def test_select_hot_never_selects_shed_pages():
    """Regression: a lazily-shed table entry (negative sentinel) must
    never be chosen by either selector, even when the shed slot's pid
    would have carried the best DLZS score."""
    pool = PagePool(32, 16)
    alloc = PagedAllocator(pool, recent_pages=2)
    pages = [5, -1, 7, -1, 9, 11, -1]
    # every pid scores higher than the live ones at the shed positions
    scores = np.arange(32, dtype=np.float64) * 10.0
    for width in (1, 2, 3, 4, 6, 8):
        for sel in (alloc.select_hot, alloc.select_hot_sphere):
            phys, logical = sel(pages, width, scores)
            for p, j in zip(phys, logical):
                if int(j) >= 0:
                    assert pages[int(j)] == int(p) >= 0, (sel, width)
                else:
                    assert int(p) == -1
            picked = {int(j) for j in logical if j >= 0}
            assert picked.isdisjoint({1, 3, 6}), \
                f"{sel.__name__} width={width} selected a SHED slot"


def test_select_hot_all_shed_table_is_empty_selection():
    alloc = PagedAllocator(PagePool(8, 16))
    for sel in (alloc.select_hot, alloc.select_hot_sphere):
        phys, logical = sel([-1, -1, -1], 4)
        assert np.all(phys == -1) and np.all(logical == -1)


def test_sharded_select_hot_sphere_shed_and_global_mapping():
    """Sharded wrapper: per-shard sphere selection over the shard's
    slice skips SHED entries and reports GLOBAL logical indices; a shard
    whose slice is fully shed comes back all -1 (the decode merge skip
    signal)."""
    pools = ShardedPagePools(ShardTopology(2), n_pages_local=16,
                             page_size=16, recent_pages=2)
    # global table: shard0 owns j=0,2,4 ; shard1 owns j=1,3,5 (all shed)
    table = [3, -1, -1, -1, 7, -1]
    scores = np.tile(np.arange(16, dtype=np.float64) * 5.0, (2, 1))
    ph0, lg0 = pools.select_hot_sphere(table, 0, 4, scores, radius=None)
    sel0 = [int(j) for j in lg0 if j >= 0]
    assert sel0 == [0, 4], "live shard-0 slice: sink + newest"
    assert [int(p) for p in ph0[:2]] == [3, 7]
    ph1, lg1 = pools.select_hot_sphere(table, 1, 4, scores, radius=None)
    assert np.all(ph1 == -1) and np.all(lg1 == -1), \
        "fully-shed slice must select nothing (psum-skip signal)"
