"""Single-pool serving backend on the paged KV-cache subsystem.

Replaces the dense slot engine's one ``[max_batch, max_len]`` KV slab with
the global page pool (repro.kvcache): requests own block tables of
fixed-size pages, identical prompt prefixes share pages copy-on-write, and
the DLZS retention policy picks which pages each decode step gathers.

Layering (see docs/serving.md):

* ``repro.serving.scheduler.Scheduler`` — policy: who admits, which
  prompt prefills next, who is preempted under pool pressure.
* ``repro.serving.engine_core.EngineCore`` — the executor state machine
  the scheduler drives: admission binding, chunked + batched varlen
  prefill (the allocate/dedup/wave-split/commit scaffold lives THERE,
  once), the fused decode loop, lazy cold-page shedding,
  preempt/swap-in. Shared with the spatial engine.
* ``PagedBackend`` (this module) — the device driver EngineCore calls:
  pool slabs, jitted prefill/chunk/decode/scatter kernels, single-pool
  allocation and prefix indexing.

``PagedServingEngine`` is the thin composition of the three — construct
it directly, or (preferred) through ``repro.serving.api.LLM``.

Properties carried by this backend:

* Chunked prefill — prompts prefill in page-aligned chunks that
  interleave with decode steps. Chunk 0 reuses the bucketed monolithic
  prefill; later chunks run ``lm.prefill_chunk_paged`` against the pages
  earlier chunks wrote. Pages are allocated chunk-by-chunk.
* ``max_len`` is a per-request property; admission is length-bucketed so
  prefill compiles O(log max_len) shapes; decode compiles ONCE — its
  shapes depend only on (max_batch, hot_pages, pool size).
* Decode gathers at most ``hot_pages`` pages per sequence, DLZS page
  scores ranking the cold pages (exact, token-parity with the dense
  engine, when ``hot_pages`` covers the longest request).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.paged import pages_per_block
from repro.kvcache import (SCRATCH, PagePool, PagedAllocator, PoolExhausted,
                           bucketing, metrics, quant)
from repro.kvcache.paged_attention import default_backend
from repro.models import lm
from repro.obs import NULL_TELEMETRY
from repro.serving.engine_core import EngineCore
from repro.serving.scheduler import (NeedPages, SchedulerCfg,
                                     resolve_prefill_tokens)

__all__ = ["PagedEngineCfg", "PagedBackend", "PagedServingEngine"]


@dataclasses.dataclass(frozen=True)
class PagedEngineCfg:
    max_batch: int = 8
    page_size: int = 16
    n_pages: int = 256           # pool capacity (page 0 is scratch)
    hot_pages: int = 16          # W: pages gathered per decode step
    recent_pages: int = 2        # newest pages always hot (incl. write page)
    eos_id: int = 1
    greedy: bool = True
    temperature: float = 1.0
    bucket_pow2: bool = True     # prompt buckets: pow2 page counts
    share_prefixes: bool = True
    batch_past_pages: Optional[int] = None
    # Past-page gather width of the BATCHED chunk-prefill dispatch
    # (SchedulerCfg.prefill_tokens). Fixed at init so the batched prefill
    # compiles exactly once; None sizes it to the whole pool (always
    # safe). Set it to the largest prompt page count you actually serve
    # to shrink the per-dispatch gather — submit() rejects requests that
    # could not fit the window.


class PagedBackend:
    """Single-pool ``engine_core.Backend`` implementation."""

    def __init__(self, model_cfg, params, pcfg: PagedEngineCfg,
                 scfg: SchedulerCfg):
        if any(blk.kind != "attn" for blk in model_cfg.pattern):
            raise ValueError("paged engine supports attention-only patterns")
        if model_cfg.enc_layers or not model_cfg.causal:
            raise ValueError("paged engine needs a causal decoder-only model")
        self.cfg = model_cfg
        self.pcfg = pcfg
        self.params = params

        # protocol facts EngineCore reads
        self.page_size = pcfg.page_size
        self.max_batch = pcfg.max_batch
        self.eos_id = pcfg.eos_id
        self.greedy = pcfg.greedy
        self.temperature = pcfg.temperature
        self.bucket_pow2 = pcfg.bucket_pow2
        self.keep_recent = max(1, pcfg.recent_pages)

        # decode-time DLZS sparsity: bound the per-sequence gather at the
        # sphere-rule hot width. Fixed at init so decode compiles ONCE
        # with [max_batch, hot_width] page-state shapes.
        self.sparse_decode = scfg.decode_hot_width is not None
        self.hot_width = (min(pcfg.hot_pages, scfg.decode_hot_width)
                          if self.sparse_decode else pcfg.hot_pages)
        self.hot_radius = scfg.decode_hot_radius
        if scfg.kv_quant not in (None, "int8"):
            raise ValueError(
                f"kv_quant={scfg.kv_quant!r}: choose None or 'int8'")
        self.kv_quant = scfg.kv_quant == "int8"
        self.decode_sparsity = None  # telemetry dict, set per decode step
        # MoE models: [MoE layers, held experts] assignment counts of the
        # newest prefill or decode dispatch, left on the device
        # (EngineCore._note_moe pulls them only when something reads them)
        self.moe_hist = None

        # Prefix sharing is exact only if a full page never splits a STAR
        # prefill q-tile (tile selection mixes rows within a tile).
        self.share = pcfg.share_prefixes and (
            model_cfg.star is None
            or pcfg.page_size % model_cfg.star.block_q == 0)
        if (model_cfg.star is not None
                and scfg.chunk_pages is not None
                and (scfg.chunk_pages * pcfg.page_size)
                % model_cfg.star.block_q != 0):
            raise ValueError(
                "chunk_pages * page_size must be a multiple of the STAR "
                "q-tile (block_q) so chunk boundaries stay tile-aligned")

        self.pool = PagePool(pcfg.n_pages, pcfg.page_size)
        self.alloc = PagedAllocator(self.pool,
                                    recent_pages=pcfg.recent_pages)
        self.tel = NULL_TELEMETRY    # shared via EngineCore.attach_telemetry

        # batched varlen chunk prefill: fixed flat-buffer width + fixed
        # past-gather window => exactly one prefill compilation
        max_tokens = resolve_prefill_tokens(scfg, pcfg.page_size)
        self.batched = max_tokens is not None
        self.budget_tokens = self.batch_wp = None
        if self.batched:
            self.budget_tokens = bucketing.budget_tokens(
                max_tokens, pcfg.page_size, scfg.chunk_pages,
                pow2=pcfg.bucket_pow2)
            self.batch_wp = bucketing.bucket_count(
                pcfg.batch_past_pages or pcfg.n_pages - 1,
                pow2=pcfg.bucket_pow2)

        # each program compiles under its method's name, which the
        # profiler trace shows (jit__prefill_fn, jit__scatter_fn, ...)
        self._prefill = jax.jit(self._prefill_fn)
        self._prefill_chunk = jax.jit(self._prefill_chunk_fn)
        self._prefill_chunk_batch = jax.jit(self._prefill_chunk_batch_fn)
        # donate the cache/pool slabs: these updates would otherwise keep
        # two full copies of the page pool live per step (no-op on CPU,
        # which lacks donation — load-bearing on TPU). The decode step
        # alone stays unnamed (jit__unknown): the chip benchmark finds it
        # by the paged kernel it runs, and its CPU test holds that no
        # program is named for decode
        self._decode = jax.jit(functools.partial(self._decode_fn),
                               donate_argnums=(2,))
        # audit probe (obs.audit): the decode step, but NO donation — the
        # probe reads the live cache and its output tree is discarded, so
        # donating would invalidate self.cache under the engine
        self._audit = jax.jit(self._audit_fn)
        self._scatter = jax.jit(self._scatter_fn, donate_argnums=(0,))
        self._copy_page = jax.jit(self._copy_fn, donate_argnums=(0,))
        self._gather_pages = jax.jit(self._gather_fn)
        self._page_in = jax.jit(self._page_in_fn, donate_argnums=(0,))
        self._scores = jax.jit(metrics.page_scores)
        self._scores_by_layer = jax.jit(metrics.page_scores_per_layer)

        # Build the page pool slabs from a one-page probe prefill: every
        # prefill cache leaf [L, 1, page, nkv, dh] becomes a pool slab
        # [L, n_pages, page, nkv, dh].
        probe = {"tokens": jnp.zeros((1, pcfg.page_size), jnp.int32)}
        _, cache_one = self._prefill(params, probe,
                                     jnp.zeros((1,), jnp.int32))
        def slab(leaf):
            shape = (leaf.shape[0], pcfg.n_pages) + leaf.shape[2:]
            return jnp.zeros(shape, leaf.dtype)
        layers = jax.tree.map(slab, cache_one["layers"])
        if self.kv_quant:
            # int8 cold tier rides IN the cache tree: every attention
            # update uses dict(cache, k=..., v=...), so the tier leaves
            # pass through prefill/decode untouched and swap payloads
            # carry them automatically
            layers = quant.add_quant_slabs(layers)
            self._quantize = jax.jit(quant.quantize_pages,
                                     donate_argnums=(0,))
        self.cache = {
            "layers": layers,
            "lengths": jnp.zeros((pcfg.max_batch,), jnp.int32),
        }
        self.last_token = jnp.zeros((pcfg.max_batch, 1), jnp.int32)
        # per-page byte prices (shape-only, computed once): the full tree
        # row a swap payload carries vs the fp K/V rows a decode gather
        # reads — obs.accounting converts page counters to traffic bytes
        self.page_bytes_full = metrics.bytes_per_page(self.cache["layers"])
        self.page_bytes_gather = metrics.gather_bytes_per_page(
            self.cache["layers"])
        self.page_bytes_int8 = metrics.quant_bytes_per_page(
            self.cache["layers"])
        # pages per block of the Pallas decode kernel, from the K slab
        # itself, where that kernel is the decode path (the XLA fallback
        # and the int8 tier run none): _page_state counts its blocks
        self.kernel_ppb = (
            pages_per_block(metrics.k_pool(self.cache["layers"]),
                            self.hot_width)
            if default_backend() == "pallas" and not self.kv_quant
            else None)

    # -- jitted kernels -----------------------------------------------------

    def _prefill_fn(self, params, batch, last_index):
        return lm.prefill(params, self.cfg, batch, last_index=last_index)

    def _prefill_chunk_fn(self, params, batch, cache, chunk_state):
        return lm.prefill_chunk_paged(params, self.cfg, batch, cache,
                                      chunk_state)

    def _prefill_chunk_batch_fn(self, params, batch, cache, pack_state):
        return lm.prefill_chunk_batch_paged(params, self.cfg, batch, cache,
                                            pack_state)

    def _decode_fn(self, params, tokens, cache, page_state):
        return lm.decode_step_paged(params, self.cfg, tokens, cache,
                                    page_state)

    def _audit_fn(self, params, tokens, cache, page_state):
        return self._decode_fn(params, tokens, cache, page_state)

    def _scatter_fn(self, pool_layers, one_layers, phys):
        """Write a prefilled sequence's rows into pool pages ``phys``.

        Two-tree map over (pool slab, per-sequence cache): the prefill
        cache has no int8-tier leaves, so with the quantized tier on the
        tier is split out first and merged back untouched — freshly
        prefilled pages are fp until they leave the DLZS hot set."""
        def put(pool, one):
            rows = one[:, 0]                       # [L, T_pad, ...]
            pg = pool.shape[2]
            rows = rows.reshape(rows.shape[0], -1, pg, *rows.shape[2:])
            return pool.at[:, phys].set(rows.astype(pool.dtype))
        if self.kv_quant:
            base, tier = quant.split_quant(pool_layers)
            return quant.merge_quant(jax.tree.map(put, base, one_layers),
                                     tier)
        return jax.tree.map(put, pool_layers, one_layers)

    @staticmethod
    def _copy_fn(pool_layers, src, dst):
        """COW: duplicate physical page ``src`` into ``dst`` (all layers)."""
        return jax.tree.map(lambda pool: pool.at[:, dst].set(pool[:, src]),
                            pool_layers)

    @staticmethod
    def _gather_fn(pool_layers, phys):
        """Swap-out: pull pages ``phys`` out of every slab (pad = scratch)."""
        return jax.tree.map(lambda pool: pool[:, phys], pool_layers)

    @staticmethod
    def _page_in_fn(pool_layers, rows_layers, phys):
        """Swap-in: write gathered page rows back at new physical ids."""
        return jax.tree.map(
            lambda pool, rows: pool.at[:, phys].set(rows.astype(pool.dtype)),
            pool_layers, rows_layers)

    def _pull_scores(self) -> np.ndarray:
        scores = self._scores(self.cache["layers"])
        with self.tel.tracer.span("kv.scores.sync"):
            return np.asarray(scores)

    def export_page_scores(self, table, js) -> list[float]:
        """Per-page DLZS scores for a transfer payload (advisory: the
        importer recomputes scores from the uploaded page content)."""
        scores = self._pull_scores()
        return [float(scores[table[j]]) for j in js]

    # -- admission ----------------------------------------------------------

    def check_capacity(self, rid: int, total: int, need: int) -> None:
        if need > self.pool.n_pages - 1:
            raise ValueError(
                f"request {rid}: {total} tokens needs {need} pages; "
                f"pool holds {self.pool.n_pages - 1}")
        if self.batched and need - 1 > self.batch_wp:
            raise ValueError(
                f"request {rid}: {need} pages exceeds the batched "
                f"chunk-prefill past window ({self.batch_wp} pages); "
                f"raise PagedEngineCfg.batch_past_pages")

    # -- pool primitives ------------------------------------------------------

    def alloc_chunk(self, pf, start_page: int, n_need: int
                    ) -> tuple[list[int], list[int], bool]:
        scores = (self._pull_scores()
                  if self.pool.free_pages() < n_need else None)
        pages, fresh, _, sharing = self.alloc.admit_chunk(
            pf.toks if pf.toks is not None else pf.prompt,
            start_page, n_need, scores, sharing=pf.sharing)
        fresh_set = set(fresh)
        fresh_globals = [start_page + i for i, pid in enumerate(pages)
                         if pid in fresh_set]
        return pages, fresh_globals, sharing

    def release_pages(self, pages: list[int], start_global: int) -> None:
        self.alloc.release(pages)

    def release_table(self, table: list[int]) -> None:
        self.alloc.release([pid for pid in table if pid >= 0])

    def lookup_prefix(self, g: int, key: tuple) -> Optional[int]:
        return self.pool.lookup(key)

    def register_prefix(self, g: int, key: tuple, pid: int) -> None:
        self.pool.register(key, pid)

    def decref_page(self, g: int, pid: int) -> None:
        self.pool.decref(pid)

    def forget_prefix(self, g: int, pid: int) -> None:
        self.pool.forget(pid)

    def register_prompt_pages(self, toks, table, fresh_globals,
                              start_page: int) -> None:
        page = self.page_size
        for g in fresh_globals:
            end = (g + 1) * page
            if end <= len(toks):
                self.pool.register(toks[:end], table[g])

    def ref_of(self, table, j: int) -> int:
        return self.pool.ref(table[j])

    def held_pages(self, table, shard=None) -> int:
        """Pages preempting this slot would actually FREE: prefix-shared
        pages (ref > 1) survive a victim's release, and lazily-shed
        entries (negative sentinel) already left the device. ``shard`` is
        ignored — this backend runs one pool."""
        return sum(1 for pid in table
                   if pid >= 0 and self.pool.ref(pid) == 1)

    def page_on_shard(self, j: int, shard=None) -> bool:
        return True

    # -- prefill dispatch ------------------------------------------------------

    def dispatch_chunk(self, pf, table, start, end, width, last_idx,
                       pages, fresh_globals) -> np.ndarray:
        page = self.page_size
        start_page = start // page
        toks = bucketing.pad_tokens(pf.prompt[start:end], width)
        batch = {"tokens": jnp.asarray(toks)[None, :]}
        if start == 0:
            logits, cache_one = self._prefill(
                self.params, batch, jnp.asarray([last_idx], jnp.int32))
            self.moe_hist = cache_one.get("moe_hist")
        else:
            wp = bucketing.bucket_count(start_page,
                                        pow2=self.pcfg.bucket_pow2)
            past_phys = np.full((1, wp), -1, np.int32)
            past_phys[0, :start_page] = table[:start_page]
            past_logical = np.full((1, wp), -1, np.int32)
            past_logical[0, :start_page] = np.arange(start_page)
            chunk_state = {
                "past_phys": jnp.asarray(past_phys),
                "past_logical": jnp.asarray(past_logical),
                "past_len": jnp.asarray([start], jnp.int32),
                "last_index": jnp.asarray([last_idx], jnp.int32)}
            logits, cache_one = self._prefill_chunk(
                self.params, batch, {"layers": self.cache["layers"]},
                chunk_state)
            self.moe_hist = cache_one.get("moe_hist")
        # chunk page j -> its fresh pool page; shared pages (content
        # identical by construction) and bucket padding -> scratch
        fresh_set = set(fresh_globals)
        phys = np.full((width // page,), SCRATCH, np.int32)
        for j, pid in enumerate(pages):
            if start_page + j in fresh_set:
                phys[j] = pid
        self.cache["layers"] = self._scatter(
            self.cache["layers"], cache_one["layers"], jnp.asarray(phys))
        # stays on device: middle chunks' logits are never read, and the
        # final chunk's row is materialized once by _finish_prefill
        return logits[0]

    def arena_cost(self, past_pages: int) -> list[int]:
        return [past_pages]

    def dispatch_wave(self, flat, seg, pos, past_len, last_index,
                      lanes) -> dict[int, np.ndarray]:
        """Fill the single-pool past arena + scatter targets for one wave
        and run the compiled batched varlen dispatch."""
        page = self.page_size
        phys_sc = np.full((self.budget_tokens // page,), SCRATCH, np.int32)
        past_phys = np.full((self.batch_wp,), -1, np.int32)
        past_lane = np.full((self.batch_wp,), -1, np.int32)
        past_logical = np.full((self.batch_wp,), -1, np.int32)
        arena = 0
        for lane in lanes:
            slot, table = lane["slot"], lane["table"]
            sp = lane["start_page"]
            past_phys[arena:arena + sp] = table[:sp]
            past_lane[arena:arena + sp] = slot
            past_logical[arena:arena + sp] = np.arange(sp)
            arena += sp
            base = lane["base"]
            for j, pid in enumerate(lane["pages"]):
                if sp + j in lane["fresh"]:
                    phys_sc[base + j] = pid
        if self.tel.enabled:
            self.tel.tracer.instant("arena.fill", used=int(arena),
                                    cap=self.batch_wp,
                                    lanes=len(lanes))
            self.tel.metrics.gauge(
                "engine_arena_pages_used",
                "past-arena slots filled by the last wave").set(int(arena))
        pack_state = {
            "seg_ids": jnp.asarray(seg),
            "positions": jnp.asarray(pos),
            "past_phys": jnp.asarray(past_phys),
            "past_lane": jnp.asarray(past_lane),
            "past_logical": jnp.asarray(past_logical),
            "past_len": jnp.asarray(past_len),
            "last_index": jnp.asarray(last_index)}
        logits, cache_flat = self._prefill_chunk_batch(
            self.params, {"tokens": jnp.asarray(flat)[None, :]},
            {"layers": self.cache["layers"]}, pack_state)
        self.moe_hist = cache_flat.get("moe_hist")
        self.cache["layers"] = self._scatter(
            self.cache["layers"], cache_flat["layers"],
            jnp.asarray(phys_sc))
        with self.tel.tracer.span("prefill.sync"):
            logits_host = np.asarray(logits)
        return {lane["slot"]: logits_host[lane["slot"]] for lane in lanes}

    # -- decode ----------------------------------------------------------------

    def _page_state(self, slots, tables, lengths) -> dict:
        """Assemble block-table rows + write coordinates for this step."""
        with self.tel.tracer.span("decode.page_state"):
            b, w = self.pcfg.max_batch, self.hot_width
            page = self.pcfg.page_size
            phys = np.full((b, w), -1, np.int32)
            logical = np.full((b, w), -1, np.int32)
            write_page = np.full((b,), SCRATCH, np.int32)
            write_off = np.zeros((b,), np.int32)

            # scores are needed for hot-page selection once any table exceeds
            # W, and for eviction whenever the free list cannot cover EVERY
            # sequence growing a page this step (not just when it is empty —
            # the last grower of the step must still evict lowest-score-first).
            # Bounded sphere selection and the quantized tier both put the
            # DLZS prediction on the critical path EVERY step — the LAPA
            # "prediction is cheap enough to always run" claim.
            growers = sum(1 for s in slots
                          if int(lengths[s]) // page == len(tables[s]))
            need_scores = (self.sparse_decode or self.kv_quant
                           or any(len(tables[s]) > w for s in slots)
                           or self.pool.free_pages() < growers)
            scores = self._pull_scores() if need_scores else None
            resident: set[int] = set()
            hot_pids: set[int] = set()
            pages_total = pages_hot = blocks = 0
            per_slot: dict[int, tuple[int, int]] = {}
            for slot in slots:
                table = tables[slot]
                length = int(lengths[slot])
                idx = length // page
                if idx == len(table):          # tail page full: grow
                    try:
                        table.append(self.alloc.extend(scores))
                    except PoolExhausted:
                        raise NeedPages(slot) from None
                cow = self.alloc.ensure_owned(table, idx)
                if cow is not None:            # COW before the write
                    src, dst = cow
                    self.cache["layers"] = self._copy_page(
                        self.cache["layers"], jnp.asarray(src, jnp.int32),
                        jnp.asarray(dst, jnp.int32))
                if self.sparse_decode:
                    ph, lg = self.alloc.select_hot_sphere(
                        table, w, scores, radius=self.hot_radius)
                else:
                    ph, lg = self.alloc.select_hot(table, w, scores)
                phys[slot] = ph
                logical[slot] = lg
                write_page[slot] = table[idx]
                write_off[slot] = length % page
                n_res = sum(1 for pid in table if pid >= 0)
                n_hot = int((lg >= 0).sum())
                pages_total += n_res
                pages_hot += n_hot
                if self.kernel_ppb:
                    blocks += -(-n_hot // self.kernel_ppb)
                per_slot[slot] = (n_res, n_hot)
                if self.kv_quant:
                    resident.update(pid for pid in table if pid >= 0)
                    hot_pids.update(int(p) for p in ph if p >= 0)
            self.decode_sparsity = {"pages_total": pages_total,
                                    "pages_hot": pages_hot,
                                    "shard_skips": 0,
                                    "per_slot": per_slot}
            if self.kernel_ppb:
                self.decode_sparsity.update(
                    blocks_visited=blocks,
                    blocks_window=b * -(-w // self.kernel_ppb))
            out = {"phys": jnp.asarray(phys),
                   "logical": jnp.asarray(logical),
                   "write_page": jnp.asarray(write_page),
                   "write_off": jnp.asarray(write_off)}
            if self.kv_quant:
                out["qmask"] = jnp.asarray(self._quantize_cold(resident,
                                                               hot_pids, phys))
            return out

    def _quantize_cold(self, resident: set, hot_pids: set,
                       phys: np.ndarray) -> np.ndarray:
        """Quantize pages that left the DLZS hot set; build the step's
        [B, W] qmask. Pages hot for ANY sequence stay fp — a page only
        enters the int8 tier once no decode working set wants it exactly.
        Already-quantized pages that turn hot again read their int8 copy
        (the tier is a one-way door until the page is freed), which is
        what ``qmask`` marks."""
        tracker = self.pool.quant
        to_q = sorted(pid for pid in resident - hot_pids
                      if not tracker.is_quant(pid))
        if to_q:
            wq = bucketing.bucket_count(len(to_q),
                                        pow2=self.pcfg.bucket_pow2)
            qphys = np.full((wq,), SCRATCH, np.int32)
            qphys[:len(to_q)] = to_q
            self.cache["layers"] = self._quantize(self.cache["layers"],
                                                  jnp.asarray(qphys))
            for pid in to_q:
                tracker.mark(pid)
        qmask = np.zeros(phys.shape, bool)
        for i in range(phys.shape[0]):
            qmask[i] = [tracker.is_quant(int(p)) for p in phys[i]]
        return qmask

    def decode_step(self, slots, tables, lengths):
        ps = self._page_state(slots, tables, lengths)  # may raise NeedPages
        with self.tel.tracer.span("decode.dispatch"):
            self.cache["lengths"] = jnp.asarray(lengths, jnp.int32)
            logits, cache = self._decode(self.params, self.last_token,
                                         self.cache, ps)
            self.moe_hist = cache.pop("moe_hist", None)
            self.cache = cache
        return logits

    def set_last_token(self, slot: int, tok: int) -> None:
        self.last_token = self.last_token.at[slot, 0].set(tok)

    def get_last_token(self, slot: int) -> int:
        return int(np.asarray(self.last_token[slot, 0]))

    def commit_tokens(self, next_tokens) -> None:
        self.last_token = next_tokens[:, None].astype(jnp.int32)

    # -- shed / swap -------------------------------------------------------------

    def hot_logical(self, table) -> set[int]:
        scores = self._pull_scores()
        if self.sparse_decode:
            _, hot = self.alloc.select_hot_sphere(
                table, self.hot_width, scores, radius=self.hot_radius)
        else:
            _, hot = self.alloc.select_hot(table, self.pcfg.hot_pages,
                                           scores)
        return {int(j) for j in hot if j >= 0}

    def gather_park(self, table, js):
        """Pull pages ``js`` to the host (flat payload order). The gather
        width is pow2-bucketed for jit-shape stability, but only the real
        pages are kept — padding would inflate host swap bytes (and the
        reported swap pressure)."""
        pids = [table[j] for j in js]
        phys = np.full(
            (bucketing.bucket_count(len(pids),
                                    pow2=self.pcfg.bucket_pow2),),
            SCRATCH, np.int32)
        phys[:len(pids)] = pids
        rows = self._gather_pages(self.cache["layers"], jnp.asarray(phys))
        return jax.tree.map(
            lambda r: np.ascontiguousarray(np.asarray(r)[:, :len(pids)]),
            rows)

    def can_hold(self, park_js) -> bool:
        return (self.pool.free_pages() + len(self.pool.evictable())
                >= len(park_js))

    def page_in_extend(self, park_js):
        scores = (self._pull_scores()
                  if self.pool.free_pages() < len(park_js) else None)
        return lambda j: self.alloc.extend(scores)

    def upload_park(self, rows, uploads) -> None:
        w = bucketing.bucket_count(len(uploads),
                                   pow2=self.pcfg.bucket_pow2)
        phys = np.full((w,), SCRATCH, np.int32)
        phys[:len(uploads)] = [pid for _, _, pid in uploads]
        pos = [p for p, _, _ in uploads]
        def sub_rows(r):
            out = np.zeros((r.shape[0], w) + r.shape[2:], r.dtype)
            out[:, :len(pos)] = r[:, pos]
            return out
        self.cache["layers"] = self._page_in(
            self.cache["layers"], jax.tree.map(sub_rows, rows),
            jnp.asarray(phys))
        if self.kv_quant:
            self._restore_quant_flags(rows, uploads)

    def _restore_quant_flags(self, rows, uploads) -> None:
        """Swap-in wrote the payload's int8-tier rows back with the fp
        rows (same single-tree gather carried both out); re-derive which
        restored pages were quantized from the payload's per-page scales
        — a written scale is strictly positive, an fp-only page carries
        the zero-initialized slab row."""
        scale = quant.find_scale(rows)
        if scale is None:
            return
        for pos, _, pid in uploads:
            if float(np.max(scale[:, pos])) > 0.0:
                self.pool.quant.mark(pid)

    # -- observability -------------------------------------------------------------

    def page_accounting(self) -> dict:
        """Host-side pool census for obs.accounting: occupancy by tier,
        COW-shared vs unique pages — straight off the refcount/quant
        tables, no device syncs."""
        pool = self.pool
        live = shared = q_live = 0
        for pid in range(1, pool.n_pages):
            r = pool.ref(pid)
            if r > 0:
                live += 1
                if r > 1:
                    shared += 1
                if pool.quant.is_quant(pid):
                    q_live += 1
        return {"capacity": pool.n_pages - 1, "live": live,
                "free": pool.free_pages(), "cached": len(pool.evictable()),
                "shared": shared, "unique": live - shared,
                "quantized_live": q_live,
                "quantize_events": pool.quant.stats().quantize_events,
                "per_shard": None}

    def pool_refs(self) -> dict:
        """(shard, pid) -> refcount for every page the pool holds a
        reference on — the watchdog reconciles this against what the
        engine's tables/parks imply (obs.accounting)."""
        return {(0, pid): self.pool.ref(pid)
                for pid in range(1, self.pool.n_pages)
                if self.pool.ref(pid) > 0}

    def owner_of(self, j: int) -> int:
        """Shard owning global page index ``j`` (single pool: always 0)."""
        return 0

    def audit_decode(self, slot: int, table, length: int):
        """Exact-attention audit probe for one live decode slot (obs.audit).

        Runs the decode step over the slot's FULL resident page set on a
        non-donated jit (the live cache is read, never consumed) with the
        ``audit`` flag set, so every attention layer reports the softmax
        mass each page receives from the next query token. Returns None at
        a page boundary (the tail page the next step writes does not exist
        yet — the sampler just retries a later tick), else a host dict
        with per-layer masses over residents, the sphere-selected hot
        mask, and per-(layer, page) DLZS scores.
        """
        page = self.pcfg.page_size
        idx = length // page
        if idx >= len(table) or table[idx] < 0:
            return None
        resident = [(j, pid) for j, pid in enumerate(table) if pid >= 0]
        b = self.pcfg.max_batch
        w = bucketing.bucket_count(len(resident), pow2=self.pcfg.bucket_pow2)
        phys = np.full((b, w), -1, np.int32)
        logical = np.full((b, w), -1, np.int32)
        write_page = np.full((b,), SCRATCH, np.int32)
        write_off = np.zeros((b,), np.int32)
        for i, (j, pid) in enumerate(resident):
            phys[slot, i] = pid
            logical[slot, i] = j
        write_page[slot] = table[idx]
        write_off[slot] = length % page
        ps = {"phys": jnp.asarray(phys), "logical": jnp.asarray(logical),
              "write_page": jnp.asarray(write_page),
              "write_off": jnp.asarray(write_off),
              "audit": jnp.zeros((), jnp.int32)}
        lengths_vec = np.zeros((b,), np.int32)
        lengths_vec[slot] = length
        cache = {"layers": self.cache["layers"],
                 "lengths": jnp.asarray(lengths_vec)}
        _, out_cache = self._audit(self.params, self.last_token, cache, ps)
        leaves = jax.tree_util.tree_flatten_with_path(out_cache["layers"])[0]
        mass = np.concatenate(
            [np.asarray(leaf)[:, slot, :len(resident)]
             for path, leaf in leaves
             if any(isinstance(k, jax.tree_util.DictKey)
                    and k.key == "audit_mass" for k in path)],
            axis=0)                                  # [n_layers, n_res]

        # the hot set the NEXT decode step would gather (same selector,
        # same scores pull)
        scores = self._pull_scores()
        if self.sparse_decode:
            _, lg = self.alloc.select_hot_sphere(
                table, self.hot_width, scores, radius=self.hot_radius)
        else:
            _, lg = self.alloc.select_hot(table, self.hot_width, scores)
        hot_js = {int(j) for j in lg if j >= 0}
        hot_mask = np.array([j in hot_js for j, _ in resident], bool)

        pids = [pid for _, pid in resident]
        try:
            sl = np.asarray(self._scores_by_layer(self.cache["layers"]))
            scores_layers = sl[:, pids].tolist()
        except ValueError:
            scores_layers = None
        tot = np.maximum(mass.sum(axis=1), 1e-30)
        recall = (mass[:, hot_mask].sum(axis=1) / tot)
        return {"slot": slot, "length": length,
                "pages_resident": len(resident),
                "pages_hot": len(hot_js),
                "hot_mask": hot_mask.tolist(),
                "mass_per_layer": mass.tolist(),
                "recall_per_layer": recall.tolist(),
                "scores_per_layer": scores_layers,
                "per_shard": None}

    def stats(self) -> dict:
        pool = self.pool.stats()
        per_page = metrics.bytes_per_page(self.cache["layers"])
        out = {
            "pool": pool,
            "bytes_per_page": per_page,
            "working_set_bytes": pool.peak_live * per_page,
            "slab_bytes": metrics.tree_bytes(self.cache["layers"]),
            "decode_compiles": self._decode._cache_size(),
            "prefill_batch_compiles": self._prefill_chunk_batch._cache_size(),
            "hot_width": self.hot_width,
        }
        if self.kv_quant:
            base, tier = quant.split_quant(self.cache["layers"])
            fp_pp = metrics.bytes_per_page(base)
            q_pp = metrics.bytes_per_page(tier)
            live = [pid for pid in range(1, self.pool.n_pages)
                    if self.pool.ref(pid) > 0]
            q_live = sum(1 for pid in live
                         if self.pool.quant.is_quant(pid))
            frac = q_live / max(len(live), 1)
            blended = max((1 - frac) * fp_pp + frac * q_pp, 1.0)
            out["kv_quant"] = {
                "pages_quantized_live": q_live,
                "quantize_events": self.pool.quant.stats().quantize_events,
                "bytes_per_page_fp": fp_pp,
                "bytes_per_page_int8": q_pp,
                # pages the same byte budget would hold if cold pages
                # were stored int8-only, at the CURRENT live hot/cold mix
                "effective_capacity_pages": int(pool.capacity * fp_pp
                                                / blended),
            }
        return out


class PagedServingEngine(EngineCore):
    """The single-pool serving engine: ``PagedBackend`` under the shared
    ``EngineCore`` executor. Thin by design — every scheduler-visible
    behavior lives in engine_core.py."""

    def __init__(self, model_cfg, params, pcfg: PagedEngineCfg,
                 scfg: Optional[SchedulerCfg] = None,
                 rng: Optional[jax.Array] = None):
        scfg = scfg or SchedulerCfg()
        super().__init__(PagedBackend(model_cfg, params, pcfg, scfg),
                         scfg, rng)

    @property
    def pcfg(self) -> PagedEngineCfg:
        return self.backend.pcfg

    @property
    def pool(self) -> PagePool:
        return self.backend.pool

    @property
    def alloc(self) -> PagedAllocator:
        return self.backend.alloc

    @property
    def last_token(self):
        return self.backend.last_token

    @property
    def cache(self):
        return self.backend.cache
