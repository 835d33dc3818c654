"""Sequence-sharded serving backend across a device mesh.

One request's KV context is STRIPED page-by-page across ``n_shards``
devices (repro.spatial.topology), so the longest servable prompt — and
the aggregate decode working set — scales with device count instead of
being capped by a single device's page pool. This is the serving-side
realization of the paper's Spatial-STAR deployment: per-shard pools with
per-shard DLZS retention, replicated block-stack compute, and partial
softmax ``(m, l, o)`` states merged across shards (DRAttention's
combination) for every cross-shard attention.

Dataflow per phase (each a single SPMD shard_map dispatch — see
``lm.prefill_chunk_spatial`` / ``lm.decode_step_spatial``):

* chunked prefill — the chunk's activations are replicated; every shard
  computes a partial state of the chunk queries against ITS resident
  past pages (the causal cross-shard part), the partials merge with
  pmax/psum, and each shard scatters the chunk's K/V rows into the pages
  it owns. Exact — same math as the paged engine's gather+softmax, in a
  different reduction order.
* decode — the query token is broadcast, each shard attends over its
  local hot pages via the paged gather (DLZS page scores pick them,
  per shard), and the partial states merge to the final output. Decode
  compiles ONCE: shapes depend only on (max_batch, hot_pages_local,
  n_pages_local).

The entire executor state machine — admission, chunked + batched varlen
prefill (the allocate/dedup/wave-split/commit scaffold), decode loop,
lazy cold-page shedding, preempt/swap — is the SHARED
``serving.engine_core.EngineCore``; this module only implements the
``Backend`` protocol over sharded pools and shard_map dispatches.
Pressure is shard-tagged: a starved shard picks victims (and lazy-shed
pages) that actually free memory THERE. Because the scaffold is shared,
the spatial engine gets lazy cold-page shedding, prefill-budget
autotuning, and every future scheduler feature for free.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.kvcache import SCRATCH, bucketing, metrics, quant
from repro.models import lm
from repro.obs import NULL_TELEMETRY
from repro.serving.engine_core import EngineCore
from repro.serving.scheduler import (NeedPages, SchedulerCfg,
                                     resolve_prefill_tokens)
from repro.spatial.sharded_pool import ShardedPagePools, ShardPoolExhausted
from repro.spatial.topology import ShardTopology

__all__ = ["SpatialEngineCfg", "SpatialBackend", "SpatialServingEngine"]


@dataclasses.dataclass(frozen=True)
class SpatialEngineCfg:
    n_shards: int = 2
    max_batch: int = 8
    page_size: int = 16
    n_pages_local: int = 64      # per-shard pool capacity (page 0 scratch)
    hot_pages_local: int = 16    # W: pages gathered per shard per decode
    recent_pages: int = 2        # newest LOCAL pages always hot per shard
    eos_id: int = 1
    greedy: bool = True
    temperature: float = 1.0
    bucket_pow2: bool = True
    share_prefixes: bool = True
    batch_past_pages: Optional[int] = None
    # Per-SHARD past-page gather width of the batched chunk-prefill
    # dispatch (SchedulerCfg.prefill_tokens); None sizes it to a whole
    # local pool. Fixed at init so the batched spatial prefill compiles
    # exactly once.


class SpatialBackend:
    """Sharded-pool + shard_map ``engine_core.Backend`` implementation."""

    def __init__(self, model_cfg, params, pcfg: SpatialEngineCfg,
                 scfg: SchedulerCfg):
        if any(blk.kind != "attn" for blk in model_cfg.pattern):
            raise ValueError("spatial engine supports attention-only "
                             "patterns")
        if model_cfg.enc_layers or not model_cfg.causal:
            raise ValueError("spatial engine needs a causal decoder-only "
                             "model")
        if model_cfg.star is not None:
            raise ValueError(
                "spatial engine serves dense-attention configs; sparsity "
                "comes from per-shard DLZS hot-page retention at decode")
        if model_cfg.has_moe or model_cfg.qk_norm:
            raise ValueError(
                "spatial engine serves dense-FFN models without QK-norm; "
                "MoE and QK-norm models serve on the paged backend")
        self.cfg = model_cfg
        self.pcfg = pcfg
        self.topo = ShardTopology(pcfg.n_shards)
        self.mesh = self.topo.make_mesh()
        # every dispatch takes the weights replicated over the mesh: place
        # them once, or each call re-broadcasts them from one device
        from jax.sharding import NamedSharding, PartitionSpec as P
        self.params = jax.device_put(params, NamedSharding(self.mesh, P()))
        self.pools = ShardedPagePools(
            self.topo, pcfg.n_pages_local, pcfg.page_size,
            recent_pages=pcfg.recent_pages)
        self.tel = NULL_TELEMETRY    # shared via EngineCore.attach_telemetry

        # protocol facts EngineCore reads
        self.page_size = pcfg.page_size
        self.max_batch = pcfg.max_batch
        self.eos_id = pcfg.eos_id
        self.greedy = pcfg.greedy
        self.temperature = pcfg.temperature
        self.bucket_pow2 = pcfg.bucket_pow2
        self.share = pcfg.share_prefixes
        # a shed must keep the newest local page window of EVERY shard
        # resident: striping maps the newest r locals per shard onto the
        # newest ~r*n_shards global pages
        self.keep_recent = max(1, pcfg.recent_pages) * pcfg.n_shards

        # decode-time DLZS sparsity + int8 cold tier (SchedulerCfg knobs;
        # see serving.paged for the single-pool shape of the same wiring).
        # The width cap applies PER SHARD: each shard's slice keeps at
        # most min(hot_pages_local, decode_hot_width) sphere-rule pages,
        # and a shard whose every slice comes back empty skips its psum
        # contribution (attention.apply_decode_spatial).
        self.sparse_decode = scfg.decode_hot_width is not None
        self.hot_width = (min(pcfg.hot_pages_local, scfg.decode_hot_width)
                          if self.sparse_decode else pcfg.hot_pages_local)
        self.hot_radius = scfg.decode_hot_radius
        if scfg.kv_quant not in (None, "int8"):
            raise ValueError(
                f"kv_quant={scfg.kv_quant!r}: choose None or 'int8'")
        self.kv_quant = scfg.kv_quant == "int8"
        self.decode_sparsity = None  # telemetry dict, set per decode step

        # batched varlen chunk prefill (one shard_map dispatch per tick):
        # fixed flat width + fixed per-shard past window => one compile
        max_tokens = resolve_prefill_tokens(scfg, pcfg.page_size)
        self.batched = max_tokens is not None
        self.budget_tokens = self.batch_wp = None
        if self.batched:
            self.budget_tokens = bucketing.budget_tokens(
                max_tokens, pcfg.page_size, scfg.chunk_pages,
                pow2=pcfg.bucket_pow2)
            self.batch_wp = bucketing.bucket_count(
                pcfg.batch_past_pages or pcfg.n_pages_local - 1,
                pow2=pcfg.bucket_pow2)

        # each program compiles under its method's name, which the
        # profiler trace shows (jit__decode_fn, jit__prefill_chunk_fn, ...)
        self._prefill_chunk = jax.jit(self._prefill_chunk_fn,
                                      donate_argnums=(2,))
        self._prefill_chunk_batch = jax.jit(self._prefill_chunk_batch_fn,
                                            donate_argnums=(2,))
        self._decode = jax.jit(self._decode_fn, donate_argnums=(2,))
        # audit probe (obs.audit): reads the live cache, returns only the
        # stacked per-page masses — never donated
        self._audit = jax.jit(self._audit_fn)
        self._copy_page = jax.jit(self._copy_fn, static_argnums=(3,))
        self._gather_pages = jax.jit(self._gather_fn)
        self._page_in = jax.jit(self._page_in_fn, donate_argnums=(0,))
        self._scores = jax.jit(jax.vmap(metrics.page_scores))
        self._scores_by_layer = jax.jit(
            jax.vmap(metrics.page_scores_per_layer))

        # Per-shard pool slabs from a one-page probe prefill: each leaf
        # [L, 1, page, nkv, dh] becomes [n_shards, L, P_local, page, nkv,
        # dh], sharded over the mesh axis (one slab stack per device).
        probe = {"tokens": jnp.zeros((1, pcfg.page_size), jnp.int32)}

        def prefill_probe(p, b):
            return lm.prefill(p, model_cfg, b,
                              last_index=jnp.zeros((1,), jnp.int32))
        _, cache_one = jax.jit(prefill_probe)(params, probe)
        spec = NamedSharding(self.mesh, P(self.topo.axis))
        def slab(leaf):
            shape = (self.topo.n_shards, leaf.shape[0],
                     pcfg.n_pages_local) + leaf.shape[2:]
            return jax.device_put(jnp.zeros(shape, leaf.dtype), spec)
        layers = jax.tree.map(slab, cache_one["layers"])
        if self.kv_quant:
            # int8 tier slabs ride in the same sharded tree ([S, L, P,
            # ...] / scales [S, L, P]); re-place so every leaf carries
            # the mesh sharding the decode dispatch expects
            layers = jax.tree.map(lambda l: jax.device_put(l, spec),
                                  quant.add_quant_slabs(layers))
            self._quantize = jax.jit(quant.quantize_pages_sharded,
                                     donate_argnums=(0,))
        self.cache = {
            "layers": layers,
            "lengths": jnp.zeros((pcfg.max_batch,), jnp.int32),
        }
        # committed-replicated so the decode signature never flips between
        # the first call (fresh buffer) and later ones (jit outputs) —
        # keeps the one-decode-compilation invariant
        self.last_token = jax.device_put(
            jnp.zeros((pcfg.max_batch, 1), jnp.int32),
            NamedSharding(self.mesh, P()))
        # per-page byte prices (shape-only, one shard's slice): the full
        # tree row a swap payload carries vs the fp K/V rows the decode
        # gather reads — obs.accounting prices page traffic with these
        one = jax.tree.map(lambda leaf: leaf[0], self.cache["layers"])
        self.page_bytes_full = metrics.bytes_per_page(one)
        self.page_bytes_gather = metrics.gather_bytes_per_page(one)
        self.page_bytes_int8 = metrics.quant_bytes_per_page(one)

    # -- jitted kernels -----------------------------------------------------

    def _prefill_chunk_fn(self, params, batch, cache, chunk_state):
        return lm.prefill_chunk_spatial(params, self.cfg, batch, cache,
                                        chunk_state, mesh=self.mesh,
                                        axis=self.topo.axis)

    def _prefill_chunk_batch_fn(self, params, batch, cache, pack_state):
        return lm.prefill_chunk_batch_spatial(params, self.cfg, batch,
                                              cache, pack_state,
                                              mesh=self.mesh,
                                              axis=self.topo.axis)

    def _decode_fn(self, params, tokens, cache, page_state):
        return lm.decode_step_spatial(params, self.cfg, tokens, cache,
                                      page_state, mesh=self.mesh,
                                      axis=self.topo.axis)

    def _audit_fn(self, params, tokens, cache, page_state):
        return lm.audit_decode_spatial(params, self.cfg, tokens, cache,
                                       page_state, mesh=self.mesh,
                                       axis=self.topo.axis)

    @staticmethod
    def _copy_fn(pool_layers, src, dst, shard):
        """COW on one shard: duplicate local page src -> dst (all layers).
        ``shard`` is static — at most n_shards tiny compilations."""
        return jax.tree.map(
            lambda pool: pool.at[shard, :, dst].set(pool[shard, :, src]),
            pool_layers)

    @staticmethod
    def _gather_fn(pool_layers, phys):
        """Swap-out: pull local pages ``phys[s]`` out of every shard's
        slab (pad = scratch). phys [n_shards, Wpad]."""
        take = lambda slab, ix: slab[:, ix]
        return jax.tree.map(
            lambda slab: jax.vmap(take)(slab, phys), pool_layers)

    @staticmethod
    def _page_in_fn(pool_layers, rows_layers, phys):
        """Swap-in: write gathered rows back at new per-shard local ids."""
        put = lambda slab, r, ix: slab.at[:, ix].set(r.astype(slab.dtype))
        return jax.tree.map(
            lambda slab, r: jax.vmap(put)(slab, r, phys),
            pool_layers, rows_layers)

    def _pull_scores(self) -> np.ndarray:
        """Per-shard DLZS page scores [n_shards, n_pages_local]."""
        scores = self._scores(self.cache["layers"])
        with self.tel.tracer.span("kv.scores.sync"):
            return np.asarray(scores)

    # -- admission ----------------------------------------------------------

    def check_capacity(self, rid: int, total: int, need: int) -> None:
        if not self.pools.fits(need):
            raise ValueError(
                f"request {rid}: {total} tokens needs {need} striped "
                f"pages; {self.topo.n_shards} shards x "
                f"{self.pcfg.n_pages_local - 1} pages cannot hold them")
        if self.batched and self.topo.max_local_count(need) > self.batch_wp:
            raise ValueError(
                f"request {rid}: {need} striped pages exceeds the "
                f"batched chunk-prefill past window ({self.batch_wp} "
                f"pages/shard); raise SpatialEngineCfg.batch_past_pages")

    # -- pool primitives -----------------------------------------------------

    def alloc_chunk(self, pf, start_page: int, n_need: int
                    ) -> tuple[list[int], list[int], bool]:
        scores = self._pull_scores() \
            if any(self.pools.free_pages(s) < n_need
                   for s in range(self.topo.n_shards)) else None
        return self.pools.admit_chunk(pf.toks, start_page, n_need,
                                      scores, sharing=pf.sharing)

    def release_pages(self, pages: list[int], start_global: int) -> None:
        for i, pid in enumerate(pages):
            self.pools.pools[self.topo.owner(start_global + i)].decref(pid)

    def release_table(self, table: list[int]) -> None:
        for j, pid in enumerate(table):
            if pid >= 0:
                self.pools.pools[self.topo.owner(j)].decref(pid)

    def lookup_prefix(self, g: int, key: tuple) -> Optional[int]:
        return self.pools.pools[self.topo.owner(g)].lookup(key)

    def register_prefix(self, g: int, key: tuple, pid: int) -> None:
        self.pools.pools[self.topo.owner(g)].register(key, pid)

    def decref_page(self, g: int, pid: int) -> None:
        self.pools.pools[self.topo.owner(g)].decref(pid)

    def forget_prefix(self, g: int, pid: int) -> None:
        self.pools.pools[self.topo.owner(g)].forget(pid)

    def register_prompt_pages(self, toks, table, fresh_globals,
                              start_page: int) -> None:
        self.pools.register_prompt_pages(toks, table, fresh_globals)

    def ref_of(self, table, j: int) -> int:
        return self.pools.pools[self.topo.owner(j)].ref(table[j])

    def held_pages(self, table, shard: Optional[int] = None) -> int:
        return self.pools.held_pages(table, shard)

    def page_on_shard(self, j: int, shard: Optional[int] = None) -> bool:
        return shard is None or self.topo.owner(j) == shard

    # -- prefill dispatch -----------------------------------------------------

    def _past_state(self, table: list[int], start_page: int
                    ) -> tuple[np.ndarray, np.ndarray]:
        """Per-shard (past_phys, past_logical) [n_shards, 1, Wp] of the
        pages earlier chunks wrote. Wp is pow2-bucketed on the largest
        per-shard count so chunk compiles stay O(log^2)."""
        n = self.topo.n_shards
        wp = bucketing.bucket_count(
            max(1, self.topo.max_local_count(start_page)),
            pow2=self.pcfg.bucket_pow2)
        phys = np.full((n, 1, wp), -1, np.int32)
        logical = np.full((n, 1, wp), -1, np.int32)
        for s in range(n):
            globals_ = list(range(s, start_page, n))
            phys[s, 0, :len(globals_)] = [table[j] for j in globals_]
            logical[s, 0, :len(globals_)] = globals_
        return phys, logical

    def dispatch_chunk(self, pf, table, start, end, width, last_idx,
                       pages, fresh_globals) -> np.ndarray:
        page = self.page_size
        start_page = start // page
        toks = bucketing.pad_tokens(pf.prompt[start:end], width)
        batch = {"tokens": jnp.asarray(toks)[None, :]}
        # chunk page targets: the owner shard scatters fresh pages,
        # everything else (shared content, bucket padding) -> scratch
        n = self.topo.n_shards
        fresh_set = set(fresh_globals)
        chunk_phys = np.full((n, 1, width // page), SCRATCH, np.int32)
        for cj in range(len(pages)):
            g = start_page + cj
            if g in fresh_set:
                chunk_phys[self.topo.owner(g), 0, cj] = table[g]
        past_phys, past_logical = self._past_state(table, start_page)
        chunk_state = {
            "past_phys": jnp.asarray(past_phys),
            "past_logical": jnp.asarray(past_logical),
            "chunk_phys": jnp.asarray(chunk_phys),
            "past_len": jnp.asarray([start], jnp.int32),
            "last_index": jnp.asarray([last_idx], jnp.int32)}
        logits, new_cache = self._prefill_chunk(
            self.params, batch, {"layers": self.cache["layers"]},
            chunk_state)
        self.cache["layers"] = new_cache["layers"]
        # stays on device: middle chunks' logits are never read, and the
        # final chunk's row is materialized once by _finish_prefill
        return logits[0]

    def arena_cost(self, past_pages: int) -> list[int]:
        # striping puts ~past_pages/n past slots on each shard's arena
        return [self.topo.local_count(past_pages, s)
                for s in range(self.topo.n_shards)]

    def dispatch_wave(self, flat, seg, pos, past_len, last_index,
                      lanes) -> dict[int, np.ndarray]:
        """Fill the per-SHARD past arenas + chunk scatter targets for one
        wave and run the single compiled shard_map dispatch, cross-shard
        softmax merged through the usual pmax/psum tree."""
        page, n_sh = self.page_size, self.topo.n_shards
        b_tok, wp = self.budget_tokens, self.batch_wp
        chunk_phys = np.full((n_sh, 1, b_tok // page), SCRATCH, np.int32)
        past_phys = np.full((n_sh, wp), -1, np.int32)
        past_lane = np.full((n_sh, wp), -1, np.int32)
        past_logical = np.full((n_sh, wp), -1, np.int32)
        arena = [0] * n_sh
        for lane in lanes:
            slot, table = lane["slot"], lane["table"]
            sp = lane["start_page"]
            for s in range(n_sh):
                globals_ = list(range(s, sp, n_sh))
                a = arena[s]
                past_phys[s, a:a + len(globals_)] = \
                    [table[j] for j in globals_]
                past_lane[s, a:a + len(globals_)] = slot
                past_logical[s, a:a + len(globals_)] = globals_
                arena[s] = a + len(globals_)
            base = lane["base"]
            for cj, pid in enumerate(lane["pages"]):
                g = sp + cj
                if g in lane["fresh"]:
                    chunk_phys[self.topo.owner(g), 0, base + cj] = pid
        if self.tel.enabled:
            for s in range(n_sh):      # shard-tagged arena occupancy
                self.tel.tracer.instant("arena.fill", tid=s + 1,
                                        shard=s, used=int(arena[s]),
                                        cap=wp, lanes=len(lanes))
                self.tel.metrics.gauge(
                    "engine_arena_pages_used",
                    "past-arena slots filled by the last wave").set(
                    int(arena[s]), shard=s)
        pack_state = {
            "seg_ids": jnp.asarray(seg),
            "positions": jnp.asarray(pos),
            "past_phys": jnp.asarray(past_phys),
            "past_lane": jnp.asarray(past_lane),
            "past_logical": jnp.asarray(past_logical),
            "chunk_phys": jnp.asarray(chunk_phys),
            "past_len": jnp.asarray(past_len),
            "last_index": jnp.asarray(last_index)}
        logits, new_cache = self._prefill_chunk_batch(
            self.params, {"tokens": jnp.asarray(flat)[None, :]},
            {"layers": self.cache["layers"]}, pack_state)
        self.cache["layers"] = new_cache["layers"]
        with self.tel.tracer.span("prefill.sync"):
            logits_host = np.asarray(logits)
        return {lane["slot"]: logits_host[lane["slot"]] for lane in lanes}

    # -- decode ----------------------------------------------------------------

    def _page_state(self, slots, tables, lengths) -> dict:
        with self.tel.tracer.span("decode.page_state"):
            n = self.topo.n_shards
            b, w = self.pcfg.max_batch, self.hot_width
            page = self.pcfg.page_size
            phys = np.full((n, b, w), -1, np.int32)
            logical = np.full((n, b, w), -1, np.int32)
            write_page = np.full((n, b), SCRATCH, np.int32)
            write_off = np.zeros((n, b), np.int32)

            growers = [slot for slot in slots
                       if int(lengths[slot]) // page == len(tables[slot])]
            grow_by_shard = [0] * n
            for slot in growers:
                grow_by_shard[self.topo.owner(len(tables[slot]))] += 1
            need_scores = (
                self.sparse_decode or self.kv_quant
                or any(self.topo.max_local_count(len(tables[s])) > w
                       for s in slots)
                or any(self.pools.free_pages(s) < grow_by_shard[s]
                       for s in range(n)))
            scores = self._pull_scores() if need_scores else None
            resident = [set() for _ in range(n)]     # local pids per shard
            hot_pids = [set() for _ in range(n)]
            pages_total = pages_hot = 0
            per_slot: dict[int, tuple[int, int]] = {}
            for slot in slots:
                table = tables[slot]
                length = int(lengths[slot])
                idx = length // page
                if idx == len(table):              # tail page full: grow
                    try:
                        table.append(self.pools.extend(idx, scores))
                    except ShardPoolExhausted as e:
                        raise NeedPages(slot, e.shard) from None
                cow = self.pools.ensure_owned(table, idx)
                if cow is not None:
                    shard, src, dst = cow
                    self.cache["layers"] = self._copy_page(
                        self.cache["layers"], jnp.asarray(src, jnp.int32),
                        jnp.asarray(dst, jnp.int32), shard)
                slot_hot = 0
                for s in range(n):
                    if self.sparse_decode:
                        ph, lg = self.pools.select_hot_sphere(
                            table, s, w, scores, radius=self.hot_radius)
                    else:
                        ph, lg = self.pools.select_hot(table, s, w, scores)
                    phys[s, slot] = ph
                    logical[s, slot] = lg
                    slot_hot += int((lg >= 0).sum())
                    if self.kv_quant:
                        locals_, _ = self.pools.local_pages(table, s)
                        resident[s].update(p for p in locals_ if p >= 0)
                        hot_pids[s].update(int(p) for p in ph if p >= 0)
                pages_hot += slot_hot
                n_res = sum(1 for pid in table if pid >= 0)
                pages_total += n_res
                per_slot[slot] = (n_res, slot_hot)
                owner = self.topo.owner(idx)
                write_page[owner, slot] = table[idx]
                write_off[owner, slot] = length % page
            # DLZS-guided communication sparsity: shards whose hot sets are
            # empty for the ENTIRE batch skip their local attention + psum
            # contribution this step (the lax.cond in apply_decode_spatial)
            shard_skips = (sum(1 for s in range(n)
                               if not (logical[s] >= 0).any())
                           if slots else 0)
            self.decode_sparsity = {"pages_total": pages_total,
                                    "pages_hot": pages_hot,
                                    "shard_skips": shard_skips,
                                    "per_slot": per_slot}
            out = {"phys": jnp.asarray(phys),
                   "logical": jnp.asarray(logical),
                   "write_page": jnp.asarray(write_page),
                   "write_off": jnp.asarray(write_off)}
            if self.kv_quant:
                out["qmask"] = jnp.asarray(
                    self._quantize_cold(resident, hot_pids, phys))
            return out

    def _quantize_cold(self, resident: list, hot_pids: list,
                       phys: np.ndarray) -> np.ndarray:
        """Per-shard cold-page quantization + the step's [S, B, W] qmask
        (single-pool semantics per shard — see serving.paged)."""
        n = self.topo.n_shards
        to_q = [sorted(pid for pid in resident[s] - hot_pids[s]
                       if not self.pools.pools[s].quant.is_quant(pid))
                for s in range(n)]
        if any(to_q):
            wq = bucketing.bucket_count(max(len(t) for t in to_q),
                                        pow2=self.pcfg.bucket_pow2)
            qphys = np.full((n, wq), SCRATCH, np.int32)
            for s in range(n):
                qphys[s, :len(to_q[s])] = to_q[s]
            self.cache["layers"] = self._quantize(self.cache["layers"],
                                                  jnp.asarray(qphys))
            for s in range(n):
                for pid in to_q[s]:
                    self.pools.pools[s].quant.mark(pid)
        qmask = np.zeros(phys.shape, bool)
        for s in range(n):
            tracker = self.pools.pools[s].quant
            for i in range(phys.shape[1]):
                qmask[s, i] = [tracker.is_quant(int(p))
                               for p in phys[s, i]]
        return qmask

    def decode_step(self, slots, tables, lengths):
        ps = self._page_state(slots, tables, lengths)  # may raise NeedPages
        with self.tel.tracer.span("decode.dispatch"):
            self.cache["lengths"] = jnp.asarray(lengths, jnp.int32)
            logits, self.cache = self._decode(self.params, self.last_token,
                                              self.cache, ps)
        return logits

    def set_last_token(self, slot: int, tok: int) -> None:
        self.last_token = self.last_token.at[slot, 0].set(tok)

    def get_last_token(self, slot: int) -> int:
        return int(np.asarray(self.last_token[slot, 0]))

    def commit_tokens(self, next_tokens) -> None:
        self.last_token = next_tokens[:, None].astype(jnp.int32)

    # -- shed / swap -----------------------------------------------------------

    def hot_logical(self, table) -> set[int]:
        """Union of every shard's DLZS hot selection (global indices)."""
        scores = self._pull_scores()
        hot: set[int] = set()
        for s in range(self.topo.n_shards):
            if self.sparse_decode:
                _, lg = self.pools.select_hot_sphere(
                    table, s, self.hot_width, scores,
                    radius=self.hot_radius)
            else:
                _, lg = self.pools.select_hot(
                    table, s, self.pcfg.hot_pages_local, scores)
            hot.update(int(j) for j in lg if j >= 0)
        return hot

    def gather_park(self, table, js):
        """Pull global pages ``js`` to the host in flat payload order —
        the gather runs per shard (pow2-padded local widths for jit-shape
        stability), then the real pages are re-flattened so the payload
        layout matches the single-pool backend's exactly."""
        n = self.topo.n_shards
        by_shard = [[j for j in js if self.topo.owner(j) == s]
                    for s in range(n)]
        wpad = bucketing.bucket_count(
            max(1, max(len(b) for b in by_shard)),
            pow2=self.pcfg.bucket_pow2)
        phys = np.full((n, wpad), SCRATCH, np.int32)
        for s in range(n):
            phys[s, :len(by_shard[s])] = [table[j] for j in by_shard[s]]
        rows = self._gather_pages(self.cache["layers"], jnp.asarray(phys))
        pos_of = {j: (s, k) for s in range(n)
                  for k, j in enumerate(by_shard[s])}
        def flatten(r):
            r = np.asarray(r)                   # [n_sh, L, wpad, ...]
            out = np.empty((r.shape[1], len(js)) + r.shape[3:], r.dtype)
            for p, j in enumerate(js):
                s, k = pos_of[j]
                out[:, p] = r[s, :, k]
            return out
        return jax.tree.map(flatten, rows)

    def can_hold(self, park_js) -> bool:
        counts = [0] * self.topo.n_shards
        for j in park_js:
            counts[self.topo.owner(j)] += 1
        return all(self.pools.reclaimable(s) >= counts[s]
                   for s in range(self.topo.n_shards))

    def page_in_extend(self, park_js):
        counts = [0] * self.topo.n_shards
        for j in park_js:
            counts[self.topo.owner(j)] += 1
        scores = self._pull_scores() \
            if any(self.pools.free_pages(s) < counts[s]
                   for s in range(self.topo.n_shards)) else None
        def extend(j):
            s = self.topo.owner(j)
            return self.pools.allocs[s].extend(
                scores[s] if scores is not None else None)
        return extend

    def upload_park(self, rows, uploads) -> None:
        """Regroup flat payload rows by owner shard and write them back
        through the per-shard page-in scatter."""
        n = self.topo.n_shards
        per_shard: list[list[tuple[int, int]]] = [[] for _ in range(n)]
        for pos, j, pid in uploads:
            per_shard[self.topo.owner(j)].append((pos, pid))
        wpad = bucketing.bucket_count(
            max(1, max(len(u) for u in per_shard)),
            pow2=self.pcfg.bucket_pow2)
        phys = np.full((n, wpad), SCRATCH, np.int32)
        for s in range(n):
            phys[s, :len(per_shard[s])] = [pid for _, pid in per_shard[s]]
        def sub_rows(r):                        # r: [L, n_park, ...] flat
            out = np.zeros((n, r.shape[0], wpad) + r.shape[2:], r.dtype)
            for s in range(n):
                pos = [p for p, _ in per_shard[s]]
                if pos:
                    out[s, :, :len(pos)] = r[:, pos]
            return out
        self.cache["layers"] = self._page_in(
            self.cache["layers"], jax.tree.map(sub_rows, rows),
            jnp.asarray(phys))
        if self.kv_quant:
            scale = quant.find_scale(rows)      # flat payload [L, n_park]
            if scale is not None:
                for pos, j, pid in uploads:
                    if float(np.max(scale[:, pos])) > 0.0:
                        self.pools.pools[self.topo.owner(j)].quant.mark(pid)

    # -- observability -----------------------------------------------------------

    def page_accounting(self) -> dict:
        """Host-side census over every shard pool (obs.accounting) plus a
        per-shard breakdown. No device syncs."""
        tot = {"capacity": 0, "live": 0, "free": 0, "cached": 0,
               "shared": 0, "unique": 0, "quantized_live": 0,
               "quantize_events": 0}
        per_shard = []
        for s in range(self.topo.n_shards):
            pool = self.pools.pools[s]
            live = shared = q_live = 0
            for pid in range(1, pool.n_pages):
                r = pool.ref(pid)
                if r > 0:
                    live += 1
                    if r > 1:
                        shared += 1
                    if pool.quant.is_quant(pid):
                        q_live += 1
            row = {"shard": s, "capacity": pool.n_pages - 1, "live": live,
                   "free": pool.free_pages(),
                   "cached": len(pool.evictable()),
                   "shared": shared, "unique": live - shared,
                   "quantized_live": q_live,
                   "quantize_events": pool.quant.stats().quantize_events}
            per_shard.append(row)
            for k in tot:
                tot[k] += row[k]
        tot["per_shard"] = per_shard
        return tot

    def pool_refs(self) -> dict:
        """(shard, pid) -> refcount for every live page on every shard."""
        out = {}
        for s in range(self.topo.n_shards):
            pool = self.pools.pools[s]
            for pid in range(1, pool.n_pages):
                r = pool.ref(pid)
                if r > 0:
                    out[(s, pid)] = r
        return out

    def owner_of(self, j: int) -> int:
        return self.topo.owner(j)

    def export_page_scores(self, table, js) -> list[float]:
        """Per-page DLZS scores for a transfer payload, resolved on each
        page's owner shard (advisory: the importer recomputes)."""
        scores = self._pull_scores()
        return [float(scores[self.topo.owner(j), table[j]]) for j in js]

    def audit_decode(self, slot: int, table, length: int):
        """Exact-attention audit probe, sequence-sharded form (obs.audit).

        Each shard gathers its FULL local resident slice of the slot and
        the per-page masses come back globally normalized (pmax/psum in
        ``page_attention_mass``), so summing any shard subset is exact.
        None at a page boundary — the sampler retries a later tick.
        """
        n = self.topo.n_shards
        page = self.pcfg.page_size
        idx = length // page
        if idx >= len(table) or table[idx] < 0:
            return None
        by_shard = [[j for j, pid in enumerate(table)
                     if pid >= 0 and self.topo.owner(j) == s]
                    for s in range(n)]
        n_res = sum(len(b) for b in by_shard)
        b = self.pcfg.max_batch
        w = bucketing.bucket_count(max(1, max(len(x) for x in by_shard)),
                                   pow2=self.pcfg.bucket_pow2)
        phys = np.full((n, b, w), -1, np.int32)
        logical = np.full((n, b, w), -1, np.int32)
        write_page = np.full((n, b), SCRATCH, np.int32)
        write_off = np.zeros((n, b), np.int32)
        for s in range(n):
            for i, j in enumerate(by_shard[s]):
                phys[s, slot, i] = table[j]
                logical[s, slot, i] = j
        owner = self.topo.owner(idx)
        write_page[owner, slot] = table[idx]
        write_off[owner, slot] = length % page
        ps = {"phys": jnp.asarray(phys), "logical": jnp.asarray(logical),
              "write_page": jnp.asarray(write_page),
              "write_off": jnp.asarray(write_off),
              "audit": jnp.zeros((n,), jnp.int32)}
        lengths_vec = np.zeros((b,), np.int32)
        lengths_vec[slot] = length
        cache = {"layers": self.cache["layers"],
                 "lengths": jnp.asarray(lengths_vec)}
        out = np.asarray(self._audit(self.params, self.last_token, cache,
                                     ps))     # [n, blocks, R, B, W]
        n_layers = out.shape[1] * out.shape[2]
        mass_by_shard = [
            out[s].reshape(n_layers, b, w)[:, slot, :len(by_shard[s])]
            for s in range(n)]                # each [n_layers, n_res_s]

        # the hot selection the NEXT decode step would make, per shard
        scores = self._pull_scores()
        hot_js: set[int] = set()
        per_shard = []
        for s in range(n):
            if self.sparse_decode:
                _, lg = self.pools.select_hot_sphere(
                    table, s, self.hot_width, scores,
                    radius=self.hot_radius)
            else:
                _, lg = self.pools.select_hot(table, s, self.hot_width,
                                              scores)
            shard_hot = {int(j) for j in lg if j >= 0}
            hot_js |= shard_hot
            mass_s = float(mass_by_shard[s].sum()) / max(n_layers, 1)
            per_shard.append({
                "shard": s, "pages_resident": len(by_shard[s]),
                "pages_hot": len(shard_hot),
                "mass_share": mass_s,
                "skipped": len(shard_hot) == 0})

        mass = np.concatenate(mass_by_shard, axis=1)  # [n_layers, n_res]
        hot_mask = np.array([j in hot_js
                             for s in range(n) for j in by_shard[s]], bool)
        try:
            sl = np.asarray(self._scores_by_layer(self.cache["layers"]))
            scores_layers = np.concatenate(
                [sl[s][:, [table[j] for j in by_shard[s]]]
                 for s in range(n)], axis=1).tolist()
        except ValueError:
            scores_layers = None
        tot = np.maximum(mass.sum(axis=1), 1e-30)
        recall = mass[:, hot_mask].sum(axis=1) / tot
        return {"slot": slot, "length": length,
                "pages_resident": n_res,
                "pages_hot": len(hot_js),
                "hot_mask": hot_mask.tolist(),
                "mass_per_layer": mass.tolist(),
                "recall_per_layer": recall.tolist(),
                "scores_per_layer": scores_layers,
                "per_shard": per_shard}

    def stats(self) -> dict:
        pools = self.pools.stats()
        per_page = metrics.bytes_per_page(
            jax.tree.map(lambda leaf: leaf[0], self.cache["layers"]))
        out = {
            "pools": pools,
            "n_shards": self.topo.n_shards,
            "bytes_per_page": per_page,
            "working_set_bytes": pools["peak_live"] * per_page,
            "slab_bytes": metrics.tree_bytes(self.cache["layers"]),
            "decode_compiles": self._decode._cache_size(),
            "prefill_batch_compiles": self._prefill_chunk_batch._cache_size(),
            "hot_width": self.hot_width,
        }
        if self.kv_quant:
            base, tier = quant.split_quant(
                jax.tree.map(lambda leaf: leaf[0], self.cache["layers"]))
            fp_pp = metrics.bytes_per_page(base)
            q_pp = metrics.bytes_per_page(tier)
            q_live = live = 0
            for s in range(self.topo.n_shards):
                pool = self.pools.pools[s]
                for pid in range(1, pool.n_pages):
                    if pool.ref(pid) > 0:
                        live += 1
                        q_live += int(pool.quant.is_quant(pid))
            frac = q_live / max(live, 1)
            blended = max((1 - frac) * fp_pp + frac * q_pp, 1.0)
            out["kv_quant"] = {
                "pages_quantized_live": q_live,
                "quantize_events": sum(
                    p.quant.stats().quantize_events
                    for p in self.pools.pools),
                "bytes_per_page_fp": fp_pp,
                "bytes_per_page_int8": q_pp,
                "effective_capacity_pages": int(
                    pools["capacity"] * fp_pp / blended),
            }
        return out


class SpatialServingEngine(EngineCore):
    """The sequence-sharded serving engine: ``SpatialBackend`` under the
    shared ``EngineCore`` executor. Thin by design — every scheduler-
    visible behavior (including lazy cold-page shedding) lives in
    engine_core.py and is identical to the paged engine's."""

    def __init__(self, model_cfg, params, scfg_engine: SpatialEngineCfg,
                 scfg: Optional[SchedulerCfg] = None,
                 rng: Optional[jax.Array] = None):
        scfg = scfg or SchedulerCfg()
        super().__init__(SpatialBackend(model_cfg, params, scfg_engine,
                                        scfg), scfg, rng)

    @property
    def pcfg(self) -> SpatialEngineCfg:
        return self.backend.pcfg

    @property
    def pools(self) -> ShardedPagePools:
        return self.backend.pools

    @property
    def topo(self) -> ShardTopology:
        return self.backend.topo

    @property
    def mesh(self):
        return self.backend.mesh

    @property
    def last_token(self):
        return self.backend.last_token

    @property
    def cache(self):
        return self.backend.cache
