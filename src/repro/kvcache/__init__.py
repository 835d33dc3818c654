"""Paged KV-cache subsystem: DLZS-guided page retention for serving.

Design note
===========

The dense slot engine reserves one ``[max_batch, max_len]`` KV slab — worst
case memory for every request, a hard engine-wide length cap, and zero reuse
between requests. This package replaces that slab with a global pool of
fixed-size *pages* plus per-sequence block tables, and lets the paper's
DLZS prediction stage (§IV-A) decide which pages stay hot:

* ``pool``       — host-side page pool: ref-counted pages, a token-prefix
                   index for copy-on-write prefix sharing (identical prompt
                   prefixes are stored once), a cached tier of ref-0 pages
                   retained for future reuse, and the host-side ``SwapArea``
                   where preempted sequences park page contents under pool
                   pressure (serving/scheduler decides who; resume is a
                   page-in).
* ``allocator``  — policy layer: admission (share-then-allocate, whole
                   prompts via ``admit`` or one prefill chunk at a time via
                   ``admit_chunk``), eviction (cached pages die
                   lowest-DLZS-score-first) and hot-page retention
                   (``select_hot``) for sparse decode.
* ``paged_attention`` — gather-based decode over block tables, as an XLA
                   ``jnp.take`` fallback and a Pallas kernel that DMAs
                   blocks of each lane's valid pages (kernels/paged.py);
                   backend auto-dispatch picks pallas on
                   TPU, xla elsewhere (``REPRO_PAGED_BACKEND`` overrides).
* ``bucketing``  — page buckets, the page-aligned chunk math
                   (``chunk_spans``) and the per-tick packing
                   (``pack_budget``) behind the one-compile chunked
                   prefill.
* ``metrics``    — device-side page scoring + cache-bytes accounting.
* ``quant``      — int8 cold-page KV tier: per-page-scaled quantized
                   mirrors of the pool slabs; pages leaving the DLZS hot
                   set quantize, the decode gather dequantizes
                   (``SchedulerCfg.kv_quant``). Host flag bookkeeping is
                   ``pool.QuantTracker``.
* ``wire``       — the flat-payload swap format pinned down as a wire
                   contract: schema validation + byte accounting for
                   cross-instance KV transfer (serving/disagg).

Page size choice
----------------

Pages are rows of ``[page_size, n_kv, head_dim]`` per layer. ``page_size``
should (a) divide the STAR prefill tile ``block_kv`` or vice versa so bucket
padding stays tile-aligned, and (b) be small enough that the partial tail
page wastes little (expected waste = page_size/2 rows/seq) but large enough
that block tables and gathers stay cheap. The serving default is 16 rows —
at olmo-1b scale (16 layers x 16 KV heads x 128 dims, bf16+int8-LZ) one page
is ~2.6 MB across the stack, i.e. sub-percent waste per sequence while a
4096-token context still fits a 256-entry block table.

DLZS score -> retention mapping
-------------------------------

``metrics.page_scores`` reduces the int8 LZ-code slab (the *same* compressed
prediction operand ``star_decode`` streams) to ``max |code|`` per page:
``|code| = |floor(log2 |k|)| + 64``, so the score is a query-agnostic upper
bound on the log-magnitude any key in the page contributes to a DLZS score
estimate Q·K̂. ``allocator.select_hot`` always keeps the newest
``recent_pages`` pages (the local window plus the page being written) and
fills the remaining ``hot_pages - recent`` gather slots with the
highest-scored cold pages; eviction under admission pressure reclaims
cached prefix pages lowest-score-first. Cross-stage tiling, cache edition:
prediction metadata produced for the compute stage doubles as the memory
manager's utility signal.

Decode-time sparsity (``SchedulerCfg.decode_hot_width``) swaps the
retention selector for ``allocator.select_hot_sphere``: the SADS sphere
rule (``kernels.dlzs.sphere_keep``, keep pages within ``radius`` of the
best predicted max) under a hard width cap, with the newest page and the
position-0 sink always hot. The selection is deterministic, monotone in
width, and fixed-shape — the properties ``tests/test_decode_sparse.py``
pins down. SHED-parked entries (negative block-table sentinel) are never
selected by either selector.
"""

from repro.kvcache.allocator import PagedAllocator, select_hot_sphere
from repro.kvcache.pool import (SCRATCH, PagePool, PoolExhausted, PoolStats,
                                QuantStats, QuantTracker, SwapArea,
                                SwapStats)
from repro.kvcache.wire import payload_bytes, validate_payload

__all__ = ["PagePool", "PagedAllocator", "PoolExhausted", "PoolStats",
           "QuantStats", "QuantTracker", "SCRATCH", "SwapArea", "SwapStats",
           "payload_bytes", "select_hot_sphere", "validate_payload"]
