"""Paged KV-cache decode attention — Pallas TPU kernel.

One decode query per lane (R grouped heads per KV head, all G KV heads at
once) attends to the lane's hot pages, which sit anywhere in the pool.

Contract on the block table: each lane's valid slots form a prefix of its
row, ``logical[b, :n_valid[b]] >= 0`` and ``-1`` after it, in ascending
position order. Both decode selections (``kvcache.allocator.select_hot``
and ``select_hot_sphere``) build rows this way. Inside a valid page, rows
are masked by position (``logical * page + row < kv_len``), so the lane's
partial newest page is exact.

Grid (B,): one step per lane. The step runs a loop over
``ceil(n_valid[b] / ppb)`` blocks of ``ppb`` pages; the blocks past the
lane's last valid page are never fetched or computed, and a lane with no
valid page writes 0. The K and V pools stay in HBM (``pl.ANY``). Each
block issues ``ppb`` page DMAs per pool, straight from the pool slab in
its native [P, page, G, d] layout, into one slot of a double-buffered
[2, ppb, page, G, d] VMEM buffer; block i+1's copies start before block
i is waited on. In a partial last block the slots past the last valid
page re-read that page and are masked out. The block table, ``kv_len``
and ``n_valid`` are scalar-prefetch operands, read from SMEM.

Arithmetic: K and V are read as stored; scores, the running max and sum,
and ``p @ V`` accumulate in float32 with ``p`` kept in float32.

``ppb`` is a pure function of the K pool's shape and dtype and of W
(``pages_per_block``): as many pages as fit ``VMEM_BUDGET`` once the
(G, d) tiles are padded to the dtype's sublane multiple and the float32
temporaries are counted, capped at W. The engine counts the kernel's
blocks with the same function (serving.paged).

Validated in interpret mode against the jnp gather reference
(repro.kvcache.paged_attention.paged_gather_decode); on a TPU the same
code lowers to Mosaic (tests/test_tpu_compile.py compiles it for v5e at
the benchmark cells' shapes).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret

NEG_INF = -1e30

# VMEM a block's pages may take, by ``block_vmem_bytes``: half of Mosaic's
# default 16-MiB scoped limit on v5e, which gives 8 pages at both benchmark
# cells' shapes. Kernel alone on a v5e, 8-page blocks were 2.9% faster than
# 16-page ones at the MHA shape and 0.65% slower at the GQA one; 4, 32 and
# 64 pages were slower at both (PERF.md).
VMEM_BUDGET = 8 * 2 ** 20


def _tile_bytes(rows: int, cols: int, dtype) -> int:
    """Bytes of a [rows, cols] slice once padded to the VMEM tile: rows to
    the dtype's sublane multiple (8 for 32-bit, 16 for 16-bit), cols to
    128 lanes."""
    itemsize = np.dtype(dtype).itemsize
    sub = 8 * max(1, 4 // itemsize)
    return (-(-rows // sub) * sub) * (-(-cols // 128) * 128) * itemsize


def block_vmem_bytes(ppb: int, page: int, g: int, d: int, dtype) -> int:
    """VMEM a block of ``ppb`` pages takes, with every [G, d] row padded
    to its tile: eight page copies in ``dtype`` (the two slots of the K
    and V buffers, the loaded K and V, and the copies the two products
    are laid out from) and four in float32 (V, its laid-out copy and the
    products' temporaries). An upper bound of Mosaic's scoped allocation
    at the benchmark cells' shapes (ahead-of-time v5e compiles: 0.89 MiB
    a page for G 16, up to 0.72 MiB for G 2, against 1.0 and 0.75)."""
    return ppb * page * (8 * _tile_bytes(g, d, dtype)
                         + 4 * _tile_bytes(g, d, jnp.float32))


def pages_per_block(k_pages, w: int) -> int:
    """Pages per kernel block: the largest power of two, at most ``w``,
    whose block fits ``VMEM_BUDGET``. ``k_pages`` is the K pool, or any
    array of its trailing [page, G, d] shape and dtype (the engine passes
    its [L, P, page, G, d] slab): the block depends on nothing else."""
    page, g, d = k_pages.shape[-3:]
    ppb = 1
    while (ppb * 2 <= w and block_vmem_bytes(ppb * 2, page, g, d,
                                             k_pages.dtype) <= VMEM_BUDGET):
        ppb *= 2
    return ppb


def _paged_kernel(phys_ref, logical_ref, kvlen_ref, nvalid_ref, q_ref,
                  k_hbm, v_hbm, o_ref, k_buf, v_buf, sem, *,
                  scale: float, page: int, ppb: int):
    b = pl.program_id(0)
    n = nvalid_ref[b]
    kv_len = kvlen_ref[b]
    n_blk = (n + ppb - 1) // ppb

    def copies(blk, slot):
        """The block's page DMAs; slots past the last valid page re-read
        it (masked below), never page 0."""
        out = []
        for j in range(ppb):
            pid = phys_ref[b, jnp.minimum(blk * ppb + j, n - 1)]
            out.append(pltpu.make_async_copy(k_hbm.at[pid], k_buf.at[slot, j],
                                             sem.at[0, slot]))
            out.append(pltpu.make_async_copy(v_hbm.at[pid], v_buf.at[slot, j],
                                             sem.at[1, slot]))
        return out

    @pl.when(n_blk > 0)
    def _prefetch_first():
        for c in copies(0, 0):
            c.start()

    q = q_ref[0]                                      # [G, R, d]
    g, r, d = q.shape
    rows = ppb * page
    col = jax.lax.broadcasted_iota(jnp.int32, (1, 1, rows), 2)

    def block(blk, carry):
        m_prev, l_prev, acc = carry
        slot = blk % 2

        @pl.when(blk + 1 < n_blk)
        def _prefetch_next():
            for c in copies(blk + 1, 1 - slot):
                c.start()

        for c in copies(blk, slot):
            c.wait()
        k = k_buf[slot].reshape(rows, g, d)
        v = v_buf[slot].reshape(rows, g, d).astype(jnp.float32)
        s = jnp.einsum("grd,sgd->grs", q, k,
                       preferred_element_type=jnp.float32) * scale

        # position of every row; a slot past the last valid page reads as
        # kv_len, so all of its rows are masked
        pos = jnp.full(col.shape, kv_len, jnp.int32)
        for j in range(ppb):
            i = blk * ppb + j
            start = jnp.where(i < n,
                              logical_ref[b, jnp.minimum(i, n - 1)] * page,
                              kv_len)
            in_page = (col >= j * page) & (col < (j + 1) * page)
            pos = jnp.where(in_page, start - j * page + col, pos)
        s = jnp.where(pos < kv_len, s, NEG_INF)

        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        alpha = jnp.where(m_prev <= NEG_INF / 2, 0.0,
                          jnp.exp(m_prev - m_new))
        p = jnp.where(s <= NEG_INF / 2, 0.0, jnp.exp(s - m_new))
        l_new = l_prev * alpha + p.sum(axis=-1, keepdims=True)
        acc = acc * alpha + jnp.einsum("grs,sgd->grd", p, v,
                                       preferred_element_type=jnp.float32)
        return m_new, l_new, acc

    init = (jnp.full((g, r, 1), NEG_INF, jnp.float32),
            jnp.zeros((g, r, 1), jnp.float32),
            jnp.zeros((g, r, d), jnp.float32))
    _, l, acc = jax.lax.fori_loop(0, n_blk, block, init)
    o_ref[0] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


def paged_decode_attention(q: jax.Array, k_pages: jax.Array,
                           v_pages: jax.Array, phys: jax.Array,
                           logical: jax.Array, kv_len: jax.Array,
                           n_valid: jax.Array, *, scale: float,
                           interpret: Optional[bool] = None) -> jax.Array:
    """q [B,G,R,d]; k/v pages [P,page,G,d]; phys/logical [B,W]; kv_len and
    n_valid [B].

    Returns [B, G, R, d] in q.dtype (fp32 accumulate). ``n_valid[b]``
    counts lane b's valid slots, which must be the first ones of its row
    (module docstring); ``phys`` must hold valid page ids there. Rows are
    masked by ``logical`` and ``kv_len``. ``interpret`` None resolves by
    ``repro.kernels.resolve_interpret`` (compiled on TPU only).
    """
    bsz, g, r, d = q.shape
    page = k_pages.shape[1]
    w = phys.shape[1]
    ppb = pages_per_block(k_pages, w)

    kernel = functools.partial(_paged_kernel, scale=scale, page=page,
                               ppb=ppb)
    lane = pl.BlockSpec((1, g, r, d), lambda b, *_: (b, 0, 0, 0))
    pool = pl.BlockSpec(memory_space=pl.ANY)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(bsz,),
        in_specs=[lane, pool, pool],
        out_specs=lane,
        scratch_shapes=[
            pltpu.VMEM((2, ppb, page, g, d), k_pages.dtype),
            pltpu.VMEM((2, ppb, page, g, d), v_pages.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),          # [pool, slot]
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((bsz, g, r, d), q.dtype),
        interpret=resolve_interpret(interpret),
        name="paged_decode",
    )(phys, logical, kv_len, n_valid, q, k_pages, v_pages)
