"""Paged KV-cache decode attention — Pallas TPU kernel.

One decode query (R grouped heads per KV head, all G KV heads at once)
attends to a sequence whose KV rows live in non-contiguous pool pages.
The block table is a scalar-prefetch operand: the kernel's BlockSpec index
maps read the physical page id for grid step (b, w) *before* the body
runs, so each page is DMA'd straight from its pool slab into VMEM — the
gather never materializes a contiguous copy of the sequence in HBM.

Grid (batch, hot_page); the page dim is innermost (sequential on TPU), so
the online-softmax state (m, l, acc) lives in VMEM scratch across page
steps and the normalized output is written once, at the last page. The
kernel reads the pool slab in its native [P, page, G, d] layout, one page
of all G heads per step. Every block's last two dims are full array dims
((R, d) or (G, d)), so the kernel satisfies the TPU (8, 128) tiling rule
at any head grouping.

Validated in interpret mode against the jnp gather reference
(repro.kvcache.paged_attention.paged_gather_decode); on a TPU the same
code lowers to Mosaic (tests/test_tpu_compile.py compiles it for v5e).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret

NEG_INF = -1e30


def _paged_kernel(phys_ref, logical_ref, kvlen_ref, q_ref, k_ref, v_ref,
                  o_ref, m_sc, l_sc, acc_sc, *, scale: float, page: int):
    b = pl.program_id(0)
    w = pl.program_id(1)

    @pl.when(w == 0)
    def _init():
        m_sc[...] = jnp.full_like(m_sc, NEG_INF)
        l_sc[...] = jnp.zeros_like(l_sc)
        acc_sc[...] = jnp.zeros_like(acc_sc)

    q = q_ref[0].astype(jnp.float32)                 # [G, R, d]
    k = k_ref[0].astype(jnp.float32)                 # [page, G, d]
    v = v_ref[0].astype(jnp.float32)
    s = jnp.einsum("grd,pgd->grp", q, k,
                   preferred_element_type=jnp.float32) * scale

    lg = logical_ref[b, w]                           # logical page index
    row_pos = lg * page + jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
    valid = (lg >= 0) & (row_pos < kvlen_ref[b])
    s = jnp.where(valid, s, NEG_INF)

    m_prev = m_sc[...]                               # [G, R, 1]
    m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
    alpha = jnp.where(m_prev <= NEG_INF / 2, 0.0, jnp.exp(m_prev - m_new))
    p = jnp.where(s <= NEG_INF / 2, 0.0, jnp.exp(s - m_new))
    m_sc[...] = m_new
    l_sc[...] = l_sc[...] * alpha + p.sum(axis=-1, keepdims=True)
    acc_sc[...] = acc_sc[...] * alpha + jnp.einsum(
        "grp,pgd->grd", p, v, preferred_element_type=jnp.float32)

    @pl.when(w == pl.num_programs(1) - 1)
    def _finish():
        out = acc_sc[...] / jnp.maximum(l_sc[...], 1e-30)
        o_ref[0] = out.astype(o_ref.dtype)


def paged_decode_attention(q: jax.Array, k_pages: jax.Array,
                           v_pages: jax.Array, phys: jax.Array,
                           logical: jax.Array, kv_len: jax.Array, *,
                           scale: float,
                           interpret: Optional[bool] = None) -> jax.Array:
    """q [B,G,R,d]; k/v pages [P,page,G,d]; phys/logical [B,W]; kv_len [B].

    Returns [B, G, R, d] in q.dtype (fp32 accumulate). ``phys`` must be
    pre-clipped to valid page ids; rows are masked via ``logical``
    (-1 = padded slot) and ``kv_len``. ``interpret`` None resolves by
    ``repro.kernels.resolve_interpret`` (compiled on TPU only).
    """
    bsz, g, r, d = q.shape
    page = k_pages.shape[1]
    w = phys.shape[1]

    kernel = functools.partial(_paged_kernel, scale=scale, page=page)
    kv_spec = pl.BlockSpec((1, page, g, d),
                           lambda b, w, phys, lg, kl: (phys[b, w], 0, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(bsz, w),
        in_specs=[
            pl.BlockSpec((1, g, r, d),
                         lambda b, w, phys, lg, kl: (b, 0, 0, 0)),
            kv_spec,
            kv_spec,
        ],
        out_specs=pl.BlockSpec((1, g, r, d),
                               lambda b, w, phys, lg, kl: (b, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((g, r, 1), jnp.float32),      # running max m
            pltpu.VMEM((g, r, 1), jnp.float32),      # running sum l
            pltpu.VMEM((g, r, d), jnp.float32),      # unnormalized o
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((bsz, g, r, d), q.dtype),
        interpret=resolve_interpret(interpret),
    )(phys, logical, kv_len, q, k_pages, v_pages)
