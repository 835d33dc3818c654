"""SU-FA Pallas TPU kernel — sorted-updating block-sparse flash attention.

The cross-stage contract: SADS hands this kernel, per query tile, the list
of selected KV tiles in DESCENDING predicted-max order (+ validity and
in-tile masks). The kernel streams ONLY those tiles; with ``strict=False``
(the paper's descend-updating fast path) the running max is frozen after the
first — highest — tile, eliminating FA-2's per-tile max refresh and the
o/l rescale multiplies (Fig. 11b).

KV tiles are pre-gathered by XLA into [BH, n_qt, keep, Bc, d] so the
BlockSpec index maps stay static (the selection indices were consumed by the
gather). The grid is (BH, n_qt, keep) with the keep dim innermost; (m, l, o)
accumulate in VMEM scratch exactly like kernels/flash.py.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret

NEG_INF = -1e30


def _sufa_kernel(q_ref, kg_ref, vg_ref, mask_ref, o_ref, m_sc, l_sc,
                 acc_sc, *, scale: float, strict: bool):
    ji = pl.program_id(2)

    @pl.when(ji == 0)
    def _init():
        acc_sc[...] = jnp.zeros_like(acc_sc)
        m_sc[...] = jnp.full_like(m_sc, NEG_INF)
        l_sc[...] = jnp.zeros_like(l_sc)

    q = q_ref[0, 0].astype(jnp.float32)              # [Bq, d]
    k = kg_ref[0, 0, 0].astype(jnp.float32)          # [Bc, d]
    v = vg_ref[0, 0, 0].astype(jnp.float32)
    mask = mask_ref[0, 0, 0] != 0                    # [Bq, Bc]

    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
    s = jnp.where(mask, s, NEG_INF)
    tile_max = s.max(axis=-1, keepdims=True)         # [Bq, 1]
    m_prev = m_sc[...]
    l_prev = l_sc[...]

    if strict:
        # exact online softmax (rescale like FA-2; order-independent)
        m_new = jnp.maximum(m_prev, tile_max)
        alpha = jnp.exp(m_prev - m_new)
        alpha = jnp.where(m_prev <= NEG_INF / 2, 0.0, alpha)
    else:
        # descend updating: tiles arrive max-first, so the max set by tile 0
        # is final — no comparison against m_prev, no rescale multiply.
        first = m_prev <= NEG_INF / 2
        m_new = jnp.where(first, tile_max, m_prev)
        alpha = jnp.ones_like(m_prev)

    p = jnp.exp(s - m_new)
    p = jnp.where(s <= NEG_INF / 2, 0.0, p)
    m_sc[...] = m_new
    l_sc[...] = l_prev * alpha + p.sum(axis=-1, keepdims=True)
    acc_sc[...] = acc_sc[...] * alpha + jnp.dot(
        p, v, preferred_element_type=jnp.float32)

    @pl.when(ji == pl.num_programs(2) - 1)
    def _finish():
        o_ref[0, 0] = (acc_sc[...] / jnp.maximum(l_sc[...], 1e-30)
                       ).astype(o_ref.dtype)


def sufa_attention(q: jax.Array, kg: jax.Array, vg: jax.Array,
                   mask: jax.Array, *, scale: float | None = None,
                   strict: bool = False,
                   interpret: Optional[bool] = None):
    """q [BH, T, d]; kg/vg [BH, n_qt, keep, Bc, d] (gathered, desc order);
    mask [BH, n_qt, keep, Bq, Bc] (validity x causal x sphere) -> [BH, T, d].
    ``interpret`` None resolves by ``repro.kernels.resolve_interpret``.
    """
    bh, t, d = q.shape
    _, n_qt, keep, block_kv, _ = kg.shape
    block_q = t // n_qt
    scale = scale or (1.0 / math.sqrt(d))

    kernel = functools.partial(_sufa_kernel, scale=scale, strict=strict)
    grid = (bh, n_qt, keep)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d), lambda b, i, j: (b, i, 0, 0)),
            pl.BlockSpec((1, 1, 1, block_kv, d),
                         lambda b, i, j: (b, i, j, 0, 0)),
            pl.BlockSpec((1, 1, 1, block_kv, d),
                         lambda b, i, j: (b, i, j, 0, 0)),
            pl.BlockSpec((1, 1, 1, block_q, block_kv),
                         lambda b, i, j: (b, i, j, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, d),
                               lambda b, i, j: (b, i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, n_qt, block_q, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),   # running max m
            pltpu.VMEM((block_q, 1), jnp.float32),   # running sum l
            pltpu.VMEM((block_q, d), jnp.float32),   # unnormalized o
        ],
        interpret=resolve_interpret(interpret),
    )(q.reshape(bh, n_qt, block_q, d), kg, vg,
      mask.astype(jnp.int8))
    return out.reshape(bh, t, d)
