"""FlashAttention-2 Pallas TPU kernel — the paper's formal-compute baseline.

Grid (batch*heads, q_tiles, kv_tiles); the kv dim is the innermost
(sequential on TPU), so the (m, l, o) accumulators live in VMEM scratch
across kv steps and the normalized tile is written at the last kv step —
the standard TPU flash pattern. Block shapes are explicit BlockSpecs sized
for VMEM (q/k/v tiles of [block x head_dim], fp32 accumulator
[block_q x head_dim]).

This kernel intentionally keeps FA-2's per-tile max refresh + rescale — the
overhead SU-FA (kernels/sufa.py) removes. Validated in interpret mode vs
ref.flash_ref; on a TPU the same code lowers to Mosaic.
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


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_sc, l_sc, acc_sc, *,
                  scale: float, causal: bool, block_q: int, block_kv: int,
                  q_offset: int = 0):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_sc[...] = jnp.zeros_like(acc_sc)
        m_sc[...] = jnp.full_like(m_sc, NEG_INF)
        l_sc[...] = jnp.zeros_like(l_sc)

    q = q_ref[0].astype(jnp.float32)                 # [Bq, d]
    k = k_ref[0].astype(jnp.float32)                 # [Bc, d]
    v = v_ref[0].astype(jnp.float32)
    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale

    if causal:
        q_pos = q_offset + qi * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_kv), 0)
        kv_pos = ki * block_kv + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_kv), 1)
        s = jnp.where(kv_pos <= q_pos, s, NEG_INF)

    m_prev = m_sc[...]                               # [Bq, 1]
    l_prev = l_sc[...]
    # FA-2 line 5-8: per-tile max refresh + accumulator rescale.
    m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    alpha = jnp.where(m_prev <= NEG_INF / 2, 0.0, alpha)
    p = jnp.exp(s - m_new)
    p = jnp.where(s <= NEG_INF / 2, 0.0, p)
    m_sc[...] = m_new
    l_sc[...] = l_prev * alpha + p.sum(axis=-1, keepdims=True)
    acc_sc[...] = acc_sc[...] * alpha + jnp.dot(
        p, v, preferred_element_type=jnp.float32)

    @pl.when(ki == pl.num_programs(2) - 1)
    def _finish():
        o_ref[0] = (acc_sc[...] / jnp.maximum(l_sc[...], 1e-30)
                    ).astype(o_ref.dtype)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, scale: float | None = None,
                    block_q: int = 128, block_kv: int = 128,
                    interpret: Optional[bool] = None):
    """q [BH, T, d], k/v [BH, S, d] -> [BH, T, d] (fp32 accumulate).
    ``interpret`` None resolves by ``repro.kernels.resolve_interpret``."""
    bh, t, d = q.shape
    s = k.shape[1]
    scale = scale or (1.0 / math.sqrt(d))
    block_q = min(block_q, t)
    block_kv = min(block_kv, s)
    grid = (bh, t // block_q, s // block_kv)

    kernel = functools.partial(_flash_kernel, scale=scale, causal=causal,
                               block_q=block_q, block_kv=block_kv,
                               q_offset=s - t)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_kv, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_kv, d), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, t, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),   # running max m
            pltpu.VMEM((block_q, 1), jnp.float32),   # running sum l
            pltpu.VMEM((block_q, d), jnp.float32),   # unnormalized o
        ],
        interpret=resolve_interpret(interpret),
    )(q, k, v)
