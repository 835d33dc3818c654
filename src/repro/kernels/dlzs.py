"""DLZS block-max Pallas TPU kernel — fused predict + tile-reduce.

Stage-1/stage-2 fusion of the cross-stage pipeline: estimates attention
scores with the one-sided pow2-quantized K (DLZS) and reduces each
(q_tile x kv_tile) to its predicted MAX — all in VMEM. The [T, S] estimated
score matrix never reaches HBM; only the tiny [n_qt, n_kt] block-max matrix
does, which SADS then top-k's. This is the paper's "Â stays on chip" claim
realized on TPU.

pow2 quantization is done bitwise (mask off the mantissa of the f32
representation: sign·2^e with mantissa -> 1.0 exactly), which is both
faithful to the LZ shift semantics and a single VPU op per element.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import resolve_interpret

NEG_INF = -1e30
_LANES = 128


def _pow2_bitwise(x: jax.Array) -> jax.Array:
    """sign(x)·2^floor(log2|x|) by zeroing the f32 mantissa bits."""
    bits = jax.lax.bitcast_convert_type(x.astype(jnp.float32), jnp.uint32)
    masked = jnp.bitwise_and(bits, jnp.uint32(0xFF800000))
    return jax.lax.bitcast_convert_type(masked, jnp.float32)


def sphere_keep(scores, radius: float):
    """SADS sphere rule over per-page DLZS scores.

    Keeps every page whose predicted max is within ``radius`` of the best
    page: ``scores >= max(scores) - radius``. Works on numpy or jax
    arrays; returns a boolean mask of the same shape. This is the paper's
    score-sphere criterion — decode-time selectors bound the resulting
    set to a fixed hot width, but the sphere is the admission test.
    """
    import numpy as _np
    xp = jnp if isinstance(scores, jax.Array) else _np
    s = xp.asarray(scores)
    return s >= (s.max() - radius)


def _dlzs_kernel(q_ref, k_ref, bmax_ref, *, scale: float, causal: bool,
                 block_q: int, block_kv: int, q_offset: int = 0):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    q = q_ref[0].astype(jnp.float32)                 # [Bq, d] — exact side
    k = _pow2_bitwise(k_ref[0])                      # [Bc, d] — LZ side
    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
    if causal:
        q_pos = q_offset + qi * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_kv), 0)
        kv_pos = ki * block_kv + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_kv), 1)
        s = jnp.where(kv_pos <= q_pos, s, NEG_INF)
    # one predicted max per (q_tile, kv_tile), broadcast over a lane
    # row so the output block keeps a full (1, 128) minor tile
    bmax_ref[...] = jnp.full(bmax_ref.shape, s.max(), jnp.float32)


def dlzs_block_scores(q: jax.Array, k: jax.Array, *, causal: bool = True,
                      scale: float | None = None, block_q: int = 128,
                      block_kv: int = 128,
                      interpret: Optional[bool] = None):
    """q [BH, T, d], k [BH, S, d] -> predicted block maxima [BH, n_qt, n_kt].
    ``interpret`` None resolves by ``repro.kernels.resolve_interpret``.
    """
    bh, t, d = q.shape
    s = k.shape[1]
    scale = scale or (1.0 / math.sqrt(d))
    block_q = min(block_q, t)
    block_kv = min(block_kv, s)
    n_qt, n_kt = t // block_q, s // block_kv

    kernel = functools.partial(_dlzs_kernel, scale=scale, causal=causal,
                               block_q=block_q, block_kv=block_kv,
                               q_offset=s - t)
    bmax = pl.pallas_call(
        kernel,
        grid=(bh, n_qt, n_kt),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_kv, d), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, 1, 1, _LANES),
                               lambda b, i, j: (b, i, j, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, n_qt, n_kt, 1, _LANES),
                                       jnp.float32),
        interpret=resolve_interpret(interpret),
    )(q, k)
    return bmax[..., 0, 0]
