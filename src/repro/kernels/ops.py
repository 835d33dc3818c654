"""jit'd public wrappers for the Pallas kernels + the fused STAR pipeline.

``star_attention_fused`` chains the three stages kernel-side:
  dlzs_block_scores (fused predict+tile-max, VMEM-resident Â)
  -> jax.lax.top_k over the block-max matrix (SADS tile selection, desc)
  -> XLA gather of the selected KV tiles
  -> sufa_attention (descend-updating block-sparse flash).
``interpret=None`` resolves by ``repro.kernels.resolve_interpret``: the
kernel bodies run in the interpreter off the TPU and lower to Mosaic on it.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from repro.kernels.dlzs import dlzs_block_scores
from repro.kernels.flash import flash_attention
from repro.kernels.sufa import sufa_attention

NEG_INF = -1e30


@functools.partial(jax.jit, static_argnames=("causal", "block_q", "block_kv",
                                             "interpret"))
def flash(q, k, v, *, causal=True, block_q=128, block_kv=128,
          interpret=None):
    return flash_attention(q, k, v, causal=causal, block_q=block_q,
                           block_kv=block_kv, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("strict", "interpret"))
def sufa(q, kg, vg, mask, *, strict=False, interpret=None):
    return sufa_attention(q, kg, vg, mask, strict=strict,
                          interpret=interpret)


@functools.partial(jax.jit, static_argnames=("causal", "block_q", "block_kv",
                                             "interpret"))
def dlzs_blockmax(q, k, *, causal=True, block_q=128, block_kv=128,
                  interpret=None):
    return dlzs_block_scores(q, k, causal=causal, block_q=block_q,
                             block_kv=block_kv, interpret=interpret)


@functools.partial(jax.jit, static_argnames=(
    "causal", "block_q", "block_kv", "keep", "strict", "interpret"))
def star_attention_fused(q, k, v, *, keep: int, causal=True, block_q=128,
                         block_kv=128, radius=5.0, strict=False,
                         interpret=None):
    """Full kernel-side STAR pipeline. q/k/v [BH, T|S, d] -> [BH, T, d]."""
    bh, t, d = q.shape
    s = k.shape[1]
    scale = 1.0 / math.sqrt(d)
    block_q = min(block_q, t)
    block_kv = min(block_kv, s)
    n_qt, n_kt = t // block_q, s // block_kv
    keep = min(keep, n_kt)

    # Stage 1+2a (kernel): predicted per-tile maxima, Â stays in VMEM.
    bmax = dlzs_block_scores(q, k, causal=causal, block_q=block_q,
                             block_kv=block_kv, interpret=interpret)
    # Stage 2b: SADS tile top-k (desc) + sphere pruning on the tiny matrix.
    vals, idx = jax.lax.top_k(bmax, keep)             # [BH, n_qt, keep]
    valid = (vals > NEG_INF / 2) & (vals >= vals[..., :1] - radius)

    # Gather the selected tiles (XLA dynamic-slice fan-in to the kernel).
    kt = k.reshape(bh, n_kt, block_kv, d)
    vt = v.reshape(bh, n_kt, block_kv, d)
    take = lambda tiles: jnp.take_along_axis(
        tiles[:, None], idx[..., None, None], axis=2)  # [BH,n_qt,keep,Bc,d]
    kg, vg = take(kt), take(vt)

    # in-tile causal mask for the selected tiles
    q_pos = (jnp.arange(t) + (s - t)).reshape(n_qt, block_q)
    kv_pos = idx[..., None] * block_kv + jnp.arange(block_kv)
    mask = jnp.broadcast_to(valid[..., None, None],
                            (bh, n_qt, keep, block_q, block_kv))
    if causal:
        causal_m = (kv_pos[:, :, :, None, :]
                    <= q_pos[None, :, None, :, None])
        mask = mask & causal_m

    # Stage 3 (kernel): descend-updating block-sparse flash.
    return sufa_attention(q, kg, vg, mask, scale=scale, strict=strict,
                          interpret=interpret)
