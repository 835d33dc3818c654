"""DRAttention — Distributed Ring-flow Attention (paper §V-B1).

Q and KV are both partitioned along the sequence dim across compute units;
the *query* sub-blocks rotate around a logical ring (Q is d_h wide vs KV's
2·d_h — half the traffic of RingAttention-KV), carrying their partial
softmax state (m_i, l_i, o_i) which is merged at every hop. After N steps
every Q sub-block has visited every KV shard and holds the exact global
softmax result.

TPU mapping (DESIGN.md §2c): the ring is ``jax.lax.ppermute`` over a
sequence-parallel mesh axis inside ``shard_map``; the ICI torus provides the
wrap-around physically, so MRCA (core/mrca.py) is only needed on the
simulated NoC mesh.

Also provides ``distributed_decode_merge`` — the degenerate single-query
form (flash-decoding style (m,l,o) tree-merge) used by the seq-sharded
decode path.
"""

from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.sads import NEG_INF
from repro.core.star_attention import STARConfig, star_attention


def _local_attn_stats(q, k, v, *, scale, mask):
    """Unnormalized local attention: returns (m [T], l [T], o [T,d])."""
    sc = jnp.einsum("td,sd->ts", q, k).astype(jnp.float32) * scale
    sc = jnp.where(mask, sc, NEG_INF)
    m = sc.max(axis=-1)
    p = jnp.exp(sc - m[:, None])
    p = jnp.where(sc <= NEG_INF / 2, 0.0, p)
    l = p.sum(axis=-1)
    o = p @ v.astype(jnp.float32)
    return m, l, o


def _merge_stats(m_a, l_a, o_a, m_b, l_b, o_b):
    """Combine two partial softmax states (the paper's m_i/l_i update)."""
    m = jnp.maximum(m_a, m_b)
    ea = jnp.exp(m_a - m)
    eb = jnp.exp(m_b - m)
    # empty partitions (m == NEG_INF) contribute nothing
    ea = jnp.where(m_a <= NEG_INF / 2, 0.0, ea)
    eb = jnp.where(m_b <= NEG_INF / 2, 0.0, eb)
    l = l_a * ea + l_b * eb
    o = o_a * ea[:, None] + o_b * eb[:, None]
    return m, l, o


def dr_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                 mesh, axis: str, causal: bool = True,
                 scale: Optional[float] = None,
                 star: Optional[STARConfig] = None) -> jax.Array:
    """Ring-flow attention over a sequence-sharded mesh axis.

    q/k/v: [S, d] GLOBAL arrays, sharded along S over ``axis`` (call under
    jit; vmap over batch/heads outside). Returns [S, d] sharded the same.
    """
    from jax.sharding import PartitionSpec as P

    n = mesh.shape[axis]
    s = q.shape[0]
    d = q.shape[-1]
    scale = scale or (1.0 / math.sqrt(d))
    chunk = s // n

    def local_fn(q_loc, k_loc, v_loc):
        me = jax.lax.axis_index(axis)
        # Global positions of the resident KV shard and the visiting Q chunk.
        kv_pos = me * chunk + jnp.arange(chunk)

        def hop(carry, t):
            qc, m, l, o, owner = carry
            # attention of the visiting Q chunk vs the LOCAL KV shard
            q_pos = owner * chunk + jnp.arange(chunk)
            mask = (kv_pos[None, :] <= q_pos[:, None]) if causal else \
                jnp.ones((chunk, chunk), bool)
            mh, lh, oh = _local_attn_stats(qc, k_loc, v_loc, scale=scale,
                                           mask=mask)
            m, l, o = _merge_stats(m, l, o, mh, lh, oh)
            # rotate Q (+ its stats) to the next unit; KV stays resident
            perm = [(i, (i + 1) % n) for i in range(n)]
            qc, m, l, o, owner = jax.lax.ppermute(
                (qc, m, l, o, owner), axis, perm)
            return (qc, m, l, o, owner), None

        vary = lambda x: jax.lax.pcast(x, (axis,), to="varying")
        init = (q_loc,
                vary(jnp.full((chunk,), NEG_INF, jnp.float32)),
                vary(jnp.zeros((chunk,), jnp.float32)),
                vary(jnp.zeros((chunk, d), jnp.float32)),
                me)
        (qc, m, l, o, owner), _ = jax.lax.scan(hop, init, jnp.arange(n))
        # after n hops each chunk is home again with global (m, l, o)
        out = o / jnp.maximum(l, 1e-30)[:, None]
        return out.astype(q_loc.dtype)

    fn = jax.shard_map(local_fn, mesh=mesh,
                       in_specs=(P(axis), P(axis), P(axis)),
                       out_specs=P(axis))
    return fn(q, k, v)


def distributed_decode_merge(q: jax.Array, k: jax.Array, v: jax.Array, *,
                             mesh, axis: str, length,
                             scale: Optional[float] = None) -> jax.Array:
    """Seq-sharded single-query decode: local partial (m,l,o) + global merge.

    q [d] replicated; k/v [S, d] sharded over ``axis``; ``length`` = valid
    prefix. The merge is DRAttention's (m_i, l_i) combination executed as a
    psum-tree instead of a ring — optimal when T=1.
    """
    from jax.sharding import PartitionSpec as P

    n = mesh.shape[axis]
    s = k.shape[0]
    d = k.shape[-1]
    scale = scale or (1.0 / math.sqrt(d))
    chunk = s // n

    def local_fn(q_r, k_loc, v_loc):
        me = jax.lax.axis_index(axis)
        pos = me * chunk + jnp.arange(chunk)
        mask = (pos < length)[None, :]
        m, l, o = _local_attn_stats(q_r[None, :], k_loc, v_loc, scale=scale,
                                    mask=mask)
        # global max, then rescale local sums — one all-reduce each
        m_g = jax.lax.pmax(m, axis)
        w = jnp.where(m <= NEG_INF / 2, 0.0, jnp.exp(m - m_g))
        l_g = jax.lax.psum(l * w, axis)
        o_g = jax.lax.psum(o * w[:, None], axis)
        out = o_g[0] / jnp.maximum(l_g[0], 1e-30)
        return out.astype(k_loc.dtype)

    fn = jax.shard_map(local_fn, mesh=mesh,
                       in_specs=(P(), P(axis), P(axis)),
                       out_specs=P())
    return fn(q, k, v)
