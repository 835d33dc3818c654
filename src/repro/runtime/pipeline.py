"""Pipeline parallelism: collective-permute GPipe over a mesh axis.

Stages live on consecutive ranks of ``axis``; microbatches stream through
with ``ppermute`` moving activations stage-to-stage. The classic GPipe
schedule runs S + M - 1 ticks for S stages x M microbatches (bubble
fraction (S-1)/(S+M-1)). The official 40-cell matrix maps the pod axis to
DP (shapes fit without PP), but this module + its multi-device test are the
PP substrate for configurations that need depth-wise sharding (e.g. pod as
a 2-stage pipeline for >700B-param models).

Semantics: ``params`` is a pytree stacked on a leading [n_stages] dim and
sharded over ``axis``; ``stage_fn(stage_params, x)`` maps activations
through one stage. x is [M, micro_batch, ...] (microbatch-major). Output
equals the sequential composition stage_{S-1}(...stage_0(x)) per microbatch.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def gpipe(stage_fn, params, x, *, mesh, axis: str):
    """Run x [M, b, ...] through the stacked stages. Returns [M, b, ...]."""
    s = mesh.shape[axis]
    m = x.shape[0]

    def local_fn(p_loc, x_loc):
        # p_loc: this stage's params (leading dim 1); x_loc: full microbatch
        # stream, present on every rank (replicated over `axis`).
        me = jax.lax.axis_index(axis)
        p_me = jax.tree.map(lambda t: t[0], p_loc)
        nticks = s + m - 1
        perm = [(i, (i + 1) % s) for i in range(s)]

        def tick(carry, t):
            buf, outs = carry               # buf: activation held here
            # stage 0 ingests microbatch t (if in range) — others use buf
            mb_idx = jnp.clip(t, 0, m - 1)
            incoming = jnp.where(t < m, 1.0, 0.0)
            x_in = jnp.where((me == 0) & (t < m),
                             x_loc[mb_idx], buf)
            y = stage_fn(p_me, x_in)
            # the LAST stage's result for microbatch (t - s + 1) is final
            out_idx = t - (s - 1)
            keep = (me == s - 1) & (out_idx >= 0) & (out_idx < m)
            outs = jnp.where(
                keep,
                jax.lax.dynamic_update_index_in_dim(
                    outs, y, jnp.clip(out_idx, 0, m - 1), 0),
                outs)
            # ship activations downstream (ring; rank 0's recv is ignored)
            buf = jax.lax.ppermute(y, axis, perm)
            del incoming
            return (buf, outs), None

        buf0 = jnp.zeros_like(x_loc[0])
        outs0 = jax.lax.pcast(jnp.zeros_like(x_loc), (axis,), to="varying")
        (_, outs), _ = jax.lax.scan(
            tick, (jax.lax.pcast(buf0, (axis,), to="varying"), outs0),
            jnp.arange(nticks))
        # only the last stage holds real outputs; broadcast them
        outs = jax.lax.psum(
            jnp.where(me == s - 1, outs, jnp.zeros_like(outs)), axis)
        return outs

    fn = jax.shard_map(
        local_fn, mesh=mesh,
        in_specs=(P(axis), P()),
        out_specs=P())
    return fn(params, x)


def bubble_fraction(n_stages: int, n_micro: int) -> float:
    return (n_stages - 1) / (n_stages + n_micro - 1)
