"""Mixture-of-Experts with expert parallelism (GShard-style, shard_map).

Dataflow per MoE layer (inside ``shard_map`` over the full mesh):

  tokens --gate/top-k--> scatter into per-virtual-expert capacity buffers
         --all_to_all(data)--> each shard's experts process their tokens
         (batched matmuls, TP over 'model' inside each expert, psum)
         --all_to_all(data)--> gather back, combine weighted by gate probs.

**Virtual experts** make every assigned arch divide the fixed production
mesh: with E real experts and EP = |data| shards,
  * E >= EP  (olmoe 64, jamba 16): each shard owns E/EP whole experts;
  * E <  EP  (grok 8 on EP=16): each expert's FFN dim is split ``tpw = EP/E``
    ways — a token is dispatched to all ``tpw`` slices of its expert and the
    slice outputs sum (the W2 contraction distributes over the split), i.e.
    Megatron-TP *within* an expert across the EP axis. Compute and capacity
    stay exactly balanced; only routing traffic duplicates by tpw.

Capacity-based token dropping (capacity_factor, default 1.25) bounds the
buffers; a load-balancing auxiliary loss (Switch-style) keeps routing usable
for training. Long token streams are processed in fixed-size chunks via
lax.scan so dispatch buffers stay O(chunk), not O(batch·seq).

Serving never drops: ``apply_dropless`` routes every token over all
experts and sends each of its top-k choices to its expert, through grouped
matmuls (``jax.lax.ragged_dot``) over the experts this chip holds, so a
token's output never depends on the tokens it shares a batch with. A chip
of an expert-parallel deployment holds a contiguous share of the experts
(``init(..., held=n)``) and computes only their part of the layer's output;
a token none of whose choices is held gets no expert term here.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models import common
from repro.shardlib import rules as shr
from repro.shardlib import shd


@dataclasses.dataclass(frozen=True)
class MoECfg:
    n_experts: int
    top_k: int
    d_model: int = 0            # the model's widths: lm.ModelCfg.moe_cfg()
    d_ff: int = 0               # sets both (d_ff is the per-expert width)
    act: str = "silu"
    gated: bool = True
    capacity_factor: float = 1.25
    token_chunk: int = 2048     # per-shard tokens per dispatch round
    aux_loss_weight: float = 0.01
    renorm: bool = True         # renormalise the top-k probabilities
    #                             (OLMoE's norm_topk_prob: false)
    dtype: jnp.dtype = jnp.bfloat16

    def virtual(self, ep: int) -> tuple[int, int]:
        """(V virtual experts, tpw split factor) for an EP-way expert axis."""
        if self.n_experts >= ep:
            if self.n_experts % ep:
                raise ValueError(
                    f"E={self.n_experts} not divisible by EP={ep}")
            return self.n_experts, 1
        if ep % self.n_experts:
            raise ValueError(f"EP={ep} not divisible by E={self.n_experts}")
        return ep, ep // self.n_experts


def init(key, cfg: MoECfg, ep_hint: int = 16, held: int = 0):
    """Parameters are stored pre-split into virtual-expert layout [V, ...].

    ``ep_hint`` is the maximum EP degree the layout must divide (the
    production data-axis size); running on a smaller mesh still works because
    V stays divisible by any EP' | EP. With ``held`` > 0 only that many whole
    experts are drawn ([held, ...]: one chip's share); the router keeps all
    ``n_experts`` outputs.
    """
    v, tpw = (held, 1) if held else cfg.virtual(ep_hint)
    ks = jax.random.split(key, 4)
    ff = cfg.d_ff // tpw
    p = {
        "wg": common.truncated_normal_init(ks[0],
                                           (cfg.d_model, cfg.n_experts),
                                           1.0, jnp.float32),
        "w1": common.truncated_normal_init(
            ks[1], (v, cfg.d_model, ff), 1.0, cfg.dtype),
        "w2": common.truncated_normal_init(
            ks[2], (v, ff, cfg.d_model), 1.0, cfg.dtype),
    }
    if cfg.gated:
        p["w3"] = common.truncated_normal_init(
            ks[3], (v, cfg.d_model, ff), 1.0, cfg.dtype)
    return p


def axes(cfg: MoECfg):
    a = {
        "wg": ("embed", None),
        "w1": ("experts", "embed", "expert_mlp"),
        "w2": ("experts", "expert_mlp", "embed"),
    }
    if cfg.gated:
        a["w3"] = ("experts", "embed", "expert_mlp")
    return a


def _gate(x, wg, cfg: MoECfg):
    """Top-k routing. x [t,H] -> (probs [t,k], eidx [t,k], aux_loss scalar)."""
    logits = jnp.einsum("th,he->te", x.astype(jnp.float32),
                        wg.astype(jnp.float32))
    probs_full = jax.nn.softmax(logits, axis=-1)
    top_p, eidx = jax.lax.top_k(probs_full, cfg.top_k)
    if cfg.renorm:
        top_p = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)
    # Switch-style load-balance loss: E * sum_e f_e * P_e.
    f = jnp.zeros((cfg.n_experts,), jnp.float32).at[eidx.reshape(-1)].add(
        1.0) / (eidx.size)
    pbar = probs_full.mean(axis=0)
    aux = cfg.n_experts * jnp.sum(f * pbar)
    return top_p, eidx, aux


def _dispatch_combine(x, p, cfg: MoECfg, ep_axis: Optional[str],
                      tp_axis: Optional[str], ep: int):
    """One chunk of tokens through the EP pipeline (runs per device)."""
    t_loc, h = x.shape
    v = p["w1"].shape[0] * ep           # global virtual experts
    tpw = v // cfg.n_experts
    kc = cfg.top_k * tpw                # choices per token (incl. splits)
    cap = int(t_loc * cfg.top_k * tpw * cfg.capacity_factor / v + 1)
    cap = max(8, -(-cap // 8) * 8)      # round up to 8

    top_p, eidx, aux = _gate(x, p["wg"], cfg)

    # token choices -> virtual expert targets [t, k, tpw] -> flat [N]
    vidx = (eidx[..., None] * tpw + jnp.arange(tpw)).reshape(t_loc, kc)
    w_choice = jnp.repeat(top_p, tpw, axis=-1)          # same prob per slice
    vflat = vidx.reshape(-1)                            # [N = t*kc]
    onehot = jax.nn.one_hot(vflat, v, dtype=jnp.int32)  # [N, V]
    pos = (jnp.cumsum(onehot, axis=0) - 1) * onehot     # position in expert
    pos = pos.sum(axis=-1)                              # [N]
    keep = pos < cap
    slot = jnp.where(keep, pos, cap)                    # overflow -> slot cap

    # scatter tokens into [V, cap(+1 overflow), H]
    buf = jnp.zeros((v, cap + 1, h), x.dtype)
    token_rows = jnp.repeat(x, kc, axis=0)              # [N, H]
    buf = buf.at[vflat, slot].set(token_rows)           # last writer wins: ok
    buf = buf[:, :cap]                                  # drop overflow slot

    if ep_axis is not None:
        # [V, cap, H] -> [V/ep, ep*cap, H]: expert shards receive their tokens
        buf = jax.lax.all_to_all(buf, ep_axis, split_axis=0, concat_axis=1,
                                 tiled=True)

    # Expert FFN: batched over local experts, TP over 'model' on the ff dim.
    act = common.activation(cfg.act)
    hmid = jnp.einsum("vth,vhf->vtf", buf, p["w1"])
    hmid = act(hmid)
    if cfg.gated:
        hmid = hmid * jnp.einsum("vth,vhf->vtf", buf, p["w3"])
    y = jnp.einsum("vtf,vfh->vth", hmid, p["w2"])
    if tp_axis is not None:
        y = jax.lax.psum(y, tp_axis)                    # TP partial sums

    if ep_axis is not None:
        y = jax.lax.all_to_all(y, ep_axis, split_axis=1, concat_axis=0,
                               tiled=True)              # back to [V, cap, H]

    # combine: gather each choice's output row, weight, sum over choices
    y = jnp.concatenate([y, jnp.zeros((v, 1, h), y.dtype)], axis=1)
    rows = y[vflat, slot]                               # [N, H]
    rows = rows * (w_choice.reshape(-1, 1).astype(rows.dtype)
                   * keep[:, None].astype(rows.dtype))
    out = rows.reshape(t_loc, kc, h).sum(axis=1)
    return out, aux


def apply(params, cfg: MoECfg, x):
    """x [B,S,H] -> (y [B,S,H], aux_loss scalar). Uses the active mesh."""
    mesh = shr.current_mesh()
    b, s, h = x.shape

    ep_axis = shr.mesh_axis("experts")
    tp_axis = shr.mesh_axis("expert_mlp")
    batch_ax = shr.batch_axes()

    def local_fn(xl, pl):
        ep = 1
        if ep_axis is not None:
            ax = (ep_axis,) if isinstance(ep_axis, str) else ep_axis
            ep = 1
            for a in ax:
                ep *= mesh.shape[a]
        bl, sl, _ = xl.shape
        tokens = xl.reshape(bl * sl, h)
        t_loc = tokens.shape[0]
        chunk = min(cfg.token_chunk, t_loc)
        while t_loc % chunk:
            chunk -= 1
        n_chunks = t_loc // chunk

        if n_chunks == 1:
            out, aux = _dispatch_combine(
                tokens, pl, cfg, ep_axis if ep > 1 else None,
                tp_axis, ep)
        else:
            def step(_, xc):
                o, a = _dispatch_combine(
                    xc, pl, cfg, ep_axis if ep > 1 else None, tp_axis, ep)
                return None, (o, a)

            # remat each chunk: dispatch/a2a buffers are recomputed in bwd
            # instead of staying live for every chunk simultaneously.
            _, (out, aux) = jax.lax.scan(
                jax.checkpoint(step), None, tokens.reshape(n_chunks, chunk,
                                                           h))
            out = out.reshape(t_loc, h)
            aux = aux.mean()
        out = out.reshape(bl, sl, h)
        # aux replicated everywhere; out must be *provably* replicated over
        # any EP axis the tokens were NOT sharded over (B=1 decode: every
        # shard dispatched identical tokens — a pmean makes check_vma see
        # it, at the cost of a tiny [1,1,H] all-reduce).
        for axn in mesh.axis_names if mesh is not None else ():
            aux = jax.lax.pmean(aux, axn)
        if ep_axis is not None:
            ep_axes = (ep_axis,) if isinstance(ep_axis, str) else ep_axis
            for axn in ep_axes:
                if axn not in batch_ax:
                    out = jax.lax.pmean(out, axn)
        return out, aux

    if mesh is None:
        # No mesh context (pure CPU unit tests): single-shard execution.
        out, aux = local_fn(x, params)
        return out, aux

    bspec = shr.logical_spec(("batch", None, None), (b, s, h))
    pspecs = {
        "wg": P(),
        "w1": shr.logical_spec(("experts", None, "expert_mlp"),
                               params["w1"].shape),
        "w2": shr.logical_spec(("experts", "expert_mlp", None),
                               params["w2"].shape),
    }
    if cfg.gated:
        pspecs["w3"] = pspecs["w1"]

    fn = jax.shard_map(local_fn, mesh=mesh,
                       in_specs=(bspec, pspecs),
                       out_specs=(bspec, P()))
    return fn(x, params)


def apply_dropless(params, cfg: MoECfg, x, *, first: int = 0, held: int = 0,
                   valid=None):
    """Dropless expert layer over the experts held here (serving).

    x [..., H] -> (y [..., H], hist [held] int32). The router scores every
    token over all ``n_experts``; each of its top-k choices that falls in
    the held range [first, first + held) (``held`` 0: all experts) goes to
    that expert with no capacity limit, and the outputs are summed weighted
    by the router probabilities (renormalised over the top-k only with
    ``cfg.renorm``). Assignments are sorted by held (virtual) expert and run
    as three grouped matmuls (``ragged_dot``); choices of experts held
    elsewhere sort past the last group, which computes nothing for them.
    ``hist`` counts, per held expert, the assignments of the tokens that
    ``valid`` (x's leading shape, default all) marks real.
    """
    shape = x.shape
    t = x.reshape(-1, shape[-1])
    n = t.shape[0]
    held = held or cfg.n_experts
    hv = params["w1"].shape[0]              # held virtual experts
    tpw = hv // held
    kc = cfg.top_k * tpw

    logits = jnp.einsum("th,he->te", t.astype(jnp.float32),
                        params["wg"].astype(jnp.float32))
    top_p, eidx = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), cfg.top_k)
    if cfg.renorm:
        top_p = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)
    local = eidx - first
    here = (local >= 0) & (local < held)                # [n, k]
    vloc = (local[..., None] * tpw + jnp.arange(tpw)).reshape(n, kc)
    here_v = jnp.repeat(here, tpw, axis=-1)
    flat = jnp.where(here_v, vloc, hv).reshape(-1)      # [n * kc]
    order = jnp.argsort(flat, stable=True)
    sizes = jnp.zeros((hv + 1,), jnp.int32).at[flat].add(1)[:hv]
    rows = jnp.take(t, order // kc, axis=0)

    act = common.activation(cfg.act)
    f32 = jnp.float32
    with jax.named_scope("moe_gmm"):
        h = act(jax.lax.ragged_dot(rows, params["w1"], sizes,
                                   preferred_element_type=f32))
        if cfg.gated:
            h = h * jax.lax.ragged_dot(rows, params["w3"], sizes,
                                       preferred_element_type=f32)
        y = jax.lax.ragged_dot(h.astype(x.dtype), params["w2"], sizes,
                               preferred_element_type=f32)
    y = jnp.zeros_like(y).at[order].set(y).reshape(n, kc, shape[-1])
    w = jnp.where(here_v, jnp.repeat(top_p, tpw, axis=-1), 0.0)
    out = (y * w[..., None]).sum(axis=1).astype(x.dtype).reshape(shape)

    ok = here if valid is None else here & valid.reshape(n, 1)
    hist = jnp.zeros((held + 1,), jnp.int32).at[
        jnp.where(ok, local, held).reshape(-1)].add(1)[:held]
    return out, hist
