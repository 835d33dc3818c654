"""xLSTM blocks: mLSTM (matrix memory, chunk-parallel) and sLSTM (scalar).

mLSTM is a gated linear attention:  C_t = f_t C_{t-1} + i_t v_t k_tᵀ,
n_t = f_t n_{t-1} + i_t k_t,  y_t = C_t q_t / max(|n_tᵀ q_t|, 1) — we reuse
``chunked_linear_attention`` with the normalizer carried as an extra value
column (X = [i·v, i·1]). Exponential input gates are soft-clamped instead of
running the paper's m_t stabilizer (fp32 statistics make it unnecessary at
our scale; noted in DESIGN.md).

sLSTM keeps per-head scalar state with block-diagonal recurrent weights and
is inherently sequential -> lax.scan over time.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.models import common
from repro.models.ssm import chunked_linear_attention, linear_attention_step
from repro.shardlib import shd


@dataclasses.dataclass(frozen=True)
class XLSTMCfg:
    d_model: int
    n_heads: int
    chunk: int = 256
    dtype: jnp.dtype = jnp.bfloat16

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_heads


def _clamp_exp(x, lo=-10.0, hi=5.0):
    return jnp.exp(jnp.clip(x, lo, hi))


# ---------------------------------------------------------------------------
# mLSTM
# ---------------------------------------------------------------------------

def mlstm_init(key, cfg: XLSTMCfg):
    ks = jax.random.split(key, 7)
    h, nh, dh = cfg.d_model, cfg.n_heads, cfg.head_dim
    return {
        "wq": common.truncated_normal_init(ks[0], (h, nh * dh), 1.0,
                                           cfg.dtype).reshape(h, nh, dh),
        "wk": common.truncated_normal_init(ks[1], (h, nh * dh), 1.0,
                                           cfg.dtype).reshape(h, nh, dh),
        "wv": common.truncated_normal_init(ks[2], (h, nh * dh), 1.0,
                                           cfg.dtype).reshape(h, nh, dh),
        "wi": common.truncated_normal_init(ks[3], (h, nh), 1.0, jnp.float32),
        "wf": common.truncated_normal_init(ks[4], (h, nh), 1.0, jnp.float32),
        "wog": common.truncated_normal_init(ks[5], (h, h), 1.0, cfg.dtype),
        "wo": common.truncated_normal_init(ks[6], (nh * dh, h), 1.0,
                                           cfg.dtype).reshape(nh, dh, h),
        "norm_scale": jnp.ones((nh, dh), jnp.float32),
    }


def mlstm_axes(cfg: XLSTMCfg):
    return {
        "wq": ("embed_w", "heads_ssm", "head_dim"),
        "wk": ("embed_w", "heads_ssm", "head_dim"),
        "wv": ("embed_w", "heads_ssm", "head_dim"),
        "wi": ("embed_w", "heads_ssm"), "wf": ("embed_w", "heads_ssm"),
        "wog": ("embed_w", "embed"),
        "wo": ("heads_ssm", "head_dim", "embed_w"),
        "norm_scale": ("heads_ssm", "head_dim"),
    }


def _mlstm_gates(params, cfg: XLSTMCfg, x):
    q = jnp.einsum("bsh,hnd->bsnd", x, params["wq"])
    k = jnp.einsum("bsh,hnd->bsnd", x, params["wk"]) \
        / jnp.sqrt(float(cfg.head_dim)).astype(x.dtype)
    v = jnp.einsum("bsh,hnd->bsnd", x, params["wv"])
    i_raw = jnp.einsum("bsh,hn->bsn", x.astype(jnp.float32), params["wi"])
    f_raw = jnp.einsum("bsh,hn->bsn", x.astype(jnp.float32), params["wf"])
    i_gate = _clamp_exp(i_raw)                        # exponential input gate
    log_f = jax.nn.log_sigmoid(f_raw)                 # log decay <= 0
    return q, k, v, i_gate, log_f


def _headnorm(y, scale):
    """Per-head RMS norm of the mLSTM readout (xLSTM's multi-head norm)."""
    var = jnp.mean(jnp.square(y), axis=-1, keepdims=True)
    return y * jax.lax.rsqrt(var + 1e-6) * scale


def mlstm_apply(params, cfg: XLSTMCfg, x, *, make_cache: bool = False):
    """x [B,S,H] -> (y, cache|None). Chunk-parallel over the sequence."""
    bsz, s, _ = x.shape
    nh, dh = cfg.n_heads, cfg.head_dim
    q, k, v, i_gate, log_f = _mlstm_gates(params, cfg, x)
    ones = jnp.ones((bsz, s, nh, 1), jnp.float32)
    x_aug = jnp.concatenate(
        [v.astype(jnp.float32), ones], axis=-1) * i_gate[..., None]
    chunk = min(cfg.chunk, s)
    while s % chunk:
        chunk -= 1
    y_aug, h_final = chunked_linear_attention(
        q.astype(jnp.float32), k.astype(jnp.float32), x_aug, log_f,
        chunk=chunk)
    num, den = y_aug[..., :dh], y_aug[..., dh:]
    y = num / jnp.maximum(jnp.abs(den), 1.0)
    y = _headnorm(y, params["norm_scale"])
    og = jax.nn.sigmoid(jnp.einsum("bsh,hg->bsg", x, params["wog"]))
    out = jnp.einsum("bsnd,ndh->bsh", y.astype(x.dtype), params["wo"]) * og
    out = shd(out, "batch", "act_seq", "embed")
    cache = {"state": h_final} if make_cache else None
    return out, cache


def mlstm_decode(params, cfg: XLSTMCfg, x, cache):
    """x [B,1,H] -> (y [B,1,H], new cache). O(1) per step."""
    bsz = x.shape[0]
    nh, dh = cfg.n_heads, cfg.head_dim
    q, k, v, i_gate, log_f = _mlstm_gates(params, cfg, x)
    x_aug = jnp.concatenate(
        [v[:, 0].astype(jnp.float32), jnp.ones((bsz, nh, 1))], -1) \
        * i_gate[:, 0, :, None]
    y_aug, h_new = linear_attention_step(
        q[:, 0].astype(jnp.float32), k[:, 0].astype(jnp.float32), x_aug,
        log_f[:, 0], cache["state"])
    num, den = y_aug[..., :dh], y_aug[..., dh:]
    y = num / jnp.maximum(jnp.abs(den), 1.0)
    y = _headnorm(y, params["norm_scale"])[:, None]
    og = jax.nn.sigmoid(jnp.einsum("bsh,hg->bsg", x, params["wog"]))
    out = jnp.einsum("bsnd,ndh->bsh", y.astype(x.dtype), params["wo"]) * og
    return out, {"state": h_new}


# ---------------------------------------------------------------------------
# sLSTM
# ---------------------------------------------------------------------------

def slstm_init(key, cfg: XLSTMCfg):
    ks = jax.random.split(key, 8)
    h, nh, dh = cfg.d_model, cfg.n_heads, cfg.head_dim
    w = lambda i: common.truncated_normal_init(
        ks[i], (h, nh * dh), 1.0, cfg.dtype).reshape(h, nh, dh)
    r = lambda i: common.truncated_normal_init(
        ks[i], (nh * dh, dh), 1.0, jnp.float32).reshape(nh, dh, dh)
    return {
        "wz": w(0), "wi": w(1), "wf": w(2), "wo_gate": w(3),
        "rz": r(4), "ri": r(5), "rf": r(6), "ro": r(7),
        "wout": common.truncated_normal_init(
            jax.random.fold_in(key, 99), (nh * dh, h), 1.0,
            cfg.dtype).reshape(nh, dh, h),
    }


def slstm_axes(cfg: XLSTMCfg):
    per_head = ("heads_ssm", "head_dim")
    return {
        "wz": ("embed_w",) + per_head, "wi": ("embed_w",) + per_head,
        "wf": ("embed_w",) + per_head, "wo_gate": ("embed_w",) + per_head,
        "rz": ("heads_ssm", "head_dim", None),
        "ri": ("heads_ssm", "head_dim", None),
        "rf": ("heads_ssm", "head_dim", None),
        "ro": ("heads_ssm", "head_dim", None),
        "wout": ("heads_ssm", "head_dim", "embed_w"),
    }


def _scan_shardmapped(params, carry, xs):
    """Run the sLSTM time scan per-device via shard_map (see slstm_apply)."""
    from repro.shardlib import rules as shr

    mesh = shr.current_mesh()
    rparams = {k: params[k] for k in ("rz", "ri", "rf", "ro")}

    from jax.sharding import PartitionSpec as P

    bspec = shr.logical_spec(("batch",), (xs[0].shape[1],)) \
        if mesh is not None else P()
    b_ax = bspec[0] if len(bspec) else None
    vary_axes = () if b_ax is None else \
        ((b_ax,) if isinstance(b_ax, str) else tuple(b_ax))

    def local(rp, cr, xs_):
        # Cast R to varying FIRST, over exactly the axes the activations
        # vary on, so the recurrent einsum's transpose needs no per-step
        # psum_invariant — the single psum lands at this cast's
        # transpose, outside the 4096-step loop (§Perf cell C5).
        rp = jax.tree.map(
            lambda r: jax.lax.pcast(r, vary_axes, to="varying"), rp)
        return jax.lax.scan(lambda c, g: _slstm_step(rp, c, g), cr, xs_)

    if mesh is None or not vary_axes:
        rparams_local = {k: params[k] for k in ("rz", "ri", "rf", "ro")}
        return jax.lax.scan(
            lambda c, g: _slstm_step(rparams_local, c, g), carry, xs)
    rspec = jax.tree.map(
        lambda r: shr.logical_spec(("heads_ssm", "head_dim", None),
                                   r.shape), rparams)
    state_sp = P(b_ax)
    xs_sp = tuple(P(None, b_ax) for _ in xs)
    fn = jax.shard_map(
        local, mesh=mesh,
        in_specs=(rspec, (state_sp,) * 3, xs_sp),
        out_specs=((state_sp,) * 3, P(None, b_ax)))
    return fn(rparams, carry, xs)


def _slstm_step(params, carry, gates_t):
    """One recurrent step. carry = (c, n, h) each [B,nh,dh]."""
    c, n, h = carry
    gz, gi, gf, go = gates_t                    # [B,nh,dh] pre-activations
    rec = lambda r: jnp.einsum("bnd,nde->bne", h, r)
    z = jnp.tanh(gz + rec(params["rz"]))
    i = _clamp_exp(gi + rec(params["ri"]))
    f = jax.nn.sigmoid(gf + rec(params["rf"]))
    o = jax.nn.sigmoid(go + rec(params["ro"]))
    c_new = f * c + i * z
    n_new = f * n + i
    h_new = o * (c_new / jnp.maximum(jnp.abs(n_new), 1.0))
    return (c_new, n_new, h_new), h_new


def slstm_apply(params, cfg: XLSTMCfg, x, *, make_cache: bool = False,
                carry=None):
    """x [B,S,H] -> (y, cache|None). Sequential scan over time."""
    bsz, s, _ = x.shape
    nh, dh = cfg.n_heads, cfg.head_dim
    pre = {g: jnp.einsum("bsh,hnd->bsnd", x,
                         params[g]).astype(jnp.float32)
           for g in ("wz", "wi", "wf", "wo_gate")}
    if carry is None:
        zero = jnp.zeros((bsz, nh, dh), jnp.float32)
        carry = (zero, zero, zero)
    xs = tuple(jnp.moveaxis(pre[g], 1, 0)
               for g in ("wz", "wi", "wf", "wo_gate"))
    # The time scan runs under shard_map: all per-step math is device-local
    # (batch-sharded), so autodiff's psum for the recurrent R-matrix grads
    # lands ONCE at the layer boundary — GSPMD otherwise emits an all-reduce
    # of dR inside the loop, 4096x per layer (§Perf cell C iteration 3).
    carry, hs = _scan_shardmapped(params, carry, xs)
    hs = jnp.moveaxis(hs, 0, 1)                 # [B,S,nh,dh]
    out = jnp.einsum("bsnd,ndh->bsh", hs.astype(x.dtype), params["wout"])
    out = shd(out, "batch", "act_seq", "embed")
    cache = {"c": carry[0], "n": carry[1], "h": carry[2]} if make_cache \
        else None
    return out, cache


def slstm_decode(params, cfg: XLSTMCfg, x, cache):
    carry = (cache["c"], cache["n"], cache["h"])
    y, new_cache = slstm_apply(params, cfg, x, make_cache=True, carry=carry)
    return y, new_cache
