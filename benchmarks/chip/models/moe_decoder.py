"""Plain reference of a pre-norm mixture-of-experts decoder (OLMoE-1B-7B)
on one chip's share of the experts, and the seeded weights both it and the
served program run on.

Written from the published description (arXiv:2409.02060; the model's
``config.json``), in float32 ``jax.numpy`` with every matmul at
``Precision.HIGHEST``; it imports nothing of the program under test, and
of ``dense_decoder`` only its plain pieces (norm, RoPE, the fp8 rounding,
the weight drawing). The sizes come from a configuration file's ``model``
section:

    x = embed[token]
    per layer:  h = rms(x)
                q = rope(q_norm(h Wq)), k = rope(k_norm(h Wk)), v = h Wv
                    (q_norm, k_norm: RMSNorm over all heads' features)
                a = causal softmax(q k^T / sqrt(dh)) v;  x = x + a Wo
                u = rms(x);  p = softmax(u Wg) over all n_experts
                x = x + sum over e in top_k(p) held here of
                        p_e (silu(u W1_e) * u W3_e) W2_e
    logits = rms(x) W_head

The top-k probabilities are not renormalised (``norm_topk_prob`` false;
``model.norm_topk_prob`` true renormalises). The chip holds experts
[``expert_first``, ``expert_first`` + ``experts_held``) of every layer:
the router keeps its published width and experts per token, and only the
held experts' terms are added, so a token none of whose choices is held
gets no expert term, as in the program.

Departures from the published model, all of which leave the work and the
checked arithmetic as the served program does it:

* RoPE rotates adjacent pairs (2i, 2i+1), as in ``dense_decoder``; OLMoE
  rotates halves (i, i + dh/2). On random weights that is the same model up
  to a fixed permutation of the query and key columns.
* The program's RMSNorm takes eps 1e-6 where OLMoE states 1e-5; the
  reference keeps 1e-5 (``model.norm_eps``).

The weights are drawn in the program's parameter layout (the tree of
``repro.models.lm``), stacked per layer under ``blocks.b0``: Q/K/V as
[d, heads, dh], O as [heads, dh, d], the QK-norm scales as
``core/q_norm/scale`` [heads * dh] and ``core/k_norm/scale``, the router as
``ffn/wg`` [d, n_experts] in float32 and the held experts as ``ffn/w1``,
``w3`` [held, d, d_ff] and ``w2`` [held, d_ff, d]. ``forward(..., fp8=True)``
is the control, as in ``dense_decoder``.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.chip.models import dense_decoder as dd


def held(m: dict) -> int:
    return m.get("experts_held") or m["n_experts"]


# -- weights -------------------------------------------------------------------

def param_shapes(m: dict, vocab_padded: int) -> dict:
    """{path: (shape, dtype)} of the program's parameter tree."""
    d, L, nh, nkv, f = (m["d_model"], m["n_layers"], m["n_heads"],
                        m["n_kv"], m["d_ff"])
    dh = dd._dh(m)
    e = held(m)
    bf, f32 = jnp.bfloat16, jnp.float32
    return {"embed": ((vocab_padded, d), bf),
            "out_head": ((d, vocab_padded), bf),
            "final_norm/scale": ((d,), f32),
            "blocks/b0/norm1/scale": ((L, d), f32),
            "blocks/b0/norm2/scale": ((L, d), f32),
            "blocks/b0/core/wq": ((L, d, nh, dh), bf),
            "blocks/b0/core/wk": ((L, d, nkv, dh), bf),
            "blocks/b0/core/wv": ((L, d, nkv, dh), bf),
            "blocks/b0/core/wo": ((L, nh, dh, d), bf),
            "blocks/b0/core/q_norm/scale": ((L, nh * dh), f32),
            "blocks/b0/core/k_norm/scale": ((L, nkv * dh), f32),
            "blocks/b0/ffn/wg": ((L, d, m["n_experts"]), f32),
            "blocks/b0/ffn/w1": ((L, e, d, f), bf),
            "blocks/b0/ffn/w3": ((L, e, d, f), bf),
            "blocks/b0/ffn/w2": ((L, e, f, d), bf)}


def make_params(m: dict, vocab_padded: int, seed_key) -> dict:
    """All weights, drawn on the device in one jitted call."""
    shapes = param_shapes(m, vocab_padded)

    @jax.jit
    def draw(key):
        keys = jax.random.split(key, len(shapes))
        return {p: dd._draw(p, shp, dt, k)
                for k, (p, (shp, dt)) in zip(keys, sorted(shapes.items()))}

    return dd._nest(draw(seed_key))


# -- reference forward ---------------------------------------------------------

def _rms(m, x, scale):
    eps = m.get("norm_eps", 1e-5)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * scale


def experts(m, fp8, u, g):
    """The MoE term of one layer over tokens u [S, d] (normed), from the
    held experts of ffn params ``g``: [S, d]."""
    f32 = lambda a: a.astype(jnp.float32)
    k, first, e = m["top_k"], m.get("expert_first", 0), held(m)
    p = jax.nn.softmax(dd._mm("sd,de->se", u, f32(g["wg"]), fp8), axis=-1)
    top_p, top_i = jax.lax.top_k(p, k)
    if m.get("norm_topk_prob"):
        top_p = top_p / top_p.sum(-1, keepdims=True)
    # weight of each held expert for each token: its probability where it
    # is among the token's top k, else 0
    ids = first + jnp.arange(e)
    w = jnp.where(top_i[:, :, None] == ids[None, None, :],
                  top_p[:, :, None], 0.0).sum(axis=1)          # [S, e]
    h = jax.nn.silu(dd._mm("sd,edf->sef", u, f32(g["w1"]), fp8)) \
        * dd._mm("sd,edf->sef", u, f32(g["w3"]), fp8)
    y = dd._mm("sef,efd->sed", h, f32(g["w2"]), fp8)
    return (y * w[..., None]).sum(axis=1)


def _layer(m, fp8, q_block, x, p):
    s_len = x.shape[0]
    nh, nkv = m["n_heads"], m["n_kv"]
    dh = dd._dh(m)
    f32 = lambda a: a.astype(jnp.float32)
    pos = jnp.arange(s_len)
    h = _rms(m, x, f32(p["norm1"]["scale"]))
    c = p["core"]
    q = dd._mm("sd,dnh->snh", h, f32(c["wq"]), fp8)
    k = dd._mm("sd,dnh->snh", h, f32(c["wk"]), fp8)
    v = dd._mm("sd,dnh->snh", h, f32(c["wv"]), fp8)
    if m.get("qk_norm"):
        q = _rms(m, q.reshape(s_len, -1),
                 f32(c["q_norm"]["scale"])).reshape(q.shape)
        k = _rms(m, k.reshape(s_len, -1),
                 f32(c["k_norm"]["scale"])).reshape(k.shape)
    q, k = dd._rope(m, q, pos), dd._rope(m, k, pos)
    k = jnp.repeat(k, nh // nkv, axis=1)
    v = jnp.repeat(v, nh // nkv, axis=1)

    def attend(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * q_block, q_block, 0)
        sc = dd._mm("qnh,snh->nqs", qb, k, fp8) / math.sqrt(dh)
        qpos = i * q_block + jnp.arange(q_block)
        sc = jnp.where(pos[None, None, :] <= qpos[None, :, None], sc,
                       -jnp.inf)
        return dd._mm("nqs,snh->qnh", jax.nn.softmax(sc, axis=-1), v, fp8)

    a = jax.lax.map(attend, jnp.arange(s_len // q_block))
    x = x + dd._mm("snh,nhd->sd", a.reshape(s_len, nh, dh), f32(c["wo"]),
                   fp8)
    u = _rms(m, x, f32(p["norm2"]["scale"]))
    return x + experts(m, fp8, u, p["ffn"])


@functools.partial(jax.jit, static_argnames=("m_items", "fp8"))
def _forward(params, tokens, read_pos, *, m_items, fp8):
    m = dict(m_items)
    s_len = tokens.shape[0]
    q_block = min(512, s_len)
    x = params["embed"][tokens].astype(jnp.float32)

    def body(x, p):
        return _layer(m, fp8, q_block, x, p), None

    x, _ = jax.lax.scan(body, x, params["blocks"]["b0"])
    xr = _rms(m, x[read_pos],
              params["final_norm"]["scale"].astype(jnp.float32))
    head = params["out_head"][:, :m["vocab"]].astype(jnp.float32)
    return dd._mm("kd,dv->kv", xr, head, fp8)


def logits_at(m: dict, params, tokens, read_pos, *, fp8: bool = False,
              bucket: int = 1024, reads: int = 512) -> np.ndarray:
    """Float32 logits [len(read_pos), vocab] of the reference over
    ``tokens``, read at positions ``read_pos``, padded as in
    ``dense_decoder.logits_at`` to keep compiles few."""
    n = len(tokens)
    s_len = -(-n // bucket) * bucket
    toks = np.zeros((s_len,), np.int32)
    toks[:n] = tokens
    k = len(read_pos)
    rp = np.zeros((max(reads, k),), np.int32)
    rp[:k] = read_pos
    out = _forward(params, jnp.asarray(toks), jnp.asarray(rp),
                   m_items=dd._freeze(m), fp8=fp8)
    return np.asarray(out)[:k]
