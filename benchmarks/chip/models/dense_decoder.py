"""Plain reference of a pre-norm dense decoder (OLMo-1B, ChatGLM3-6B), and
the seeded weights both it and the served program run on.

Written from the published descriptions, in float32 ``jax.numpy`` with
every matmul at ``Precision.HIGHEST``; it imports nothing of the program
under test. The sizes come from a configuration file's ``model`` section:

    x = embed[token]
    per layer:  h = norm(x);  q, k, v = h Wq (+bq), h Wk (+bk), h Wv (+bv)
                rotary on the first rope_fraction of each head's dims
                a = causal softmax(q k^T / sqrt(dh)) v, query head i
                    reading key/value head i // (n_heads / n_kv)
                x = x + a Wo;  x = x + (silu(norm(x) W1) * norm(x) W3) W2
    logits = norm(x) W_head

Departures from the published models, all of which leave the work and
the checked arithmetic as the served program does it:

* RoPE rotates adjacent pairs (2i, 2i+1), as ChatGLM3 does. OLMo-1B
  rotates halves (i, i + dh/2); on random weights that is the same model
  up to a fixed permutation of the query and key columns.
* OLMo-1B ties its output head to its embedding; here the head is a
  weight of its own, as the program serves it.
* norm = OLMo's LayerNorm without affine weights (eps 1e-5), or
  ChatGLM3's RMSNorm with a scale (eps 1e-5, ``model.norm_eps``).

The weights are drawn in the program's parameter layout (the tree of
``repro.models.lm``): stacked per layer under ``blocks.b0``, Q/K/V as
[d, heads, dh], O as [heads, dh, d], the vocabulary padded to the
program's ``vocab_padded`` rows. They are made on the device in one
jitted call, in the dtypes they are served in.

``forward(..., fp8=True)`` is the control: the same reference with every
matmul operand rounded to float8 e4m3 under a per-tensor scale, the
precision step below the bf16 the configurations state.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
E4M3_MAX = 448.0


def _dh(m: dict) -> int:
    return m.get("head_dim") or m["d_model"] // m["n_heads"]


# -- weights -------------------------------------------------------------------

def param_shapes(m: dict, vocab_padded: int) -> dict:
    """{path: (shape, dtype)} of the program's parameter tree."""
    d, L, nh, nkv, f = (m["d_model"], m["n_layers"], m["n_heads"],
                        m["n_kv"], m["d_ff"])
    dh = _dh(m)
    bf, f32 = jnp.bfloat16, jnp.float32
    s = {"embed": ((vocab_padded, d), bf),
         "out_head": ((d, vocab_padded), bf),
         "blocks/b0/core/wq": ((L, d, nh, dh), bf),
         "blocks/b0/core/wk": ((L, d, nkv, dh), bf),
         "blocks/b0/core/wv": ((L, d, nkv, dh), bf),
         "blocks/b0/core/wo": ((L, nh, dh, d), bf),
         "blocks/b0/ffn/w1": ((L, d, f), bf),
         "blocks/b0/ffn/w2": ((L, f, d), bf),
         "blocks/b0/ffn/w3": ((L, d, f), bf)}
    if m["qkv_bias"]:
        s["blocks/b0/core/bq"] = ((L, nh, dh), bf)
        s["blocks/b0/core/bk"] = ((L, nkv, dh), bf)
        s["blocks/b0/core/bv"] = ((L, nkv, dh), bf)
    if m["norm"] == "rmsnorm":
        s["final_norm/scale"] = ((d,), f32)
        s["blocks/b0/norm1/scale"] = ((L, d), f32)
        s["blocks/b0/norm2/scale"] = ((L, d), f32)
    return s


def _fan_in(path: str, shape) -> int:
    """Input width of a weight: what its std is scaled by."""
    name = path.rsplit("/", 1)[-1]
    if name == "wo":
        return shape[-3] * shape[-2]
    if name in ("wq", "wk", "wv"):
        return shape[-3]
    return shape[-2]


def _draw(path: str, shape, dtype, key):
    name = path.rsplit("/", 1)[-1]
    if name == "scale":                       # norm gains around 1
        return 1.0 + 0.1 * jax.random.normal(key, shape, dtype)
    x = jax.random.normal(key, shape, jnp.float32)
    if name in ("bq", "bk", "bv"):
        x = 0.5 * x
    elif name != "embed":
        x = x / math.sqrt(_fan_in(path, shape))
    return x.astype(dtype)


def _nest(flat: dict) -> dict:
    tree: dict = {}
    for path, leaf in flat.items():
        node = tree
        *head, last = path.split("/")
        for k in head:
            node = node.setdefault(k, {})
        node[last] = leaf
    if "final_norm" not in tree:
        tree["final_norm"] = {}
    b0 = tree["blocks"]["b0"]
    b0.setdefault("norm1", {})
    b0.setdefault("norm2", {})
    return tree


def make_params(m: dict, vocab_padded: int, seed_key) -> dict:
    """All weights, drawn on the device in one jitted call."""
    shapes = param_shapes(m, vocab_padded)

    @jax.jit
    def draw(key):
        keys = jax.random.split(key, len(shapes))
        return {p: _draw(p, shp, dt, k)
                for k, (p, (shp, dt)) in zip(keys, sorted(shapes.items()))}

    return _nest(draw(seed_key))


# -- reference forward ---------------------------------------------------------

def _q8(x):
    """Round to float8 e4m3 under one per-tensor scale."""
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / E4M3_MAX
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(eq, a, b, fp8):
    if fp8:
        a, b = _q8(a), _q8(b)
    return jnp.einsum(eq, a, b, precision=HIGHEST)


def _norm(m, x, scale):
    eps = m.get("norm_eps", 1e-5)
    if m["norm"] == "rmsnorm":
        y = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)
        return y * scale
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps)


def _rope(m, x, pos):
    """x [S, heads, dh]: rotate pairs (2i, 2i+1) of the first
    rope_fraction of the dims by pos * theta^(-2i / rotary_dim)."""
    dh = x.shape[-1]
    rd = int(dh * m.get("rope_fraction", 1.0))
    rd -= rd % 2
    inv = 1.0 / (m.get("rope_theta", 1e4)
                 ** (jnp.arange(0, rd, 2, dtype=jnp.float32) / rd))
    ang = pos[:, None].astype(jnp.float32) * inv          # [S, rd/2]
    c, s = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., 0:rd:2], x[..., 1:rd:2]
    r = jnp.stack([x1 * c - x2 * s, x2 * c + x1 * s], -1).reshape(
        x.shape[:-1] + (rd,))
    return jnp.concatenate([r, x[..., rd:]], -1)


def _layer(m, fp8, q_block, x, p):
    s_len = x.shape[0]
    nh, nkv = m["n_heads"], m["n_kv"]
    dh = _dh(m)
    f32 = lambda a: a.astype(jnp.float32)
    pos = jnp.arange(s_len)
    h = _norm(m, x, f32(p["norm1"]["scale"]) if p["norm1"] else None)
    c = p["core"]
    q = _mm("sd,dnh->snh", h, f32(c["wq"]), fp8)
    k = _mm("sd,dnh->snh", h, f32(c["wk"]), fp8)
    v = _mm("sd,dnh->snh", h, f32(c["wv"]), fp8)
    if "bq" in c:
        q, k, v = q + f32(c["bq"]), k + f32(c["bk"]), v + f32(c["bv"])
    q, k = _rope(m, q, pos), _rope(m, k, pos)
    k = jnp.repeat(k, nh // nkv, axis=1)
    v = jnp.repeat(v, nh // nkv, axis=1)

    def attend(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * q_block, q_block, 0)
        sc = _mm("qnh,snh->nqs", qb, k, fp8) / math.sqrt(dh)
        qpos = i * q_block + jnp.arange(q_block)
        sc = jnp.where(pos[None, None, :] <= qpos[None, :, None], sc,
                       -jnp.inf)
        w = jax.nn.softmax(sc, axis=-1)
        return _mm("nqs,snh->qnh", w, v, fp8)

    a = jax.lax.map(attend, jnp.arange(s_len // q_block))
    a = a.reshape(s_len, nh, dh)
    x = x + _mm("snh,nhd->sd", a, f32(c["wo"]), fp8)
    h2 = _norm(m, x, f32(p["norm2"]["scale"]) if p["norm2"] else None)
    g = p["ffn"]
    u = jax.nn.silu(_mm("sd,df->sf", h2, f32(g["w1"]), fp8)) \
        * _mm("sd,df->sf", h2, f32(g["w3"]), fp8)
    return x + _mm("sf,fd->sd", u, f32(g["w2"]), fp8)


@functools.partial(jax.jit, static_argnames=("m_items", "fp8"))
def _forward(params, tokens, read_pos, *, m_items, fp8):
    m = dict(m_items)
    s_len = tokens.shape[0]
    q_block = min(512, s_len)
    x = params["embed"][tokens].astype(jnp.float32)

    def body(x, p):
        return _layer(m, fp8, q_block, x, p), None

    x, _ = jax.lax.scan(body, x, params["blocks"]["b0"])
    fn = params["final_norm"]
    xr = _norm(m, x[read_pos],
               fn["scale"].astype(jnp.float32) if fn else None)
    head = params["out_head"][:, :m["vocab"]].astype(jnp.float32)
    return _mm("kd,dv->kv", xr, head, fp8)


def _freeze(m: dict) -> tuple:
    return tuple(sorted((k, v) for k, v in m.items()
                        if isinstance(v, (int, float, str, bool))))


def logits_at(m: dict, params, tokens, read_pos, *, fp8: bool = False,
              bucket: int = 1024, reads: int = 512) -> np.ndarray:
    """Float32 logits [len(read_pos), vocab] of the reference over
    ``tokens``, read at positions ``read_pos``. The sequence is padded to
    a multiple of ``bucket`` (causal, so padding never reaches a read
    position) and the reads to ``reads`` rows, to keep compiles few."""
    n = len(tokens)
    s_len = -(-n // bucket) * bucket
    toks = np.zeros((s_len,), np.int32)
    toks[:n] = tokens
    k = len(read_pos)
    rp = np.zeros((max(reads, k),), np.int32)
    rp[:k] = read_pos
    out = _forward(params, jnp.asarray(toks), jnp.asarray(rp),
                   m_items=_freeze(m), fp8=fp8)
    return np.asarray(out)[:k]
