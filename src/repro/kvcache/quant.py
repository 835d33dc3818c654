"""int8 cold-page KV tier: per-page-scaled quantized copies of pool slabs.

The STAR retention story says a page that leaves the DLZS hot set is, by
construction, the page least likely to matter to any future query — which
makes it the safest page to hold at lower precision. This module adds a
quantized MIRROR tier next to the fp slabs: every attention cache dict
(``{"k", "v", "k_lz", ...}``) gains

* ``kq``/``vq``     — int8 codes, same shape as ``k``/``v``;
* ``k_scale``/``v_scale`` — f32 per-(layer, page) absmax scales, shape
  ``k.shape[:-3]`` (``[L, P]`` single-pool, ``[S, L, P]`` spatial).

Pages are quantized symmetrically (``scale = absmax / 127``), so the
per-element round-trip error is bounded by ``scale / 2`` — the bound the
property tests assert. The fp rows stay intact: prefill past-page reads
remain exact, only the bounded decode gather reads the int8 tier
(dequantize-on-gather in ``kvcache.paged_attention``). Capacity-wise the
tier is accounted as the blended bytes of an fp hot set plus int8 cold
pages — the "roughly doubles effective pool capacity" claim, measured in
``BENCH_serving.json decode_sparse``. Host-side which-page-is-quantized
bookkeeping lives in ``pool.QuantTracker``.

Every helper here is structural (works on the nested layer dict of either
backend) or pure jittable math; nothing touches the pool.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

QUANT_KEYS = ("kq", "vq", "k_scale", "v_scale")
_EPS = 1e-8


def _is_attn(d) -> bool:
    return isinstance(d, dict) and "k" in d and "v" in d


def _map_attn(layers, fn):
    """Apply ``fn`` to every attention cache dict in the layer tree."""
    if _is_attn(layers):
        return fn(layers)
    if isinstance(layers, dict):
        return {k: _map_attn(v, fn) for k, v in layers.items()}
    return layers


def has_quant(layers) -> bool:
    """Does this layer tree carry the quantized tier?"""
    if _is_attn(layers):
        return "kq" in layers
    if isinstance(layers, dict):
        return any(has_quant(v) for v in layers.values())
    return False


def find_scale(layers):
    """First ``k_scale`` leaf in the tree (None when the tier is absent).
    ``quantize_pages`` writes every attn dict's scales for the same page
    set, so any one leaf answers "was this page quantized?"."""
    if _is_attn(layers):
        return layers.get("k_scale")
    if isinstance(layers, dict):
        for v in layers.values():
            s = find_scale(v)
            if s is not None:
                return s
    return None


def add_quant_slabs(layers):
    """Attach zeroed int8 slabs + per-page scales to every attn dict."""
    def add(d):
        out = dict(d)
        out["kq"] = jnp.zeros(d["k"].shape, jnp.int8)
        out["vq"] = jnp.zeros(d["v"].shape, jnp.int8)
        sh = d["k"].shape[:-3]          # drop (page, n_kv, head_dim)
        out["k_scale"] = jnp.zeros(sh, jnp.float32)
        out["v_scale"] = jnp.zeros(sh, jnp.float32)
        return out
    return _map_attn(layers, add)


def split_quant(layers):
    """(base, quant) with identical nesting: ``base`` holds the fp leaves,
    ``quant`` only the tier leaves. Lets two-tree kernels written against
    the fp structure (e.g. the prefill scatter, whose flat wave cache has
    no quant leaves) run untouched, with the tier merged back after."""
    def walk(d):
        if _is_attn(d):
            return ({k: v for k, v in d.items() if k not in QUANT_KEYS},
                    {k: v for k, v in d.items() if k in QUANT_KEYS})
        base, quant = {}, {}
        for k, v in d.items():
            base[k], quant[k] = walk(v)
        return base, quant
    return walk(layers)


def merge_quant(base, quant):
    """Inverse of ``split_quant``."""
    def walk(b, q):
        if _is_attn(b):
            return {**b, **q}
        return {k: walk(b[k], q[k]) for k in b}
    return walk(base, quant)


# -- pure quantization math (jittable) ---------------------------------------

def quantize_rows(rows):
    """fp page rows [..., page, n_kv, dh] -> (int8 codes, scales [...]).

    Symmetric per-page absmax: ``scale = max|x| / 127`` over the trailing
    (page, n_kv, dh) axes, codes clipped to [-127, 127]. Error per element
    is <= scale / 2.
    """
    x = rows.astype(jnp.float32)
    amax = jnp.max(jnp.abs(x), axis=(-1, -2, -3))
    scale = jnp.maximum(amax, _EPS) / 127.0
    q = jnp.clip(jnp.round(x / scale[..., None, None, None]),
                 -127, 127).astype(jnp.int8)
    return q, scale


def dequantize_rows(q, scale):
    """Inverse map back to f32 (the decode gather's read path)."""
    return q.astype(jnp.float32) * scale[..., None, None, None]


def quantize_pages(layers, phys):
    """Write int8 copies of pages ``phys`` (int32 [N], page axis 1) into
    the tier slabs of every attn dict; fp rows stay intact. jit-friendly:
    fixed [N] gather/scatter, idempotent on already-quantized pages. The
    spatial backend vmaps this over the shard axis with per-shard phys."""
    def upd(d):
        out = dict(d)
        for src, qk, sk in (("k", "kq", "k_scale"),
                            ("v", "vq", "v_scale")):
            q, s = quantize_rows(d[src][:, phys])
            out[qk] = d[qk].at[:, phys].set(q)
            out[sk] = d[sk].at[:, phys].set(s)
        return out
    return _map_attn(layers, upd)


def quantize_pages_sharded(layers, phys):
    """Spatial variant: leaves [S, L, P, ...], ``phys`` [S, N] per-shard
    page ids — one vmapped ``quantize_pages`` over the shard axis."""
    return jax.vmap(quantize_pages)(layers, phys)
