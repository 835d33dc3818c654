"""Gather-based paged decode attention: block tables in, attention out.

Two backends behind one signature (mirroring how kernels/flash.py pairs a
Pallas kernel with kernels/ref.py):

* ``paged_gather_decode`` — pure-XLA fallback: ``jnp.take`` the hot pages
  out of the pool slab into a [B, W·page] working set, then one grouped-GQA
  masked softmax. Runs anywhere (the CPU test/serving path) and is the
  numerics oracle for the kernel.
* ``kernels.paged.paged_decode_attention`` — Pallas kernel whose BlockSpec
  index maps read the block table via scalar prefetch, DMA-ing pages
  directly from the pool (no contiguous HBM copy at all).

Both only touch the ``W`` hot pages the DLZS retention policy selected
(kvcache.allocator.select_hot), so decode compute AND memory traffic scale
with the retained working set, not the sequence length — the engine admits
any prompt length against one compiled decode shape.
"""

from __future__ import annotations

import math
import os
import warnings
from typing import Optional

import jax
import jax.numpy as jnp

NEG_INF = -1e30

_BACKENDS = ("xla", "pallas")


def default_backend() -> str:
    """Backend the model decode path uses.

    Auto-selects the Pallas block-table kernel when JAX is actually running
    on a TPU (the kernel lowers to Mosaic there) and the XLA gather
    fallback everywhere else. ``REPRO_PAGED_BACKEND=xla|pallas`` overrides
    — e.g. to A/B the kernel on TPU or to exercise the Pallas interpreter
    on CPU. Note the engine's decode path reads this inside a jitted
    function, so the override is captured at FIRST COMPILATION per engine:
    set the env var before constructing the engine, not between steps.
    Tests assert kernel/fallback parity in interpret mode and
    ``chip_smoke.py`` on the chip, so the numerics agree to bf16 rounding
    either way.
    """
    env = os.environ.get("REPRO_PAGED_BACKEND", "").strip().lower()
    if env:
        if env not in _BACKENDS:
            raise ValueError(
                f"REPRO_PAGED_BACKEND={env!r}: choose from {_BACKENDS}")
        return env
    return "pallas" if jax.default_backend() == "tpu" else "xla"


def _group(q: jax.Array, n_kv: int) -> jax.Array:
    """q [B, nh, d] -> [B, G, R, d] grouped per KV head."""
    b, nh, d = q.shape
    return q.reshape(b, n_kv, nh // n_kv, d)


def _gather_hot(k_pages, v_pages, phys, logical, kv_len, quant=None):
    """Pull the hot pages into [B, S_hot, nkv, d] rows + validity mask.

    ``phys`` entries < 0 are padded slots (gather is clipped to page 0, the
    scratch page, and masked out via ``logical``).

    ``quant`` (optional) is the int8 cold-tier read path: a dict with the
    tier slabs ``kq``/``vq`` [P, page, nkv, d] int8, per-page scales
    ``k_scale``/``v_scale`` [P] f32, and ``qmask`` [B, W] bool marking
    which gathered slots hold quantized content. Marked slots are replaced
    by their dequantized int8 rows (``kvcache.quant`` round-trip) — fp
    slots read the fp slab bit-exactly, so an all-False qmask is identical
    to the dense path.
    """
    page = k_pages.shape[1]
    b, w = phys.shape
    safe = jnp.maximum(phys, 0)
    kg = jnp.take(k_pages, safe, axis=0)          # [B, W, page, nkv, d]
    vg = jnp.take(v_pages, safe, axis=0)
    if quant is not None:
        qm = quant["qmask"][:, :, None, None, None]
        ks = jnp.take(quant["k_scale"], safe, axis=0)[:, :, None, None, None]
        vs = jnp.take(quant["v_scale"], safe, axis=0)[:, :, None, None, None]
        kq = jnp.take(quant["kq"], safe, axis=0).astype(jnp.float32)
        vq = jnp.take(quant["vq"], safe, axis=0).astype(jnp.float32)
        kg = jnp.where(qm, (kq * ks).astype(kg.dtype), kg)
        vg = jnp.where(qm, (vq * vs).astype(vg.dtype), vg)
    s_hot = w * page
    kg = kg.reshape(b, s_hot, *k_pages.shape[2:])
    vg = vg.reshape(b, s_hot, *v_pages.shape[2:])
    row_pos = (logical[:, :, None] * page
               + jnp.arange(page)[None, None, :]).reshape(b, s_hot)
    valid = (logical[:, :, None] >= 0).repeat(page, axis=2).reshape(b, s_hot)
    valid = valid & (row_pos < kv_len[:, None])
    return kg, vg, valid


def paged_gather_decode(q: jax.Array, k_pages: jax.Array, v_pages: jax.Array,
                        phys: jax.Array, logical: jax.Array,
                        kv_len: jax.Array, *, n_kv: int,
                        scale: Optional[float] = None,
                        quant=None) -> jax.Array:
    """XLA paged decode. q [B,nh,d]; k/v pages [P,page,nkv,d];
    phys/logical [B,W]; kv_len [B] -> [B,nh,d].

    ``phys`` entries < 0 are padded slots (gather is clipped to page 0, the
    scratch page, and masked out via ``logical``). ``quant`` enables the
    int8 cold-tier read path (see ``_gather_hot``).
    """
    b, nh, d = q.shape
    scale = scale or (1.0 / math.sqrt(d))
    kg, vg, valid = _gather_hot(k_pages, v_pages, phys, logical, kv_len,
                                quant)

    # Grouped-GQA: the gathered pages stay at n_kv width, never repeated.
    qg = _group(q, n_kv)                           # [B, G, R, d]
    kc = jnp.moveaxis(kg, 1, 2)                    # [B, G, S_hot, d]
    vc = jnp.moveaxis(vg, 1, 2)
    sc = jnp.einsum("bgrd,bgsd->bgrs", qg, kc).astype(jnp.float32) * scale
    sc = jnp.where(valid[:, None, None, :], sc, NEG_INF)
    m = sc.max(axis=-1, keepdims=True)
    p = jnp.exp(sc - m)
    p = jnp.where(sc <= NEG_INF / 2, 0.0, p)
    l = jnp.maximum(p.sum(axis=-1, keepdims=True), 1e-30)
    o = jnp.einsum("bgrs,bgsd->bgrd", (p / l).astype(q.dtype), vc)
    return o.reshape(b, nh, d)


def paged_gather_decode_stats(q: jax.Array, k_pages: jax.Array,
                              v_pages: jax.Array, phys: jax.Array,
                              logical: jax.Array, kv_len: jax.Array, *,
                              n_kv: int, scale: Optional[float] = None,
                              quant=None
                              ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Unnormalized partial-softmax state of a paged decode step.

    Same contract as ``paged_gather_decode`` but returns the flash-style
    ``(m, l, o)`` triple — m/l [B,G,R] f32, o [B,G,R,d] f32 — instead of the
    normalized output, so a sequence sharded across several page pools can
    compute one partial per shard and merge them (DRAttention's m_i/l_i
    update, ``core.dr_attention``). A shard holding no valid page for a
    sequence yields m = NEG_INF / l = 0 / o = 0, the neutral element of the
    merge.
    """
    b, nh, d = q.shape
    scale = scale or (1.0 / math.sqrt(d))
    kg, vg, valid = _gather_hot(k_pages, v_pages, phys, logical, kv_len,
                                quant)
    qg = _group(q, n_kv)
    kc = jnp.moveaxis(kg, 1, 2)
    vc = jnp.moveaxis(vg, 1, 2)
    sc = jnp.einsum("bgrd,bgsd->bgrs", qg, kc).astype(jnp.float32) * scale
    sc = jnp.where(valid[:, None, None, :], sc, NEG_INF)
    m = sc.max(axis=-1)
    p = jnp.exp(sc - m[..., None])
    p = jnp.where(sc <= NEG_INF / 2, 0.0, p)
    l = p.sum(axis=-1)
    o = jnp.einsum("bgrs,bgsd->bgrd", p, vc.astype(jnp.float32))
    return m, l, o


def page_attention_mass(q: jax.Array, k_pages: jax.Array, phys: jax.Array,
                        logical: jax.Array, kv_len: jax.Array, *, n_kv: int,
                        scale: Optional[float] = None,
                        axis: Optional[str] = None) -> jax.Array:
    """Exact per-page attention mass of one decode query — the audit probe.

    Same gather contract as ``paged_gather_decode`` (q [B,nh,d], pool slab
    [P,page,nkv,d], phys/logical [B,W], kv_len [B]) but instead of the
    attention output it returns [B, W] f32: the softmax probability mass
    each gathered page receives, averaged over heads. Feed it the FULL
    resident page set and the masses of one batch row sum to 1, so summing
    over any candidate hot subset yields that subset's attention-mass
    recall (obs.audit) — the metric LAPA/SOFA score predictors by.

    ``axis`` switches on the sequence-sharded form: call inside shard_map
    with each shard's local pages and the softmax normalizes GLOBALLY via
    pmax/psum (DRAttention's merge), so the per-shard [B, W_local] masses
    still sum to 1 across the whole mesh. Shards with no resident pages
    return zeros. V is never gathered — the probe needs scores only.
    """
    b, nh, d = q.shape
    page = k_pages.shape[1]
    w = phys.shape[1]
    scale = scale or (1.0 / math.sqrt(d))
    safe = jnp.maximum(phys, 0)
    kg = jnp.take(k_pages, safe, axis=0).reshape(b, w * page,
                                                 *k_pages.shape[2:])
    row_pos = (logical[:, :, None] * page
               + jnp.arange(page)[None, None, :]).reshape(b, w * page)
    valid = (logical[:, :, None] >= 0).repeat(page, axis=2)
    valid = valid.reshape(b, w * page) & (row_pos < kv_len[:, None])
    qg = _group(q, n_kv)                           # [B, G, R, d]
    kc = jnp.moveaxis(kg, 1, 2)                    # [B, G, S, d]
    sc = jnp.einsum("bgrd,bgsd->bgrs", qg, kc).astype(jnp.float32) * scale
    sc = jnp.where(valid[:, None, None, :], sc, NEG_INF)
    m = sc.max(axis=-1)                            # [B, G, R]
    if axis is not None:
        m = jax.lax.pmax(m, axis)
    p = jnp.exp(sc - m[..., None])
    p = jnp.where(sc <= NEG_INF / 2, 0.0, p)
    l = p.sum(axis=-1)
    if axis is not None:
        l = jax.lax.psum(l, axis)
    probs = p / jnp.maximum(l, 1e-30)[..., None]   # [B, G, R, S]
    mass = probs.mean(axis=(1, 2))                 # head-averaged [B, S]
    return mass.reshape(b, w, page).sum(axis=-1)   # [B, W]


def paged_decode(q: jax.Array, k_pages: jax.Array, v_pages: jax.Array,
                 phys: jax.Array, logical: jax.Array, kv_len: jax.Array, *,
                 n_kv: int, scale: Optional[float] = None,
                 backend: Optional[str] = None,
                 quant=None) -> jax.Array:
    """Backend dispatch. ``backend``: 'xla' (gather fallback) or 'pallas'
    (block-table kernel); None resolves via ``default_backend()`` —
    pallas on TPU, xla elsewhere, ``REPRO_PAGED_BACKEND`` overriding. The
    kernel compiles to Mosaic on a TPU and runs in the Pallas interpreter
    anywhere else (``repro.kernels.resolve_interpret``). ``quant`` (the
    int8 cold-tier inputs, see ``_gather_hot``) is served by the XLA
    gather path — the Pallas kernel has no dequant lane yet, so a quant
    request falls back to XLA regardless of ``backend``, with a warning
    at trace time."""
    if backend is None:
        backend = default_backend()
    if backend == "pallas" and quant is not None:
        warnings.warn("paged decode: the int8 cold tier has no Pallas "
                      "lane; this step runs the XLA gather instead",
                      stacklevel=2)
    if backend == "xla" or quant is not None:
        return paged_gather_decode(q, k_pages, v_pages, phys, logical,
                                   kv_len, n_kv=n_kv, scale=scale,
                                   quant=quant)
    if backend != "pallas":
        raise ValueError(f"unknown paged-attention backend {backend!r}")
    from repro.kernels import paged as kpaged
    b, nh, d = q.shape
    scale = scale or (1.0 / math.sqrt(d))
    qg = _group(q, n_kv)
    o = kpaged.paged_decode_attention(qg, k_pages, v_pages,
                                      jnp.maximum(phys, 0),
                                      logical, kv_len, scale=scale)
    return o.reshape(b, nh, d).astype(q.dtype)
