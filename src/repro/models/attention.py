"""Multi-head attention layer: GQA + RoPE + {dense | STAR-sparse} + KV cache.

Modes:
  * prefill / train — full-sequence attention; dense (chunked masked softmax)
    or the STAR pipeline (DLZS -> SADS -> SU-FA block-sparse) when a
    ``STARConfig`` is supplied.
  * decode — one new token against the cache; dense row attention or
    element-granular ``star_decode`` reading the int8 LZ prediction cache.

The layer is mesh-agnostic: logical sharding constraints (`shd`) become
no-ops outside an ``axis_rules`` context.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core import dlzs
from repro.core.sads import NEG_INF
from repro.core.star_attention import (STARConfig, star_attention_batched,
                                       star_decode)
from repro.models import common
from repro.shardlib import shd


@dataclasses.dataclass(frozen=True)
class AttentionCfg:
    d_model: int
    n_heads: int
    n_kv: int
    head_dim: int
    rope_fraction: float = 1.0   # 0 = none, 0.5 = ChatGLM 2d-RoPE, 1 = full
    rope_theta: float = 1e4
    qkv_bias: bool = False
    qk_norm: bool = False        # RMSNorm over all heads of q and of k
    causal: bool = True
    q_chunk: int = 1024          # query tile for chunked dense softmax
    star: Optional[STARConfig] = None   # sparse mode (None = dense)
    chunk_sparse: bool = False   # DLZS page selection over gathered past
    #                              pages in later prefill chunks (needs star)
    lz_cache: bool = True        # keep int8 LZ codes of K in the KV cache
    dtype: jnp.dtype = jnp.bfloat16


def init(key, cfg: AttentionCfg):
    ks = jax.random.split(key, 4)
    h, nh, nkv, dh = cfg.d_model, cfg.n_heads, cfg.n_kv, cfg.head_dim
    p = {
        "wq": common.truncated_normal_init(ks[0], (h, nh * dh), 1.0,
                                           cfg.dtype).reshape(h, nh, dh),
        "wk": common.truncated_normal_init(ks[1], (h, nkv * dh), 1.0,
                                           cfg.dtype).reshape(h, nkv, dh),
        "wv": common.truncated_normal_init(ks[2], (h, nkv * dh), 1.0,
                                           cfg.dtype).reshape(h, nkv, dh),
        "wo": common.truncated_normal_init(ks[3], (nh * dh, h), 1.0,
                                           cfg.dtype).reshape(nh, dh, h),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((nh, dh), cfg.dtype)
        p["bk"] = jnp.zeros((nkv, dh), cfg.dtype)
        p["bv"] = jnp.zeros((nkv, dh), cfg.dtype)
    if cfg.qk_norm:
        p["q_norm"] = common.rmsnorm_init(nh * dh)
        p["k_norm"] = common.rmsnorm_init(nkv * dh)
    return p


def axes(cfg: AttentionCfg):
    a = {
        "wq": ("embed_w", "heads", "head_dim"),
        "wk": ("embed_w", "kv_heads", "head_dim"),
        "wv": ("embed_w", "kv_heads", "head_dim"),
        "wo": ("heads", "head_dim", "embed_w"),
    }
    if cfg.qkv_bias:
        a["bq"] = ("heads", "head_dim")
        a["bk"] = ("kv_heads", "head_dim")
        a["bv"] = ("kv_heads", "head_dim")
    if cfg.qk_norm:
        a["q_norm"] = {"scale": (None,)}
        a["k_norm"] = {"scale": (None,)}
    return a


def _norm_heads(params, x):
    """RMSNorm of [..., n, dh] over all n * dh features at once."""
    return common.rmsnorm_apply(
        params, x.reshape(*x.shape[:-2], -1)).reshape(x.shape)


def _project_qkv(params, cfg: AttentionCfg, x, positions):
    """x [B,S,H] -> q [B,S,nh,dh], k/v [B,S,nkv,dh] with RoPE applied
    (after the QK-norm, when the model has one). Every attention path
    projects here, so the K it caches is the normed and rotated one."""
    q = jnp.einsum("bsh,hnd->bsnd", x, params["wq"])
    k = jnp.einsum("bsh,hnd->bsnd", x, params["wk"])
    v = jnp.einsum("bsh,hnd->bsnd", x, params["wv"])
    if cfg.qkv_bias:
        q = q + params["bq"]
        k = k + params["bk"]
        v = v + params["bv"]
    if cfg.qk_norm:
        q = _norm_heads(params["q_norm"], q)
        k = _norm_heads(params["k_norm"], k)
    if cfg.rope_fraction > 0:
        q = common.apply_rope(q, positions, theta=cfg.rope_theta,
                              rotary_fraction=cfg.rope_fraction)
        k = common.apply_rope(k, positions, theta=cfg.rope_theta,
                              rotary_fraction=cfg.rope_fraction)
    q = shd(q, "batch", "seq", "heads", "head_dim")
    k = shd(k, "batch", "seq", "kv_heads", "head_dim")
    v = shd(v, "batch", "seq", "kv_heads", "head_dim")
    return q, k, v


def _repeat_kv(kv, n_rep: int):
    """[B,S,nkv,dh] -> [B,S,nkv*n_rep,dh] (GQA group expansion)."""
    if n_rep == 1:
        return kv
    return jnp.repeat(kv, n_rep, axis=2)


def _dense_chunked(q, k, v, *, causal: bool, q_chunk: int, scale: float,
                   kv_lengths=None):
    """Chunked masked softmax: q [B,T,n,d], k/v [B,S,n,d] -> [B,T,n,d].

    Scans over query chunks so the score matrix is [B,n,chunk,S], never
    [B,n,T,S]. (Causal masking is applied; the masked upper-triangle matmul
    work is accepted — see DESIGN.md §7 and the §Perf remat/causal notes.)
    """
    b, t, n, d = q.shape
    s = k.shape[1]
    chunk = min(q_chunk, t)
    if t % chunk:
        chunk = t  # fall back to a single chunk for odd sizes
    n_chunks = t // chunk
    qs = jnp.moveaxis(q.reshape(b, n_chunks, chunk, n, d), 1, 0)
    kT = jnp.moveaxis(k, 1, 2)  # [B,n,S,d]
    vT = jnp.moveaxis(v, 1, 2)

    kv_pos = jnp.arange(s)

    def step(_, inp):
        qc, off = inp                                  # [B,chunk,n,d], scalar
        qc = jnp.moveaxis(qc, 1, 2)                    # [B,n,chunk,d]
        sc = jnp.einsum("bntd,bnsd->bnts", qc, kT).astype(jnp.float32)
        sc = sc * scale
        if causal:
            q_pos = off + jnp.arange(chunk)
            sc = jnp.where(kv_pos[None, :] <= q_pos[:, None], sc, NEG_INF)
        if kv_lengths is not None:
            sc = jnp.where(kv_pos[None, None, None, :]
                           < kv_lengths[:, None, None, None], sc, NEG_INF)
        m = sc.max(axis=-1, keepdims=True)
        p = jnp.exp(sc - m)
        p = jnp.where(sc <= NEG_INF / 2, 0.0, p)
        l = jnp.maximum(p.sum(axis=-1, keepdims=True), 1e-30)
        o = jnp.einsum("bnts,bnsd->bntd", (p / l).astype(q.dtype), vT)
        return None, jnp.moveaxis(o, 1, 2)             # [B,chunk,n,d]

    offsets = jnp.arange(n_chunks) * chunk
    # remat each chunk: backward recomputes the [B,n,chunk,S] score tile
    # instead of keeping every chunk's scores+masks live (see §Perf log).
    _, outs = jax.lax.scan(jax.checkpoint(step), None, (qs, offsets))
    return jnp.moveaxis(outs, 0, 1).reshape(b, t, n, d)


def apply_prefill(params, cfg: AttentionCfg, x, positions, *,
                  make_cache: bool = False, cache_len: Optional[int] = None):
    """Full-sequence attention. x [B,S,H] -> (y [B,S,H], cache | None)."""
    b, s, _ = x.shape
    scale = 1.0 / math.sqrt(cfg.head_dim)
    q, k, v = _project_qkv(params, cfg, x, positions)
    n_rep = cfg.n_heads // cfg.n_kv

    if cfg.star is not None:
        # Grouped GQA: vmap STAR over (batch, kv-head, rep) — K/V are shared
        # per group, never materialized at n_heads width.
        qh = jnp.moveaxis(q, 2, 1).reshape(b, cfg.n_kv, n_rep, s,
                                           cfg.head_dim)
        kh = jnp.moveaxis(k, 2, 1)    # [B,g,S,d]
        vh = jnp.moveaxis(v, 2, 1)
        from repro.core.star_attention import star_attention_scanq
        one = lambda qv, kv, vv: star_attention_scanq(
            qv, kv, vv, cfg.star, causal=cfg.causal, scale=scale)
        f = jax.vmap(one, in_axes=(0, None, None))
        f = jax.vmap(f, in_axes=(0, 0, 0))
        f = jax.vmap(f, in_axes=(0, 0, 0))
        o = f(qh, kh, vh)             # [B,g,r,S,d]
        y = jnp.moveaxis(o.reshape(b, cfg.n_heads, s, cfg.head_dim), 1, 2)
    else:
        kf = shd(_repeat_kv(k, n_rep), "batch", "seq", "heads", "head_dim")
        vf = shd(_repeat_kv(v, n_rep), "batch", "seq", "heads", "head_dim")
        y = _dense_chunked(q, kf, vf, causal=cfg.causal, q_chunk=cfg.q_chunk,
                           scale=scale)
    y = shd(y, "batch", "seq", "heads", "head_dim")
    out = jnp.einsum("bsnd,ndh->bsh", y, params["wo"])
    out = shd(out, "batch", "act_seq", "embed")

    cache = None
    if make_cache:
        s_max = cache_len or s
        pad = s_max - s
        kc = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        vc = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        cache = {"k": shd(kc, "batch", "kv_seq", "kv_heads", "head_dim"),
                 "v": shd(vc, "batch", "kv_seq", "kv_heads", "head_dim")}
        if cfg.lz_cache:
            cache["k_lz"] = shd(dlzs.lz_pack(kc),
                                "batch", "kv_seq", "kv_heads", "head_dim")
    return out, cache


def apply_decode(params, cfg: AttentionCfg, x, cache, lengths):
    """One-token decode. x [B,1,H]; cache k/v [B,S_max,nkv,dh]; lengths [B].

    Returns (y [B,1,H], updated cache). The new token is written at position
    ``lengths`` per sequence; attention covers [0, lengths].
    """
    b = x.shape[0]
    s_max = cache["k"].shape[1]
    scale = 1.0 / math.sqrt(cfg.head_dim)
    q, k_new, v_new = _project_qkv(params, cfg, x, lengths[:, None])

    def _scatter_row(c, row):
        """Write row [B,1,n,d] into c [B,S,n,d] at per-sequence position."""
        return jax.vmap(lambda ci, ri, i: jax.lax.dynamic_update_slice(
            ci, ri.astype(ci.dtype), (i, 0, 0)))(c, row, lengths)

    new_cache = dict(cache,
                     k=_scatter_row(cache["k"], k_new),
                     v=_scatter_row(cache["v"], v_new))
    if cfg.lz_cache and "k_lz" in cache:
        new_cache["k_lz"] = _scatter_row(cache["k_lz"], dlzs.lz_pack(k_new))

    # Grouped-GQA decode: q heads are grouped per KV head and the cache is
    # NEVER repeated to n_heads — a 16x replication at 32k context that
    # would dominate decode memory (see §Perf log).
    n_rep = cfg.n_heads // cfg.n_kv
    qg = q[:, 0].reshape(b, cfg.n_kv, n_rep, cfg.head_dim)  # [B,g,r,d]
    kc = jnp.moveaxis(new_cache["k"], 1, 2)   # [B,g,S,d]
    vc = jnp.moveaxis(new_cache["v"], 1, 2)
    kv_len = lengths + 1

    if cfg.star is not None:
        if cfg.lz_cache and "k_lz" in new_cache:
            lzc = jnp.moveaxis(new_cache["k_lz"], 1, 2)
            one = lambda qv, kv, vv, lv, ln: star_decode(
                qv, kv, vv, cfg.star, length=ln, k_lz=lv, scale=scale)
            f = jax.vmap(one, in_axes=(0, None, None, None, None))  # reps
            f = jax.vmap(f, in_axes=(0, 0, 0, 0, None))             # kv grp
            f = jax.vmap(f, in_axes=(0, 0, 0, 0, 0))                # batch
            o = f(qg, kc, vc, lzc, kv_len)
        else:
            one = lambda qv, kv, vv, ln: star_decode(
                qv, kv, vv, cfg.star, length=ln, scale=scale)
            f = jax.vmap(one, in_axes=(0, None, None, None))
            f = jax.vmap(f, in_axes=(0, 0, 0, None))
            f = jax.vmap(f, in_axes=(0, 0, 0, 0))
            o = f(qg, kc, vc, kv_len)
    else:
        sc = jnp.einsum("bgrd,bgsd->bgrs", qg, kc).astype(jnp.float32)
        sc = sc * scale
        pos = jnp.arange(s_max)
        sc = jnp.where(pos[None, None, None, :]
                       < kv_len[:, None, None, None], sc, NEG_INF)
        m = sc.max(axis=-1, keepdims=True)
        p = jnp.exp(sc - m)
        p = jnp.where(sc <= NEG_INF / 2, 0.0, p)
        l = jnp.maximum(p.sum(axis=-1, keepdims=True), 1e-30)
        o = jnp.einsum("bgrs,bgsd->bgrd", (p / l).astype(x.dtype), vc)

    o = o.reshape(b, cfg.n_heads, cfg.head_dim)
    y = jnp.einsum("bnd,ndh->bh", o, params["wo"])[:, None, :]
    return shd(y, "batch", "seq", "embed"), new_cache


def apply_prefill_chunk(params, cfg: AttentionCfg, x, positions, cache,
                        past_phys, past_logical, past_len):
    """Prefill one page-aligned chunk from a nonzero cache offset.

    x [B,C,H] — the chunk's hidden states; positions [B,C] — ABSOLUTE token
    positions (RoPE is position-exact, so past K rows already in the pool
    match); cache k/v [P,page,nkv,dh] — this layer's pool slabs, read-only
    here; past_phys/past_logical [B,Wp] — block-table rows of every page
    written by earlier chunks (-1 = pad); past_len [B] — tokens already in
    the cache.

    Attention is exact: each chunk query attends to all past rows plus the
    causal prefix of its own chunk (no STAR tile selection — chunked
    prefill trades first-chunk sparsity for admission latency; see
    docs/serving.md). Returns (y, chunk_cache) where chunk_cache holds the
    chunk's K/V (+ int8 LZ codes) in prefill layout [B,C,nkv,dh] — the
    caller scatters it into pool pages.
    """
    b, c, _ = x.shape
    scale = 1.0 / math.sqrt(cfg.head_dim)
    q, k, v = _project_qkv(params, cfg, x, positions)
    page = cache["k"].shape[1]

    safe = jnp.maximum(past_phys, 0)
    kg = jnp.take(cache["k"], safe, axis=0)        # [B, Wp, page, nkv, d]
    vg = jnp.take(cache["v"], safe, axis=0)
    wp = past_phys.shape[1]
    sp = wp * page
    kg = kg.reshape(b, sp, cfg.n_kv, cfg.head_dim).astype(q.dtype)
    vg = vg.reshape(b, sp, cfg.n_kv, cfg.head_dim).astype(q.dtype)

    past_pos = (past_logical[:, :, None] * page
                + jnp.arange(page)[None, None, :]).reshape(b, sp)
    past_ok = (past_logical[:, :, None] >= 0).repeat(page, axis=2)
    past_ok = past_ok.reshape(b, sp) & (past_pos < past_len[:, None])

    k_all = jnp.concatenate([kg, k], axis=1)        # [B, Sp+C, nkv, d]
    v_all = jnp.concatenate([vg, v], axis=1)
    kv_pos = jnp.concatenate([past_pos, positions], axis=1)
    kv_ok = jnp.concatenate(
        [past_ok, jnp.ones((b, c), bool)], axis=1)

    # Grouped-GQA masked softmax in one tile: C is a handful of pages, so
    # the [B,g,r,C,Sp+C] score block stays small; junk rows (chunk padding,
    # page tails past past_len) are masked and can only feed junk queries.
    n_rep = cfg.n_heads // cfg.n_kv
    qg = q.reshape(b, c, cfg.n_kv, n_rep, cfg.head_dim)
    sc = jnp.einsum("btgrd,bsgd->bgrts", qg, k_all).astype(jnp.float32)
    sc = sc * scale
    mask = kv_ok[:, None, None, None, :] & \
        (kv_pos[:, None, None, None, :] <= positions[:, None, None, :, None])

    if cfg.star is not None and cfg.chunk_sparse and wp > 0:
        # STAR inside later chunks: DLZS-predict the chunk's scores against
        # the gathered PAST pages (streaming the int8 LZ slab when present)
        # and drop whole pages outside the SADS sphere — a page whose best
        # predicted score sits more than ``radius`` below the per-sequence
        # max contributes < e^-radius relative softmax mass. The chunk's
        # own causal block always stays dense, so the approximation touches
        # only the long-context tail.
        if "k_lz" in cache:
            khat = dlzs.lz_unpack(jnp.take(cache["k_lz"], safe, axis=0),
                                  q.dtype)
            khat = khat.reshape(b, sp, cfg.n_kv, cfg.head_dim)
        else:
            khat = dlzs.pow2_quantize(kg)
        s_hat = jnp.einsum("btgrd,bsgd->bgrts", qg, khat
                           ).astype(jnp.float32) * scale
        s_hat = jnp.where(mask[..., :sp], s_hat, NEG_INF)
        page_max = s_hat.reshape(b, cfg.n_kv, n_rep, c, wp, page
                                 ).max(axis=(1, 2, 3, 5))        # [B, Wp]
        row_max = page_max.max(axis=-1, keepdims=True)
        keep = page_max >= row_max - cfg.star.radius             # sphere
        keep_rows = keep[:, :, None].repeat(page, axis=2).reshape(b, sp)
        keep_all = jnp.concatenate(
            [keep_rows, jnp.ones((b, c), bool)], axis=1)
        mask = mask & keep_all[:, None, None, None, :]

    sc = jnp.where(mask, sc, NEG_INF)
    m = sc.max(axis=-1, keepdims=True)
    p = jnp.exp(sc - m)
    p = jnp.where(sc <= NEG_INF / 2, 0.0, p)
    l = jnp.maximum(p.sum(axis=-1, keepdims=True), 1e-30)
    o = jnp.einsum("bgrts,bsgd->btgrd", (p / l).astype(q.dtype), v_all)
    y = o.reshape(b, c, cfg.n_heads, cfg.head_dim)
    out = jnp.einsum("bsnd,ndh->bsh", y, params["wo"])
    out = shd(out, "batch", "act_seq", "embed")

    chunk_cache = {"k": shd(k, "batch", "kv_seq", "kv_heads", "head_dim"),
                   "v": shd(v, "batch", "kv_seq", "kv_heads", "head_dim")}
    if cfg.lz_cache:
        chunk_cache["k_lz"] = shd(dlzs.lz_pack(k),
                                  "batch", "kv_seq", "kv_heads", "head_dim")
    return out, chunk_cache


def _batch_past_rows(cfg: AttentionCfg, cache, past_phys, past_lane,
                     past_logical, past_len, dtype):
    """Flatten the shared past-page ARENA into one row buffer.

    The arena is one flat pool of ``Wp`` past-page slots shared by every
    lane in the batch — each slot carries its owner lane id — so the KV
    axis scales with the TOTAL past actually packed this dispatch, not
    lanes x max-window. past_phys/past_lane/past_logical [Wp] (-1 pad);
    past_len [S] per lane. Returns (k [1, Wp*page, nkv, d], v likewise,
    seg [Wp*page], pos [Wp*page], ok [Wp*page]); queries match rows by
    lane id, so one masked softmax covers every lane's own past.
    """
    page = cache["k"].shape[1]
    wp = past_phys.shape[0]
    safe = jnp.maximum(past_phys, 0)
    kg = jnp.take(cache["k"], safe, axis=0)
    vg = jnp.take(cache["v"], safe, axis=0)
    sp = wp * page
    kg = kg.reshape(1, sp, cfg.n_kv, cfg.head_dim).astype(dtype)
    vg = vg.reshape(1, sp, cfg.n_kv, cfg.head_dim).astype(dtype)
    pos = (past_logical[:, None] * page
           + jnp.arange(page)[None, :]).reshape(sp)
    seg = jnp.repeat(past_lane, page)
    ok = (past_logical[:, None] >= 0).repeat(page, axis=1).reshape(sp)
    ok = ok & (pos < past_len[jnp.maximum(seg, 0)])
    return kg, vg, seg, pos, ok


def apply_prefill_chunk_batch(params, cfg: AttentionCfg, x, positions,
                              cache, pack_state):
    """Prefill MANY sequences' chunks in one flat varlen dispatch.

    x [1, B_tok, H] — every packed chunk's hidden states back to back
    (padding between/after chunks is allowed); positions [1, B_tok] —
    ABSOLUTE token positions (RoPE-exact against past pool rows);
    cache k/v [P, page, nkv, dh] — pool slabs, read-only here.
    ``pack_state``:
      seg_ids [B_tok] — lane (batch-slot) index per flat token, -1 pad,
      past_phys/past_lane/past_logical [Wp] — the shared past ARENA:
        block-table rows of pages earlier chunks wrote, each slot tagged
        with its owner lane (-1 = pad),
      past_len [S] — tokens already cached per lane.

    The mask composes three terms: lane match (a query only sees rows of
    its own sequence), validity (padding rows/tokens see nothing), and
    causality over absolute positions. Per-lane math is identical to
    ``apply_prefill_chunk`` — the batched form just runs every lane's
    gather+softmax inside one compiled program, which is what removes
    the per-sequence dispatch overhead chunked prefill used to pay.
    Returns (y [1, B_tok, H], chunk_cache [1, B_tok, nkv, dh] + LZ) —
    the caller scatters the flat rows onto each lane's pool pages.
    """
    b, t, _ = x.shape
    scale = 1.0 / math.sqrt(cfg.head_dim)
    q, k, v = _project_qkv(params, cfg, x, positions)
    seg_q = pack_state["seg_ids"]
    past_phys = pack_state["past_phys"]
    past_lane = pack_state["past_lane"]
    wp = past_phys.shape[0]
    page = cache["k"].shape[1]
    sp = wp * page
    s_lanes = pack_state["past_len"].shape[0]

    kg, vg, seg_p, pos_p, ok_p = _batch_past_rows(
        cfg, cache, past_phys, past_lane, pack_state["past_logical"],
        pack_state["past_len"], q.dtype)

    k_all = jnp.concatenate([kg, k], axis=1)      # [1, Sp+B_tok, nkv, d]
    v_all = jnp.concatenate([vg, v], axis=1)
    kv_seg = jnp.concatenate([seg_p, seg_q])
    kv_pos = jnp.concatenate([pos_p, positions[0]])
    kv_ok = jnp.concatenate([ok_p, seg_q >= 0])

    n_rep = cfg.n_heads // cfg.n_kv
    qg = q.reshape(b, t, cfg.n_kv, n_rep, cfg.head_dim)
    sc = jnp.einsum("btgrd,bsgd->bgrts", qg, k_all).astype(jnp.float32)
    sc = sc * scale
    mask = (kv_ok & (kv_seg[None, :] == seg_q[:, None])
            )[None, None, None] \
        & (kv_pos[None, None, None, None, :]
           <= positions[:, None, None, :, None])

    if cfg.star is not None and cfg.chunk_sparse and wp > 0:
        # Same DLZS sphere as apply_prefill_chunk, per lane: predicted
        # scores of OTHER lanes' queries against an arena slot are
        # already NEG_INF under the lane mask, so the per-slot max over
        # all flat queries is exactly the owner lane's max; the sphere
        # radius is then applied against a segmented per-lane row max.
        if "k_lz" in cache:
            khat = dlzs.lz_unpack(
                jnp.take(cache["k_lz"], jnp.maximum(past_phys, 0),
                         axis=0), q.dtype)
            khat = khat.reshape(1, sp, cfg.n_kv, cfg.head_dim)
        else:
            khat = dlzs.pow2_quantize(kg)
        s_hat = jnp.einsum("btgrd,bsgd->bgrts", qg, khat
                           ).astype(jnp.float32) * scale
        s_hat = jnp.where(mask[..., :sp], s_hat, NEG_INF)
        page_max = s_hat.reshape(
            b, cfg.n_kv, n_rep, t, wp, page
        ).max(axis=(0, 1, 2, 3, 5))                    # [Wp]
        lane_max = jnp.where(
            past_lane[:, None] == jnp.arange(s_lanes)[None, :],
            page_max[:, None], NEG_INF).max(axis=0)    # [S]
        keep = page_max >= \
            lane_max[jnp.maximum(past_lane, 0)] - cfg.star.radius
        keep_rows = keep[:, None].repeat(page, axis=1).reshape(sp)
        keep_all = jnp.concatenate([keep_rows, jnp.ones((t,), bool)])
        mask = mask & keep_all[None, None, None, None, :]

    sc = jnp.where(mask, sc, NEG_INF)
    m = sc.max(axis=-1, keepdims=True)
    p = jnp.exp(sc - m)
    p = jnp.where(sc <= NEG_INF / 2, 0.0, p)
    l = jnp.maximum(p.sum(axis=-1, keepdims=True), 1e-30)
    o = jnp.einsum("bgrts,bsgd->btgrd", (p / l).astype(q.dtype), v_all)
    y = o.reshape(b, t, cfg.n_heads, cfg.head_dim)
    out = jnp.einsum("bsnd,ndh->bsh", y, params["wo"])
    out = shd(out, "batch", "act_seq", "embed")

    chunk_cache = {"k": shd(k, "batch", "kv_seq", "kv_heads", "head_dim"),
                   "v": shd(v, "batch", "kv_seq", "kv_heads", "head_dim")}
    if cfg.lz_cache:
        chunk_cache["k_lz"] = shd(dlzs.lz_pack(k),
                                  "batch", "kv_seq", "kv_heads", "head_dim")
    return out, chunk_cache


def apply_decode_paged(params, cfg: AttentionCfg, x, cache, lengths,
                       page_state):
    """One-token decode against a paged pool. x [B,1,H];
    cache k/v [P,page,nkv,dh] (this layer's slab); lengths [B].

    ``page_state`` (shared across layers):
      phys/logical [B,W] — block-table rows of the hot pages (-1 = pad),
      write_page/write_off [B] — pool coordinates of the new token's row.

    The new K/V row is scattered into the pool at its page coordinates, then
    attention gathers only the W hot pages (kvcache.paged_attention) — the
    DLZS retention policy decides W's contents, the engine guarantees the
    write target is among them.
    """
    b = x.shape[0]
    scale = 1.0 / math.sqrt(cfg.head_dim)
    q, k_new, v_new = _project_qkv(params, cfg, x, lengths[:, None])

    wp, woff = page_state["write_page"], page_state["write_off"]
    new_cache = dict(
        cache,
        k=cache["k"].at[wp, woff].set(k_new[:, 0].astype(cache["k"].dtype)),
        v=cache["v"].at[wp, woff].set(v_new[:, 0].astype(cache["v"].dtype)))
    if cfg.lz_cache and "k_lz" in cache:
        new_cache["k_lz"] = cache["k_lz"].at[wp, woff].set(
            dlzs.lz_pack(k_new)[:, 0])

    from repro.kvcache import paged_attention as kv_paged
    quant = None
    if "kq" in cache and "qmask" in page_state:
        # int8 cold-tier read path: dequantize-on-gather for the hot
        # slots the backend marked as quantized (kvcache.quant)
        quant = {"kq": new_cache["kq"], "vq": new_cache["vq"],
                 "k_scale": new_cache["k_scale"],
                 "v_scale": new_cache["v_scale"],
                 "qmask": page_state["qmask"]}
    if "audit" in page_state:
        # Exact-reference probe (obs.audit): per-page softmax mass of this
        # query over the pages in page_state, read from the fp slab (rows
        # stay bit-exact there regardless of cold-tier state). Rides the
        # cache tree out of the layer scan.
        new_cache["audit_mass"] = kv_paged.page_attention_mass(
            q[:, 0], new_cache["k"], page_state["phys"],
            page_state["logical"], lengths + 1, n_kv=cfg.n_kv, scale=scale)
    o = kv_paged.paged_decode(
        q[:, 0], new_cache["k"], new_cache["v"], page_state["phys"],
        page_state["logical"], lengths + 1, n_kv=cfg.n_kv, scale=scale,
        quant=quant)
    y = jnp.einsum("bnd,ndh->bh",
                   o.reshape(b, cfg.n_heads, cfg.head_dim),
                   params["wo"])[:, None, :]
    return shd(y, "batch", "seq", "embed"), new_cache


# ---------------------------------------------------------------------------
# Spatial (sequence-sharded) attention: partial (m, l, o) per shard, merged
# over a mesh axis. Runs inside shard_map — repro.spatial drives these.
# ---------------------------------------------------------------------------

def _merge_two_stats(m_a, l_a, o_a, m_b, l_b, o_b):
    """Pairwise flash-state merge, broadcast over any leading dims
    (the [T]-shaped version lives in core.dr_attention)."""
    m = jnp.maximum(m_a, m_b)
    ea = jnp.where(m_a <= NEG_INF / 2, 0.0, jnp.exp(m_a - m))
    eb = jnp.where(m_b <= NEG_INF / 2, 0.0, jnp.exp(m_b - m))
    return m, l_a * ea + l_b * eb, o_a * ea[..., None] + o_b * eb[..., None]


def _psum_merge_stats(m, l, o, axis: str):
    """Merge per-shard partial softmax states across mesh axis ``axis``.

    DRAttention's (m_i, l_i) update executed as pmax + two psums — the
    tree form of the ring reduction, optimal for the tiny decode state.
    Empty shards (m == NEG_INF) contribute nothing.
    """
    m_g = jax.lax.pmax(m, axis)
    w = jnp.where(m <= NEG_INF / 2, 0.0, jnp.exp(m - m_g))
    l_g = jax.lax.psum(l * w, axis)
    o_g = jax.lax.psum(o * w[..., None], axis)
    return m_g, l_g, o_g


def apply_decode_spatial(params, cfg: AttentionCfg, x, cache, lengths,
                         page_state, axis: str):
    """One-token decode against a sequence-sharded paged pool (one shard's
    view; call inside shard_map over mesh axis ``axis``).

    The query is replicated (every shard computes the same projections —
    the broadcast-query decode of Star Attention); ``cache`` k/v are THIS
    shard's slabs [P_local, page, nkv, dh]. ``page_state`` carries the
    shard-local block-table rows (``logical`` holds GLOBAL page indices so
    positions stay exact) and the write coordinates — SCRATCH on every
    shard except the new token's owner. Each shard produces a partial
    (m, l, o) over its local hot pages; the states merge across the axis
    (exact — DRAttention's combination), so the result equals one-pool
    paged decode whenever the hot sets cover every page.
    """
    b = x.shape[0]
    scale = 1.0 / math.sqrt(cfg.head_dim)
    q, k_new, v_new = _project_qkv(params, cfg, x, lengths[:, None])

    wp, woff = page_state["write_page"], page_state["write_off"]
    new_cache = dict(
        cache,
        k=cache["k"].at[wp, woff].set(k_new[:, 0].astype(cache["k"].dtype)),
        v=cache["v"].at[wp, woff].set(v_new[:, 0].astype(cache["v"].dtype)))
    if cfg.lz_cache and "k_lz" in cache:
        new_cache["k_lz"] = cache["k_lz"].at[wp, woff].set(
            dlzs.lz_pack(k_new)[:, 0])

    from repro.kvcache import paged_attention as kv_paged
    quant = None
    if "kq" in cache and "qmask" in page_state:
        quant = {"kq": new_cache["kq"], "vq": new_cache["vq"],
                 "k_scale": new_cache["k_scale"],
                 "v_scale": new_cache["v_scale"],
                 "qmask": page_state["qmask"]}

    if "audit" in page_state:
        # Exact-reference probe, sequence-sharded form: the pmax/psum
        # inside page_attention_mass normalize globally, so each shard's
        # [B, W_local] masses sum to 1 across the mesh. Unconditional —
        # collectives cannot sit under the lax.cond below.
        new_cache["audit_mass"] = kv_paged.page_attention_mass(
            q[:, 0], new_cache["k"], page_state["phys"],
            page_state["logical"], lengths + 1, n_kv=cfg.n_kv, scale=scale,
            axis=axis)

    # DLZS-guided communication sparsity: a shard whose hot set is empty
    # for EVERY sequence this step (all logical == -1 — bounded hot-width
    # selection left it nothing) contributes exactly the neutral element,
    # so skip its gather/softmax and feed the merge the neutral state
    # directly. lax.cond under shard_map is a real per-shard runtime
    # branch; the psums below still run on every shard (collectives must),
    # but the skipped shard's local attention work drops to nothing.
    g, r = cfg.n_kv, cfg.n_heads // cfg.n_kv

    def _stats(_):
        return kv_paged.paged_gather_decode_stats(
            q[:, 0], new_cache["k"], new_cache["v"], page_state["phys"],
            page_state["logical"], lengths + 1, n_kv=cfg.n_kv, scale=scale,
            quant=quant)

    def _neutral(_):
        # constants are axis-invariant; cast them so both branches vary
        # over ``axis`` like the gather's outputs
        return jax.lax.pcast(
            (jnp.full((b, g, r), NEG_INF, jnp.float32),
             jnp.zeros((b, g, r), jnp.float32),
             jnp.zeros((b, g, r, cfg.head_dim), jnp.float32)),
            (axis,), to="varying")

    m, l, o = jax.lax.cond(jnp.any(page_state["logical"] >= 0),
                           _stats, _neutral, None)
    m, l, o = _psum_merge_stats(m, l, o, axis)
    o = o / jnp.maximum(l, 1e-30)[..., None]       # [B, G, R, d]
    y = jnp.einsum("bnd,ndh->bh",
                   o.reshape(b, cfg.n_heads, cfg.head_dim).astype(x.dtype),
                   params["wo"])[:, None, :]
    return shd(y, "batch", "seq", "embed"), new_cache


def apply_prefill_chunk_spatial(params, cfg: AttentionCfg, x, positions,
                                cache, page_state, axis: str):
    """Prefill one page-aligned chunk of a sequence-sharded prompt (one
    shard's view; call inside shard_map over mesh axis ``axis``).

    The chunk's hidden states are replicated; each shard computes a
    partial (m, l, o) of the chunk queries against ITS local past pages,
    the partials merge across the axis (pmax/psum — the T>1 form of the
    decode merge), and the chunk's causal self-attention block is added
    locally (identical on every shard, merged exactly once). The chunk's
    fresh K/V rows scatter into the pages this shard owns
    (``page_state["chunk_phys"]`` — SCRATCH for pages owned elsewhere), so
    the whole chunk update stays inside one SPMD dispatch.
    """
    b, c, _ = x.shape
    scale = 1.0 / math.sqrt(cfg.head_dim)
    q, k, v = _project_qkv(params, cfg, x, positions)
    page = cache["k"].shape[1]
    n_rep = cfg.n_heads // cfg.n_kv
    qg = q.reshape(b, c, cfg.n_kv, n_rep, cfg.head_dim)

    # partial stats vs this shard's past pages
    past_phys, past_logical = page_state["past_phys"], \
        page_state["past_logical"]
    safe = jnp.maximum(past_phys, 0)
    kg = jnp.take(cache["k"], safe, axis=0)        # [B, Wp, page, nkv, d]
    vg = jnp.take(cache["v"], safe, axis=0)
    wp = past_phys.shape[1]
    sp = wp * page
    kg = kg.reshape(b, sp, cfg.n_kv, cfg.head_dim).astype(q.dtype)
    vg = vg.reshape(b, sp, cfg.n_kv, cfg.head_dim).astype(q.dtype)
    past_pos = (past_logical[:, :, None] * page
                + jnp.arange(page)[None, None, :]).reshape(b, sp)
    past_ok = (past_logical[:, :, None] >= 0).repeat(page, axis=2)
    past_ok = past_ok.reshape(b, sp) \
        & (past_pos < page_state["past_len"][:, None])
    sc_p = jnp.einsum("btgrd,bsgd->bgrts", qg, kg).astype(jnp.float32)
    sc_p = sc_p * scale
    mask_p = past_ok[:, None, None, None, :] & \
        (past_pos[:, None, None, None, :]
         <= positions[:, None, None, :, None])
    sc_p = jnp.where(mask_p, sc_p, NEG_INF)
    m1 = sc_p.max(axis=-1)                          # [B, G, R, C]
    p1 = jnp.exp(sc_p - m1[..., None])
    p1 = jnp.where(sc_p <= NEG_INF / 2, 0.0, p1)
    l1 = p1.sum(axis=-1)
    o1 = jnp.einsum("bgrts,bsgd->bgrtd", p1, vg.astype(jnp.float32))
    m1, l1, o1 = _psum_merge_stats(m1, l1, o1, axis)

    # chunk's causal self-attention block (replicated compute)
    sc_c = jnp.einsum("btgrd,bsgd->bgrts", qg, k).astype(jnp.float32)
    sc_c = sc_c * scale
    mask_c = positions[:, None, None, None, :] \
        <= positions[:, None, None, :, None]
    sc_c = jnp.where(mask_c, sc_c, NEG_INF)
    m2 = sc_c.max(axis=-1)
    p2 = jnp.exp(sc_c - m2[..., None])
    p2 = jnp.where(sc_c <= NEG_INF / 2, 0.0, p2)
    l2 = p2.sum(axis=-1)
    o2 = jnp.einsum("bgrts,bsgd->bgrtd", p2, v.astype(jnp.float32))

    m, l, o = _merge_two_stats(m1, l1, o1, m2, l2, o2)
    o = o / jnp.maximum(l, 1e-30)[..., None]        # [B, G, R, C, d]
    y = jnp.moveaxis(o, 3, 1).reshape(b, c, cfg.n_heads, cfg.head_dim)
    out = jnp.einsum("bsnd,ndh->bsh", y.astype(x.dtype), params["wo"])
    out = shd(out, "batch", "act_seq", "embed")

    # scatter the chunk's K/V rows into the pages this shard owns
    chunk_phys = page_state["chunk_phys"]           # [B, C // page]
    def put(pool, rows):
        rows = rows.reshape(b, c // page, page, *rows.shape[2:])
        return pool.at[chunk_phys].set(rows.astype(pool.dtype))
    new_cache = dict(cache, k=put(cache["k"], k), v=put(cache["v"], v))
    if cfg.lz_cache and "k_lz" in cache:
        new_cache["k_lz"] = put(cache["k_lz"], dlzs.lz_pack(k))
    return out, new_cache


def apply_prefill_chunk_batch_spatial(params, cfg: AttentionCfg, x,
                                      positions, cache, page_state,
                                      axis: str):
    """Batched varlen chunk prefill, one shard's view (inside shard_map).

    The flat chunk buffer (see ``apply_prefill_chunk_batch``) is
    replicated; each shard computes a partial (m, l, o) of EVERY lane's
    chunk queries against its local slice of that lane's past pages, the
    partials merge across ``axis`` (pmax/psum — exact), and the flat
    segment-masked causal self block is added locally (identical on
    every shard, merged exactly once). Fresh K/V rows scatter into the
    pages this shard owns via ``page_state["chunk_phys"]``
    [1, B_tok // page] (SCRATCH for pages owned elsewhere) — so many
    sequences' chunks advance in ONE SPMD dispatch.
    """
    b, t, _ = x.shape
    scale = 1.0 / math.sqrt(cfg.head_dim)
    q, k, v = _project_qkv(params, cfg, x, positions)
    page = cache["k"].shape[1]
    n_rep = cfg.n_heads // cfg.n_kv
    qg = q.reshape(b, t, cfg.n_kv, n_rep, cfg.head_dim)
    seg_q = page_state["seg_ids"]

    # partial stats vs this shard's arena slice of every lane's past
    kg, vg, seg_p, pos_p, ok_p = _batch_past_rows(
        cfg, cache, page_state["past_phys"], page_state["past_lane"],
        page_state["past_logical"], page_state["past_len"], q.dtype)
    sc_p = jnp.einsum("btgrd,bsgd->bgrts", qg, kg).astype(jnp.float32)
    sc_p = sc_p * scale
    mask_p = (ok_p & (seg_p[None, :] == seg_q[:, None])
              )[None, None, None] \
        & (pos_p[None, None, None, None, :]
           <= positions[:, None, None, :, None])
    sc_p = jnp.where(mask_p, sc_p, NEG_INF)
    m1 = sc_p.max(axis=-1)                          # [1, G, R, B_tok]
    p1 = jnp.exp(sc_p - m1[..., None])
    p1 = jnp.where(sc_p <= NEG_INF / 2, 0.0, p1)
    l1 = p1.sum(axis=-1)
    o1 = jnp.einsum("bgrts,bsgd->bgrtd", p1, vg.astype(jnp.float32))
    m1, l1, o1 = _psum_merge_stats(m1, l1, o1, axis)

    # flat causal self block, lane-masked (replicated compute)
    sc_c = jnp.einsum("btgrd,bsgd->bgrts", qg, k).astype(jnp.float32)
    sc_c = sc_c * scale
    mask_c = ((seg_q >= 0) & (seg_q[None, :] == seg_q[:, None])
              )[None, None, None] \
        & (positions[:, None, None, None, :]
           <= positions[:, None, None, :, None])
    sc_c = jnp.where(mask_c, sc_c, NEG_INF)
    m2 = sc_c.max(axis=-1)
    p2 = jnp.exp(sc_c - m2[..., None])
    p2 = jnp.where(sc_c <= NEG_INF / 2, 0.0, p2)
    l2 = p2.sum(axis=-1)
    o2 = jnp.einsum("bgrts,bsgd->bgrtd", p2, v.astype(jnp.float32))

    m, l, o = _merge_two_stats(m1, l1, o1, m2, l2, o2)
    o = o / jnp.maximum(l, 1e-30)[..., None]        # [1, G, R, B_tok, d]
    y = jnp.moveaxis(o, 3, 1).reshape(b, t, cfg.n_heads, cfg.head_dim)
    out = jnp.einsum("bsnd,ndh->bsh", y.astype(x.dtype), params["wo"])
    out = shd(out, "batch", "act_seq", "embed")

    chunk_phys = page_state["chunk_phys"]           # [1, B_tok // page]
    def put(pool, rows):
        rows = rows.reshape(b, t // page, page, *rows.shape[2:])
        return pool.at[chunk_phys].set(rows.astype(pool.dtype))
    new_cache = dict(cache, k=put(cache["k"], k), v=put(cache["v"], v))
    if cfg.lz_cache and "k_lz" in cache:
        new_cache["k_lz"] = put(cache["k_lz"], dlzs.lz_pack(k))
    return out, new_cache


# ---------------------------------------------------------------------------
# Cross-attention (encoder-decoder; seamless-m4t)
# ---------------------------------------------------------------------------

def cross_init(key, cfg: AttentionCfg):
    return init(key, cfg)


def cross_axes(cfg: AttentionCfg):
    return axes(cfg)


def cross_encode(params, cfg: AttentionCfg, enc_out):
    """Precompute encoder-side K/V once (the cross-attention 'cache')."""
    k = jnp.einsum("bsh,hnd->bsnd", enc_out, params["wk"])
    v = jnp.einsum("bsh,hnd->bsnd", enc_out, params["wv"])
    if cfg.qkv_bias:
        k = k + params["bk"]
        v = v + params["bv"]
    return {"k": shd(k, "batch", "kv_seq", "kv_heads", "head_dim"),
            "v": shd(v, "batch", "kv_seq", "kv_heads", "head_dim")}


def cross_apply(params, cfg: AttentionCfg, x, enc_cache):
    """Decoder cross-attention: x [B,T,H] against cached encoder K/V."""
    scale = 1.0 / math.sqrt(cfg.head_dim)
    q = jnp.einsum("bsh,hnd->bsnd", x, params["wq"])
    if cfg.qkv_bias:
        q = q + params["bq"]
    n_rep = cfg.n_heads // cfg.n_kv
    kf = _repeat_kv(enc_cache["k"], n_rep)
    vf = _repeat_kv(enc_cache["v"], n_rep)
    y = _dense_chunked(q, kf.astype(q.dtype), vf.astype(q.dtype),
                       causal=False, q_chunk=cfg.q_chunk, scale=scale)
    out = jnp.einsum("bsnd,ndh->bsh", y, params["wo"])
    return shd(out, "batch", "seq", "embed")
