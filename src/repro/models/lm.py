"""Top-level language models: pattern-based block stacks, scan-over-layers.

A model is a repeated *super-block pattern* (e.g. jamba: 7 mamba + 1 attn per
repeat, MoE on odd positions). ``jax.lax.scan`` runs over the repeats with
stacked parameters, keeping HLO size O(pattern), not O(depth) — essential for
compiling 96-layer configs on the dry-run host. Remat policy wraps the
super-block for training.

Paths: ``loss_fn`` (train), ``prefill`` (build KV/state caches + last-token
logits), ``decode_step`` (one token). Encoder-decoder models (seamless-m4t)
add an encoder stack whose output feeds per-layer cross-attention caches.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.core.star_attention import STARConfig
from repro.models import attention, common, mlp, moe, ssm, xlstm
from repro.shardlib import shd


@dataclasses.dataclass(frozen=True)
class BlockCfg:
    kind: str              # attn | mamba | mlstm | slstm
    ffn: str = "dense"     # dense | moe | none
    cross_attn: bool = False


@dataclasses.dataclass(frozen=True)
class ModelCfg:
    name: str
    d_model: int
    n_layers: int
    n_heads: int
    n_kv: int
    d_ff: int
    vocab: int
    pattern: tuple = (BlockCfg("attn", "dense"),)
    norm: str = "rmsnorm"
    mlp_act: str = "silu"
    mlp_gated: bool = True
    rope_fraction: float = 1.0
    rope_theta: float = 1e4
    qkv_bias: bool = False
    head_dim: Optional[int] = None
    qk_norm: bool = False          # RMSNorm over the whole projected q and
    #                                k before RoPE (OLMoE); the cache holds
    #                                the normed, rotated K
    moe: Optional[moe.MoECfg] = None   # router width, experts per token
    experts_held: int = 0          # experts of each MoE layer held on this
    #                                chip (0: all); serving computes their
    #                                share of the layer's output
    expert_first: int = 0          # global index of the first held expert
    mamba: Optional[ssm.MambaCfg] = None
    xlstm_heads: int = 0
    enc_layers: int = 0            # > 0 => encoder-decoder
    embeds_input: bool = False     # modality frontend stub feeds embeddings
    star: Optional[STARConfig] = None   # serving-time sparse attention
    star_train: bool = False
    star_chunk_sparse: bool = False     # DLZS page selection inside later
    #                                     prefill chunks (approximate; the
    #                                     chunk's causal block stays dense)
    causal: bool = True
    q_chunk: int = 1024
    seq_loss_chunk: int = 1024
    vocab_pad_to: int = 2048
    remat: str = "full"            # none | full | dots
    optimizer: str = "adamw"       # adamw | adafactor (giants: factored v)
    train_accum: int = 1           # gradient-accumulation microbatches
    accum_dtype: Any = jnp.bfloat16  # grad accumulation buffer dtype (bf16:
    #                                 at accum<=8 the loss is negligible and
    #                                 it halves the largest train-time buffer)
    dtype: Any = jnp.bfloat16
    rule_overrides: tuple = ()     # ((logical, mesh_axis), ...)

    @property
    def dh(self) -> int:
        return self.head_dim or self.d_model // self.n_heads

    @property
    def n_repeat(self) -> int:
        assert self.n_layers % len(self.pattern) == 0, \
            f"{self.n_layers} layers not a multiple of pattern " \
            f"{len(self.pattern)}"
        return self.n_layers // len(self.pattern)

    @property
    def vocab_padded(self) -> int:
        p = self.vocab_pad_to
        return -(-self.vocab // p) * p

    def attn_cfg(self, mode: str, causal: Optional[bool] = None
                 ) -> attention.AttentionCfg:
        use_star = self.star if (mode != "train" or self.star_train) else None
        return attention.AttentionCfg(
            d_model=self.d_model, n_heads=self.n_heads, n_kv=self.n_kv,
            head_dim=self.dh, rope_fraction=self.rope_fraction,
            rope_theta=self.rope_theta, qkv_bias=self.qkv_bias,
            qk_norm=self.qk_norm,
            causal=self.causal if causal is None else causal,
            q_chunk=self.q_chunk, star=use_star,
            chunk_sparse=self.star_chunk_sparse, dtype=self.dtype)

    def moe_cfg(self) -> moe.MoECfg:
        """The expert layer at this model's widths (``d_model``, and
        ``d_ff`` as each expert's), so a ``dataclasses.replace`` of the
        model's fields reaches it."""
        return dataclasses.replace(self.moe, d_model=self.d_model,
                                   d_ff=self.d_ff)

    @property
    def has_moe(self) -> bool:
        return any(blk.ffn == "moe" for blk in self.pattern)

    def mlp_cfg(self) -> mlp.MLPCfg:
        return mlp.MLPCfg(self.d_model, self.d_ff, self.mlp_act,
                          self.mlp_gated, self.dtype)

    def xlstm_cfg(self) -> xlstm.XLSTMCfg:
        return xlstm.XLSTMCfg(self.d_model, self.xlstm_heads,
                              dtype=self.dtype)


# ---------------------------------------------------------------------------
# Super-block (one pattern instance)
# ---------------------------------------------------------------------------

def _block_init(key, cfg: ModelCfg, blk: BlockCfg, causal: bool = True):
    ks = jax.random.split(key, 6)
    p = {"norm1": common.norm_init(cfg.norm, cfg.d_model)}
    if blk.kind == "attn":
        p["core"] = attention.init(ks[0], cfg.attn_cfg("train", causal))
    elif blk.kind == "mamba":
        p["core"] = ssm.init(ks[0], cfg.mamba)
    elif blk.kind == "mlstm":
        p["core"] = xlstm.mlstm_init(ks[0], cfg.xlstm_cfg())
    elif blk.kind == "slstm":
        p["core"] = xlstm.slstm_init(ks[0], cfg.xlstm_cfg())
    else:
        raise ValueError(blk.kind)
    if blk.cross_attn:
        p["norm_cross"] = common.norm_init(cfg.norm, cfg.d_model)
        p["cross"] = attention.cross_init(ks[1], cfg.attn_cfg("train", False))
    if blk.ffn != "none":
        p["norm2"] = common.norm_init(cfg.norm, cfg.d_model)
        if blk.ffn == "moe":
            p["ffn"] = moe.init(ks[2], cfg.moe_cfg(),
                                held=cfg.experts_held)
        else:
            p["ffn"] = mlp.init(ks[2], cfg.mlp_cfg())
    return p


def _block_axes(cfg: ModelCfg, blk: BlockCfg):
    a = {"norm1": common.norm_axes(cfg.norm)}
    if blk.kind == "attn":
        a["core"] = attention.axes(cfg.attn_cfg("train"))
    elif blk.kind == "mamba":
        a["core"] = ssm.axes(cfg.mamba)
    elif blk.kind == "mlstm":
        a["core"] = xlstm.mlstm_axes(cfg.xlstm_cfg())
    elif blk.kind == "slstm":
        a["core"] = xlstm.slstm_axes(cfg.xlstm_cfg())
    if blk.cross_attn:
        a["norm_cross"] = common.norm_axes(cfg.norm)
        a["cross"] = attention.cross_axes(cfg.attn_cfg("train"))
    if blk.ffn != "none":
        a["norm2"] = common.norm_axes(cfg.norm)
        a["ffn"] = moe.axes(cfg.moe_cfg()) if blk.ffn == "moe" \
            else mlp.axes(cfg.mlp_cfg())
    return a


def _block_apply(params, cfg: ModelCfg, blk: BlockCfg, x, positions, *,
                 mode: str, causal: bool = True, cache=None,
                 enc_cache=None, lengths=None, cache_len=None,
                 page_state=None, spatial_axis=None, valid=None,
                 layer=None):
    """Returns (y, new_cache, aux_loss, moe_hist). An MoE block trains
    through the capacity path and serves every other mode dropless, with
    ``moe_hist`` [held] its assignments per held expert from the tokens
    ``valid`` marks real (None for other blocks). Paged decode alone takes
    ``layer``, the repeat index: its ``cache`` is the pool of every layer
    (``decode_step_paged``)."""
    aux = jnp.zeros((), jnp.float32)
    hist = None
    h = common.norm_apply(cfg.norm, params["norm1"], x)
    acfg = cfg.attn_cfg(mode, causal)
    new_cache = {}
    if blk.kind == "attn":
        if mode == "prefill_chunk_batch" and spatial_axis is not None:
            y, c = attention.apply_prefill_chunk_batch_spatial(
                params["core"], acfg, h, positions, cache["attn"],
                page_state, spatial_axis)
            new_cache["attn"] = c
        elif mode == "prefill_chunk_batch":
            y, c = attention.apply_prefill_chunk_batch(
                params["core"], acfg, h, positions, cache["attn"],
                page_state)
            new_cache["attn"] = c
        elif mode == "decode" and spatial_axis is not None:
            y, new_attn = attention.apply_decode_spatial(
                params["core"], acfg, h, cache["attn"], lengths,
                page_state, spatial_axis)
            new_cache["attn"] = new_attn
        elif mode == "decode" and page_state is not None:
            y, new_attn = attention.apply_decode_paged(
                params["core"], acfg, h, cache["attn"], lengths, page_state,
                layer)
            new_cache["attn"] = new_attn
        elif mode == "decode":
            y, new_attn = attention.apply_decode(params["core"], acfg, h,
                                                 cache["attn"], lengths)
            new_cache["attn"] = new_attn
        else:
            y, c = attention.apply_prefill(
                params["core"], acfg, h, positions,
                make_cache=(mode == "prefill"), cache_len=cache_len)
            if c is not None:
                new_cache["attn"] = c
    elif blk.kind == "mamba":
        if mode == "decode":
            y, c = ssm.apply_decode(params["core"], cfg.mamba, h,
                                    cache["mamba"])
            new_cache["mamba"] = c
        else:
            y, c = ssm.apply(params["core"], cfg.mamba, h,
                             make_cache=(mode == "prefill"))
            if c is not None:
                new_cache["mamba"] = c
    elif blk.kind == "mlstm":
        xc = cfg.xlstm_cfg()
        if mode == "decode":
            y, c = xlstm.mlstm_decode(params["core"], xc, h, cache["mlstm"])
            new_cache["mlstm"] = c
        else:
            y, c = xlstm.mlstm_apply(params["core"], xc, h,
                                     make_cache=(mode == "prefill"))
            if c is not None:
                new_cache["mlstm"] = c
    elif blk.kind == "slstm":
        xc = cfg.xlstm_cfg()
        if mode == "decode":
            y, c = xlstm.slstm_decode(params["core"], xc, h, cache["slstm"])
            new_cache["slstm"] = c
        else:
            y, c = xlstm.slstm_apply(params["core"], xc, h,
                                     make_cache=(mode == "prefill"))
            if c is not None:
                new_cache["slstm"] = c
    x = x + y

    if blk.cross_attn:
        if mode == "decode":
            layer_cross = cache["cross"]        # built at prefill
            new_cache["cross"] = layer_cross
        elif enc_cache is not None:
            # build this layer's cross K/V from the encoder output
            layer_cross = attention.cross_encode(params["cross"], acfg,
                                                 enc_cache)
            if mode == "prefill":
                new_cache["cross"] = layer_cross
        else:
            layer_cross = None
        if layer_cross is not None:
            hc = common.norm_apply(cfg.norm, params["norm_cross"], x)
            yc = attention.cross_apply(params["cross"], acfg, hc,
                                       layer_cross)
            x = x + yc

    if blk.ffn != "none":
        h2 = common.norm_apply(cfg.norm, params["norm2"], x)
        if blk.ffn == "moe" and mode == "train":
            if cfg.experts_held:
                raise ValueError("a held share of the experts serves; it "
                                 "does not train")
            y2, a = moe.apply(params["ffn"], cfg.moe_cfg(), h2)
            aux = aux + a * cfg.moe.aux_loss_weight
        elif blk.ffn == "moe":
            y2, hist = moe.apply_dropless(
                params["ffn"], cfg.moe_cfg(), h2, first=cfg.expert_first,
                held=cfg.experts_held, valid=valid)
        else:
            y2 = mlp.apply(params["ffn"], cfg.mlp_cfg(), h2)
        x = x + y2
    return x, new_cache, aux, hist


def _superblock_init(key, cfg: ModelCfg, pattern, causal=True):
    ks = jax.random.split(key, len(pattern))
    return {f"b{i}": _block_init(ks[i], cfg, blk, causal)
            for i, blk in enumerate(pattern)}


def _superblock_axes(cfg: ModelCfg, pattern):
    return {f"b{i}": _block_axes(cfg, blk) for i, blk in enumerate(pattern)}


def _superblock_apply(params, cfg: ModelCfg, pattern, x, positions, *,
                      mode, causal=True, caches=None, enc_cache=None,
                      lengths=None, cache_len=None, page_state=None,
                      spatial_axis=None, valid=None, layer=None):
    new_caches, aux_total, hists = {}, jnp.zeros((), jnp.float32), []
    for i, blk in enumerate(pattern):
        x, nc, aux, hist = _block_apply(
            params[f"b{i}"], cfg, blk, x, positions, mode=mode,
            causal=causal, cache=caches[f"b{i}"] if caches else None,
            enc_cache=enc_cache, lengths=lengths, cache_len=cache_len,
            page_state=page_state, spatial_axis=spatial_axis, valid=valid,
            layer=layer)
        x = shd(x, "batch", "act_seq", "embed")
        new_caches[f"b{i}"] = nc
        aux_total = aux_total + aux
        if hist is not None:
            hists.append(hist)
    return x, new_caches, aux_total, (jnp.stack(hists) if hists else None)


# ---------------------------------------------------------------------------
# Model init / axes
# ---------------------------------------------------------------------------

def init(key, cfg: ModelCfg):
    ks = jax.random.split(key, 8)
    vp = cfg.vocab_padded
    p = {
        "embed": common.truncated_normal_init(ks[0], (vp, cfg.d_model),
                                              1.0, cfg.dtype),
        "final_norm": common.norm_init(cfg.norm, cfg.d_model),
        "out_head": common.truncated_normal_init(
            ks[1], (cfg.d_model, vp), 1.0, cfg.dtype),
    }
    block_keys = jax.random.split(ks[2], cfg.n_repeat)
    p["blocks"] = jax.vmap(
        lambda k: _superblock_init(k, cfg, cfg.pattern, cfg.causal)
    )(block_keys)
    if cfg.enc_layers:
        enc_pattern = (BlockCfg("attn", "dense"),)
        enc_keys = jax.random.split(ks[3], cfg.enc_layers)
        p["enc_blocks"] = jax.vmap(
            lambda k: _superblock_init(k, cfg, enc_pattern, causal=False)
        )(enc_keys)
        p["enc_norm"] = common.norm_init(cfg.norm, cfg.d_model)
    return p


def axes(cfg: ModelCfg):
    a = {
        # Embedding sharded on the HIDDEN dim: the token gather then stays
        # local per shard (no table all-gather, and the bwd scatter-add is
        # sharded too). Vocab-dim sharding forces a full-table gather.
        "embed": (None, "embed_tp"),
        "final_norm": common.norm_axes(cfg.norm),
        "out_head": ("embed_w", "vocab"),
    }
    blk = _superblock_axes(cfg, cfg.pattern)
    a["blocks"] = jax.tree.map(lambda ax: ("layers",) + ax, blk,
                               is_leaf=lambda x: isinstance(x, tuple))
    if cfg.enc_layers:
        enc = _superblock_axes(cfg, (BlockCfg("attn", "dense"),))
        a["enc_blocks"] = jax.tree.map(lambda ax: ("layers",) + ax, enc,
                                       is_leaf=lambda x: isinstance(x, tuple))
        a["enc_norm"] = common.norm_axes(cfg.norm)
    return a


# ---------------------------------------------------------------------------
# Forward paths
# ---------------------------------------------------------------------------

def _embed_inputs(params, cfg: ModelCfg, batch):
    if "embeds" in batch:
        x = batch["embeds"].astype(cfg.dtype)
    else:
        x = jnp.take(params["embed"], batch["tokens"], axis=0)
    return shd(x, "batch", "act_seq", "embed")


def _remat(fn, cfg: ModelCfg):
    if cfg.remat == "none":
        return fn
    if cfg.remat == "dots":
        policy = jax.checkpoint_policies.dots_with_no_batch_dims_saveable
    else:
        policy = jax.checkpoint_policies.nothing_saveable
    return jax.checkpoint(fn, policy=policy)


def _run_stack(blocks, cfg: ModelCfg, pattern, x, positions, *, mode,
               causal=True, caches=None, enc_cache=None, lengths=None,
               cache_len=None, page_state=None, spatial_axis=None,
               valid=None):
    """Scan the super-block over the repeat dim. Returns (x, caches, aux,
    moe_hist): ``moe_hist`` [MoE layers, held] int32 counts each layer's
    assignments per held expert (None without MoE blocks)."""

    def body(carry, layer_in):
        xc, aux_acc = carry
        xc = shd(xc, "batch", "act_seq", "embed")  # pin the carry sharding
        lp = layer_in["params"]
        lc = layer_in.get("cache")
        y, nc, aux, hist = _superblock_apply(
            lp, cfg, pattern, xc, positions, mode=mode, causal=causal,
            caches=lc, enc_cache=enc_cache, lengths=lengths,
            cache_len=cache_len, page_state=page_state,
            spatial_axis=spatial_axis, valid=valid)
        y = shd(y, "batch", "act_seq", "embed")
        return (y, aux_acc + aux), (nc, hist)

    body_fn = _remat(body, cfg) if mode == "train" else body
    xs = {"params": blocks}
    if caches is not None:
        xs["cache"] = caches
    (x, aux), (new_caches, hist) = jax.lax.scan(
        body_fn, (x, jnp.zeros((), jnp.float32)), xs)
    if hist is not None:                        # [R, blocks, held]
        hist = hist.reshape(-1, hist.shape[-1])
    return x, new_caches, aux, hist


def _with_hist(out: dict, hist) -> dict:
    """A serving path's cache dict, with the MoE routing counts beside it
    (``moe_hist``) when the model has MoE layers."""
    if hist is not None:
        out["moe_hist"] = hist
    return out


def _logits(params, cfg: ModelCfg, x):
    x = common.norm_apply(cfg.norm, params["final_norm"], x)
    logits = jnp.einsum("bsh,hv->bsv", x, params["out_head"])
    return shd(logits, "batch", "seq", "vocab")


def _encode(params, cfg: ModelCfg, batch):
    """Encoder stack (enc-dec models). Returns encoder output [B,S,H]."""
    x = batch["enc_embeds"].astype(cfg.dtype) if "enc_embeds" in batch \
        else jnp.take(params["embed"], batch["enc_tokens"], axis=0)
    x = shd(x, "batch", "seq", "embed")
    s = x.shape[1]
    positions = jnp.arange(s)
    x, _, _, _ = _run_stack(params["enc_blocks"], cfg,
                         (BlockCfg("attn", "dense"),), x, positions,
                         mode="encode", causal=False)
    return common.norm_apply(cfg.norm, params["enc_norm"], x)


def loss_fn(params, cfg: ModelCfg, batch):
    """Next-token CE loss (+ MoE aux + z-loss). batch: tokens|embeds, labels.

    Returns (loss, metrics). Logits are computed in sequence chunks so the
    [B, S, vocab] tensor never fully materializes.
    """
    x = _embed_inputs(params, cfg, batch)
    s = x.shape[1]
    positions = jnp.arange(s)
    enc_cache = _encode(params, cfg, batch) if cfg.enc_layers else None
    x, _, aux, _ = _run_stack(params["blocks"], cfg, cfg.pattern, x,
                              positions, mode="train", causal=cfg.causal,
                              enc_cache=enc_cache)
    x = common.norm_apply(cfg.norm, params["final_norm"], x)

    labels = batch["labels"]
    chunk = min(cfg.seq_loss_chunk, s)
    while s % chunk:
        chunk -= 1
    n_chunks = s // chunk
    vp = cfg.vocab_padded
    vocab_ok = jnp.arange(vp) < cfg.vocab

    def ce_chunk(_, inp):
        xc, lc = inp                       # [B,chunk,H], [B,chunk]
        logits = jnp.einsum("bsh,hv->bsv", xc, params["out_head"])
        logits = shd(logits, "batch", "seq", "vocab").astype(jnp.float32)
        logits = jnp.where(vocab_ok, logits, -1e30)
        lse = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(
            logits, jnp.maximum(lc, 0)[..., None], axis=-1)[..., 0]
        valid = (lc >= 0).astype(jnp.float32)
        ce = ((lse - gold) * valid).sum()
        zloss = (jnp.square(lse) * valid).sum()
        return None, (ce, zloss, valid.sum())

    xs = (jnp.moveaxis(x.reshape(-1, n_chunks, chunk, cfg.d_model), 1, 0),
          jnp.moveaxis(labels.reshape(-1, n_chunks, chunk), 1, 0))
    # remat each chunk: the [B,chunk,vocab] logits are recomputed in bwd.
    _, (ces, zs, cnts) = jax.lax.scan(jax.checkpoint(ce_chunk), None, xs)
    n_tok = jnp.maximum(cnts.sum(), 1.0)
    ce = ces.sum() / n_tok
    zloss = 1e-4 * zs.sum() / n_tok
    loss = ce + zloss + aux
    return loss, {"ce": ce, "aux": aux, "zloss": zloss, "tokens": n_tok}


def prefill(params, cfg: ModelCfg, batch, *, cache_len: Optional[int] = None,
            last_index: Optional[jax.Array] = None):
    """Process the prompt; build caches. Returns (last_logits, caches).

    ``last_index`` [B] selects which position's logits to return (default:
    the final one). Needed by length-bucketed serving, where prompts are
    right-padded and the real last token is mid-sequence.
    """
    x = _embed_inputs(params, cfg, batch)
    b, s, _ = x.shape
    positions = jnp.arange(s)
    enc_cache = _encode(params, cfg, batch) if cfg.enc_layers else None
    valid = None
    if cfg.has_moe and last_index is not None:
        valid = jnp.arange(s)[None, :] <= last_index[:, None]
    x, caches, _, hist = _run_stack(
        params["blocks"], cfg, cfg.pattern, x, positions, mode="prefill",
        causal=cfg.causal, enc_cache=enc_cache, cache_len=cache_len,
        valid=valid)
    if last_index is None:
        x_last = x[:, -1:, :]
        lengths = jnp.full((b,), s, jnp.int32)
    else:
        x_last = jnp.take_along_axis(
            x, last_index[:, None, None].astype(jnp.int32), axis=1)
        lengths = last_index.astype(jnp.int32) + 1
    logits = _logits(params, cfg, x_last)
    return logits[:, 0], _with_hist({"layers": caches, "lengths": lengths},
                                    hist)


def prefill_cache_shapes(params, cfg: ModelCfg, n_tokens: int):
    """Shapes and dtypes of ``prefill``'s per-layer caches for one
    ``n_tokens`` prompt, traced and never compiled — what the paged
    backends size their pool slabs from."""
    probe = {"tokens": jax.ShapeDtypeStruct((1, n_tokens), jnp.int32)}
    _, cache = jax.eval_shape(lambda p, b: prefill(p, cfg, b), params,
                              probe)
    return cache["layers"]


def prefill_chunk_batch_paged(params, cfg: ModelCfg, batch, cache,
                              pack_state):
    """Prefill MANY sequences' chunks as ONE flat varlen dispatch.

    batch["tokens"] [1, B_tok] — every packed chunk back to back in a
    fixed-width buffer (the scheduler's per-tick token budget; padding
    lanes/tails carry seg_id -1); ``cache["layers"]`` — pool slabs, read
    only; ``pack_state``:
      seg_ids [B_tok] — batch-slot lane per flat token (-1 = pad),
      positions [B_tok] — absolute token positions (RoPE-exact),
      past_phys/past_lane/past_logical [Wp] — the shared past-page
        ARENA: block-table rows of pages earlier chunks wrote, each slot
        tagged with its owner lane (-1 = pad; fixed Wp sized to TOTAL
        past, so the batched path compiles ONCE and the KV axis does not
        scale with lanes x max-window),
      past_len [S] — tokens already cached per lane,
      last_index [S] — FLAT index of each lane's last real token (its
        logits row; only meaningful on a lane's final chunk).

    Returns (logits [S, vocab_padded], chunk_caches [L, 1, B_tok, ...])
    — the engine scatters the flat rows onto each lane's pool pages. A
    one-lane wave is one sequence's chunk. All shapes depend only on
    (B_tok, S, Wp), never on the mix of chunks packed, so there is
    exactly one prefill compilation.
    """
    x = _embed_inputs(params, cfg, batch)
    positions = pack_state["positions"][None, :]
    valid = (pack_state["seg_ids"] >= 0)[None, :] if cfg.has_moe else None
    x, chunk_caches, _, hist = _run_stack(
        params["blocks"], cfg, cfg.pattern, x, positions,
        mode="prefill_chunk_batch", causal=cfg.causal,
        caches=cache["layers"], page_state=pack_state, valid=valid)
    x_last = jnp.take(x[0], pack_state["last_index"].astype(jnp.int32),
                      axis=0)[None]
    logits = _logits(params, cfg, x_last)
    return logits[0], _with_hist({"layers": chunk_caches}, hist)


def prefill_chunk_batch_spatial(params, cfg: ModelCfg, batch, cache,
                                pack_state, *, mesh, axis: str = "shards"):
    """Batched varlen chunk prefill across a device mesh: one shard_map
    dispatch advances MANY sequence-sharded prompts by one chunk each.

    Same flat layout as ``prefill_chunk_batch_paged``; the per-shard
    leaves are stacked on axis 0 and sharded over ``axis``:
      past_phys/past_lane/past_logical [n_shards, Wp] — each shard's
        slice of the past-page arena (shard-LOCAL physical ids, owner
        lane tags, GLOBAL logical page indices),
      chunk_phys [n_shards, 1, B_tok // page] — local scatter targets
        for the flat buffer's pages (SCRATCH off the owner shard);
    seg_ids/positions/past_len/last_index are replicated. Every shard
    computes partial (m, l, o) states of ALL lanes' chunk queries
    against its local past pages, merged through a pmax/psum tree (see
    ``attention.apply_prefill_chunk_batch_spatial``).
    """
    shard_spec, rep_spec = _spatial_specs(mesh, axis)
    sharded = {"past_phys", "past_lane", "past_logical", "chunk_phys"}
    ps_specs = {k: shard_spec if k in sharded else rep_spec
                for k in pack_state}

    def local_fn(p, toks, layers, ps):
        layers = jax.tree.map(lambda leaf: leaf[0], layers)
        ps = {k: (v[0] if k in sharded else v) for k, v in ps.items()}
        x = _embed_inputs(p, cfg, {"tokens": toks})
        positions = ps["positions"][None, :]
        x, new_layers, _, _ = _run_stack(
            p["blocks"], cfg, cfg.pattern, x, positions,
            mode="prefill_chunk_batch", causal=cfg.causal, caches=layers,
            page_state=ps, spatial_axis=axis)
        x_last = jnp.take(x[0], ps["last_index"].astype(jnp.int32),
                          axis=0)[None]
        logits = _logits(p, cfg, x_last)[0]
        return logits, jax.tree.map(lambda leaf: leaf[None], new_layers)

    fn = jax.shard_map(
        local_fn, mesh=mesh,
        in_specs=(jax.tree.map(lambda _: rep_spec, params), rep_spec,
                  jax.tree.map(lambda _: shard_spec, cache["layers"]),
                  ps_specs),
        out_specs=(rep_spec,
                   jax.tree.map(lambda _: shard_spec, cache["layers"])))
    logits, new_layers = fn(params, batch["tokens"], cache["layers"],
                            pack_state)
    return logits, {"layers": new_layers}


def decode_step(params, cfg: ModelCfg, tokens, cache):
    """One decode step. tokens [B,1] -> (logits [B,vocab], new cache)."""
    x = jnp.take(params["embed"], tokens, axis=0)
    x = shd(x, "batch", "seq", "embed")
    lengths = cache["lengths"]
    x, new_caches, _, hist = _run_stack(
        params["blocks"], cfg, cfg.pattern, x, lengths[:, None],
        mode="decode", causal=cfg.causal, caches=cache["layers"],
        lengths=lengths)
    logits = _logits(params, cfg, x)
    return logits[:, 0], _with_hist({"layers": new_caches,
                                     "lengths": lengths + 1}, hist)


def _spatial_specs(mesh, axis: str):
    from jax.sharding import PartitionSpec as P
    return P(axis), P()


def decode_step_spatial(params, cfg: ModelCfg, tokens, cache, page_state,
                        *, mesh, axis: str = "shards"):
    """One decode step against sequence-sharded paged pools.

    The query token is broadcast (replicated forward on every shard), each
    shard attends over its local hot pages via the paged gather, and the
    partial (m, l, o) states merge across the mesh axis — the spatial
    deployment's decode dataflow. Shapes depend only on (max_batch,
    hot_pages_local, pool size), so decode compiles ONCE regardless of the
    request mix, exactly like the single-pool engine.

    ``page_state`` leaves are stacked per-shard: phys/logical
    [n_shards, B, W] (logical = GLOBAL page index), write_page/write_off
    [n_shards, B] (SCRATCH off the owner shard). W is the backend's
    effective hot width — ``min(hot_pages_local, decode_hot_width)`` when
    the scheduler bounds the decode gather (sphere rule over DLZS scores).
    With bounded widths a shard can own ZERO hot pages for the whole
    batch; its local attention is skipped and it feeds the merge the
    neutral state (attention.apply_decode_spatial). An optional ``qmask``
    [n_shards, B, W] marks hot slots served from the int8 cold tier
    (kvcache.quant) — present only when ``SchedulerCfg.kv_quant`` is on.
    """
    shard_spec, rep_spec = _spatial_specs(mesh, axis)

    def local_fn(p, toks, layers, lengths, ps):
        layers = jax.tree.map(lambda leaf: leaf[0], layers)
        ps = jax.tree.map(lambda leaf: leaf[0], ps)
        x = jnp.take(p["embed"], toks, axis=0)
        x, new_layers, _, _ = _run_stack(
            p["blocks"], cfg, cfg.pattern, x, lengths[:, None],
            mode="decode", causal=cfg.causal, caches=layers,
            lengths=lengths, page_state=ps, spatial_axis=axis)
        logits = _logits(p, cfg, x)[:, 0]
        return logits, jax.tree.map(lambda leaf: leaf[None], new_layers)

    fn = jax.shard_map(
        local_fn, mesh=mesh,
        in_specs=(jax.tree.map(lambda _: rep_spec, params), rep_spec,
                  jax.tree.map(lambda _: shard_spec, cache["layers"]),
                  rep_spec,
                  jax.tree.map(lambda _: shard_spec, page_state)),
        out_specs=(rep_spec,
                   jax.tree.map(lambda _: shard_spec, cache["layers"])))
    logits, new_layers = fn(params, tokens, cache["layers"],
                            cache["lengths"], page_state)
    return logits, {"layers": new_layers,
                    "lengths": cache["lengths"] + 1}


def audit_decode_spatial(params, cfg: ModelCfg, tokens, cache, page_state,
                         *, mesh, axis: str = "shards"):
    """Exact-attention audit probe over sequence-sharded pools (obs.audit).

    Same dispatch shape as ``decode_step_spatial`` but ``page_state``
    carries an ``audit`` flag (so every attention layer emits its per-page
    softmax masses, globally normalized via pmax/psum) and only the stacked
    masses come back: [n_shards, n_blocks, n_repeat, B, W_local] f32.
    The cache is NOT returned and the caller must not donate it — the
    probe is read-only from the engine's point of view.
    """
    shard_spec, rep_spec = _spatial_specs(mesh, axis)

    def local_fn(p, toks, layers, lengths, ps):
        layers = jax.tree.map(lambda leaf: leaf[0], layers)
        ps = jax.tree.map(lambda leaf: leaf[0], ps)
        x = jnp.take(p["embed"], toks, axis=0)
        _, new_layers, _, _ = _run_stack(
            p["blocks"], cfg, cfg.pattern, x, lengths[:, None],
            mode="decode", causal=cfg.causal, caches=layers,
            lengths=lengths, page_state=ps, spatial_axis=axis)
        masses = [leaf for path, leaf in
                  jax.tree_util.tree_flatten_with_path(new_layers)[0]
                  if any(isinstance(k, jax.tree_util.DictKey)
                         and k.key == "audit_mass" for k in path)]
        return jnp.stack(masses)[None]     # [1, blocks, R, B, W_local]

    fn = jax.shard_map(
        local_fn, mesh=mesh,
        in_specs=(jax.tree.map(lambda _: rep_spec, params), rep_spec,
                  jax.tree.map(lambda _: shard_spec, cache["layers"]),
                  rep_spec,
                  jax.tree.map(lambda _: shard_spec, page_state)),
        out_specs=shard_spec)
    return fn(params, tokens, cache["layers"], cache["lengths"], page_state)


def _split_audit(caches):
    """A super-block's decode caches, less the audit probe's per-page
    masses, and those masses by block (empty outside the probe)."""
    pools, masses = {}, {}
    for name, c in caches.items():
        attn = dict(c["attn"])
        if "audit_mass" in attn:
            masses[name] = attn.pop("audit_mass")
        pools[name] = dict(c, attn=attn)
    return pools, masses


def decode_step_paged(params, cfg: ModelCfg, tokens, cache, page_state):
    """One decode step against paged KV pools (attention-only patterns).

    ``cache["layers"]`` leaves are page pools stacked over layers
    [L, n_pages, page, n_kv, dh]. The layer loop carries them whole: each
    layer gets its index from the scan's input beside its parameters,
    writes its new K/V rows at (layer, write_page, write_off) and hands the
    paged kernel the stacked pool with that index
    (attention.apply_decode_paged), so no layer's slab is sliced out or
    stacked back and the donated pool is updated in place.

    ``page_state`` carries the per-slot block-table rows and write
    coordinates; its W axis is the backend's effective hot width
    (``min(hot_pages, SchedulerCfg.decode_hot_width)`` under bounded
    sphere-rule selection) and an optional ``qmask`` [B, W] marks slots
    read from the int8 cold tier. With an ``audit`` key every layer also
    reports its per-page softmax masses, returned as ``audit_mass``
    {block: [L, B, W]}. Shapes depend only on (max_batch, effective hot
    width, pool size) — never on sequence length — so one compilation
    serves every request mix.
    """
    x = jnp.take(params["embed"], tokens, axis=0)
    x = shd(x, "batch", "seq", "embed")
    lengths = cache["lengths"]
    # a lane is real when it has a page to attend (idle lanes have none)
    valid = ((page_state["logical"] >= 0).any(axis=-1)[:, None]
             if cfg.has_moe else None)
    blocks = params["blocks"]

    def body(carry, layer_in):
        xc, pools = carry
        xc = shd(xc, "batch", "act_seq", "embed")
        y, pools, _, hist = _superblock_apply(
            layer_in["params"], cfg, cfg.pattern, xc, lengths[:, None],
            mode="decode", causal=cfg.causal, caches=pools, lengths=lengths,
            page_state=page_state, valid=valid, layer=layer_in["layer"])
        pools, masses = _split_audit(pools)
        return (shd(y, "batch", "act_seq", "embed"), pools), (masses, hist)

    n_layers = jax.tree.leaves(blocks)[0].shape[0]
    (x, pools), (masses, hist) = jax.lax.scan(
        body, (x, cache["layers"]),
        {"params": blocks, "layer": jnp.arange(n_layers, dtype=jnp.int32)})
    if hist is not None:                        # [R, blocks, held]
        hist = hist.reshape(-1, hist.shape[-1])
    out = {"layers": pools, "lengths": lengths + 1}
    if masses:
        out["audit_mass"] = masses
    logits = _logits(params, cfg, x)
    return logits[:, 0], _with_hist(out, hist)
