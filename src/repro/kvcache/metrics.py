"""Observability for the paged KV cache: DLZS page scores + bytes accounting.

``page_scores`` is the device half of the retention policy: reduce the int8
LZ-code pool (1 byte per cached key element — the same compressed operand
the STAR decode predictor streams) to one score per physical page, max'd
across layers, KV heads and head dims. The reduction reads |code| =
|floor(log2 |k|)| + bias, so a page scores high iff *some* key in it has a
large log-magnitude anywhere in the stack — a cheap, query-agnostic upper
bound on how large any DLZS-estimated attention score against that page can
get. Pools without an LZ slab fall back to packing K on the fly.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import dlzs


def _leaves_by_key(tree, want: str, avoid: str | None = None):
    """Leaves of ``tree`` whose path contains dict key ``want`` (and not
    ``avoid``)."""
    out = []
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        keys = [p.key for p in path if isinstance(p, jax.tree_util.DictKey)]
        if want in keys and (avoid is None or avoid not in keys):
            out.append(leaf)
    return out


def page_scores(cache_layers) -> jax.Array:
    """Per-physical-page DLZS score: max |int8 LZ code| over everything but
    the page axis. Pool leaves are [L, n_pages, page, n_kv, dh]."""
    lz = _leaves_by_key(cache_layers, "k_lz")
    if not lz:
        lz = [dlzs.lz_pack(k) for k in _leaves_by_key(cache_layers, "k")]
    if not lz:
        raise ValueError("no k/k_lz page pools in cache")
    per = [jnp.abs(leaf.astype(jnp.int32)).max(axis=(0, 2, 3, 4))
           for leaf in lz]
    return jnp.max(jnp.stack(per), axis=0)


def page_scores_per_layer(cache_layers) -> jax.Array:
    """Per-(layer, page) DLZS score: max |int8 LZ code| over the page's
    rows/heads/dims, one row per stacked layer — [n_layers, n_pages].
    ``page_scores`` is the max of this over axis 0; the audit
    (obs.audit) histograms the full matrix to show how prediction
    confidence varies across the stack."""
    lz = _leaves_by_key(cache_layers, "k_lz")
    if not lz:
        lz = [dlzs.lz_pack(k) for k in _leaves_by_key(cache_layers, "k")]
    if not lz:
        raise ValueError("no k/k_lz page pools in cache")
    per = [jnp.abs(leaf.astype(jnp.int32)).max(axis=(2, 3, 4))
           for leaf in lz]
    return jnp.concatenate(per, axis=0)


def tree_bytes(tree) -> int:
    """Total bytes of every array leaf (device-side cache footprint)."""
    return sum(leaf.size * leaf.dtype.itemsize
               for leaf in jax.tree.leaves(tree)
               if hasattr(leaf, "dtype"))


def k_pool(cache_layers):
    """The first K page pool of the stack, [L, n_pages, page, n_kv, dh]
    (every attention block's pool has the same page, head and dtype)."""
    return _leaves_by_key(cache_layers, "k")[0]


def bytes_per_page(cache_layers) -> int:
    """Bytes one physical page occupies across the whole layer stack."""
    leaves = [l for l in jax.tree.leaves(cache_layers) if hasattr(l, "dtype")]
    if not leaves:
        return 0
    n_pages = leaves[0].shape[1]
    return tree_bytes(cache_layers) // n_pages


def gather_bytes_per_page(cache_layers) -> int:
    """Bytes the decode gather reads per hot page: the fp K and V slab rows
    only — LZ codes and the int8 mirror tier are never gathered by the
    dense path, so this (not ``bytes_per_page``) prices a *skipped* page's
    avoided memory traffic (obs.accounting bytes-not-gathered)."""
    kv = _leaves_by_key(cache_layers, "k") + _leaves_by_key(cache_layers, "v")
    if not kv:
        return 0
    n_pages = kv[0].shape[1]
    return sum(l.size * l.dtype.itemsize for l in kv) // n_pages


def quant_bytes_per_page(cache_layers) -> int:
    """Bytes one page occupies in the int8 mirror tier (codes + scales);
    0 when the tier is absent. Prices a quantize transition's writes in
    the accounting traffic counters."""
    qs = [leaf for key in ("kq", "vq", "k_scale", "v_scale")
          for leaf in _leaves_by_key(cache_layers, key)]
    if not qs:
        return 0
    n_pages = qs[0].shape[1]
    return sum(l.size * l.dtype.itemsize for l in qs) // n_pages
