"""Operations and bytes of the served model, computed from its shapes.

Everything here is arithmetic on the sizes in a configuration file's
``model`` section (see ``configs/*.json``); nothing is read from the
program. Counts are of useful work: real vocabulary columns, real tokens
and each sequence's real length, never padded buckets or pages, so two
implementations that do the same work are charged the same.

* ``matmul_params`` — weights a token multiplies by: Q/K/V/O, the MLP and
  the output head (the embedding is a gather, not a product).
* ``token_flops`` — 2 per matmul weight, plus 4·L·H·dh per key attended
  (scores and the value product).
* ``paged_decode_cost`` — FLOPs and HBM bytes of decode attention over
  each lane's cached keys and values (the paged-decode kernel's work).
* ``peaks`` — the chip's published peaks from ``peaks.json``, keyed by
  JAX's ``device_kind``; an unknown kind is an error, never a default.
"""

from __future__ import annotations

import json
import pathlib

PEAKS_FILE = pathlib.Path(__file__).with_name("peaks.json")


def head_dim(m: dict) -> int:
    return m.get("head_dim") or m["d_model"] // m["n_heads"]


def layer_params(m: dict) -> int:
    """Matmul weights of one decoder layer (biases and norms excluded)."""
    d, dh = m["d_model"], head_dim(m)
    attn = d * m["n_heads"] * dh * 2 + d * m["n_kv"] * dh * 2
    mlp = (3 if m.get("mlp_gated", True) else 2) * d * m["d_ff"]
    return attn + mlp


def matmul_params(m: dict) -> int:
    return m["n_layers"] * layer_params(m) + m["d_model"] * m["vocab"]


def attn_flops_per_key(m: dict) -> int:
    """FLOPs one query token spends per cached key, over all layers."""
    return 4 * m["n_layers"] * m["n_heads"] * head_dim(m)


def token_flops(m: dict, ctx: int) -> float:
    """One token through the model, attending ``ctx`` keys."""
    return 2.0 * matmul_params(m) + attn_flops_per_key(m) * ctx


def prefill_flops(m: dict, n: int, past: int) -> float:
    """``n`` prompt tokens after ``past`` cached ones, causal: token i
    attends past + i + 1 keys."""
    keys = n * past + n * (n + 1) // 2
    return 2.0 * matmul_params(m) * n + attn_flops_per_key(m) * keys


def decode_flops(m: dict, ctxs) -> float:
    """One decode step: each lane's token attends its ``ctx`` keys."""
    return sum(token_flops(m, c) for c in ctxs)


def kv_bytes_per_token(m: dict, dtype_bytes: int = 2) -> int:
    """K and V rows of one token over all layers."""
    return 2 * m["n_layers"] * m["n_kv"] * head_dim(m) * dtype_bytes


def paged_decode_cost(m: dict, ctxs, dtype_bytes: int = 2
                      ) -> tuple[float, float]:
    """(FLOPs, HBM bytes) of decode attention for lanes of lengths
    ``ctxs``: every cached K and V row read once, and each lane's query
    read and output written once per layer."""
    dh = head_dim(m)
    flops = float(sum(attn_flops_per_key(m) * c for c in ctxs))
    qo = 2 * m["n_layers"] * m["n_heads"] * dh * dtype_bytes
    nbytes = float(sum(kv_bytes_per_token(m, dtype_bytes) * c + qo
                       for c in ctxs))
    return flops, nbytes


def peaks(device_kind: str) -> dict:
    table = json.loads(PEAKS_FILE.read_text())
    if device_kind not in table:
        raise ValueError(f"no published peaks for device kind "
                         f"{device_kind!r} in {PEAKS_FILE.name}; known: "
                         f"{sorted(table)}")
    return table[device_kind]


def roofline_s(flops: float, nbytes: float, peak: dict) -> float:
    """Least time the chip could take: the larger of the compute bound
    and the memory bound."""
    return max(flops / peak["bf16_flops_per_s"],
               nbytes / peak["hbm_bytes_per_s"])
