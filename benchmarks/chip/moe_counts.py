"""Operations and bytes of the expert layer, and the reader of the routing
counts the program keeps, for the MoE cells' per-layer metrics.

The program tallies each prefill wave's and decode step's assignments to
the experts held on the chip, per layer, on the device, and while a
profiler session is active keeps the totals with their host times
(``repro.obs.profiled_counts``, as ``engine.moe.decode`` and
``engine.moe.prefill``): ``assignments``, the token-to-expert assignments
routed to held experts, and ``experts_hit``, the (layer, held expert) pairs
that received at least one token. A program that keeps no such counts
yields none, and the readers leave their metrics out.

* ``gmm_cost`` — FLOPs and HBM bytes of the grouped expert matmuls: 2·3·d·f
  FLOPs per held assignment; each hit expert's three matrices read once per
  dispatch, and each assignment's row read in and written out once.
* ``dense_token_flops`` — 2 per attention, router and head weight, the
  model's FLOPs per token outside the experts and attention's keys.
* ``decode_flops`` — the window's decode steps: the probe's decode lanes
  (keys counted as ``counts`` does) plus 2·3·d·f per held assignment of the
  decode steps' counts.
* ``window_flops`` — every token the window processed: ``decode_flops``, the
  prefill waves, and 2·3·d·f per held assignment from the counts.
"""

from __future__ import annotations

from typing import Optional

from benchmarks.chip import counts

# op names the trace gives the expert computation's grouped matmuls
# (XLA's ragged-dot kernels, and their group metadata)
GMM_KERNEL = r"%ragged-dot"


def window_counts(ctx, phase: str = "") -> Optional[dict]:
    """Summed routing counts of the dispatches pulled in the window, of
    one ``phase`` (``decode`` or ``prefill``) where it is given."""
    from repro.obs import trace
    kept = getattr(trace, "profiled_counts", None)
    if kept is None:
        return None
    rows = [v for name, t, v in kept()
            if name.startswith("engine.moe." + phase) and ctx.in_window(t)]
    if not rows:
        return None
    return {"dispatches": len(rows),
            "assignments": sum(v["assignments"] for v in rows),
            "experts_hit": sum(v["experts_hit"] for v in rows)}


def expert_params(m: dict) -> int:
    """Weights of one expert (gated: W1, W3, W2)."""
    return 3 * m["d_model"] * m["d_ff"]


def gmm_cost(m: dict, assignments: int, experts_hit: int,
             dtype_bytes: int = 2) -> tuple[float, float]:
    flops = 2.0 * expert_params(m) * assignments
    nbytes = (float(expert_params(m)) * dtype_bytes * experts_hit
              + 2.0 * m["d_model"] * dtype_bytes * assignments)
    return flops, nbytes


def dense_token_flops(m: dict) -> float:
    d, dh = m["d_model"], counts.head_dim(m)
    attn = d * m["n_heads"] * dh * 2 + d * m["n_kv"] * dh * 2
    router = d * m["n_experts"]
    return 2.0 * (m["n_layers"] * (attn + router) + d * m["vocab"])


def decode_flops(m: dict, decodes, assignments: int) -> float:
    per_key = counts.attn_flops_per_key(m)
    tok = dense_token_flops(m)
    flops = sum(tok + per_key * c for step in decodes for c in step)
    return flops + 2.0 * expert_params(m) * assignments


def window_flops(m: dict, decodes, waves, assignments: int) -> float:
    per_key = counts.attn_flops_per_key(m)
    tok = dense_token_flops(m)
    flops = decode_flops(m, decodes, assignments)
    for wave in waves:
        for n, past in wave:
            flops += tok * n + per_key * (n * past + n * (n + 1) // 2)
    return flops
