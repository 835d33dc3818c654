"""Greedy-token parity between two serving paths, up to bf16 argmax ties.

Two paths that reduce in different orders — a sequence-sharded pool
against a single pool, or decode against prefill batch shapes — can round
a bf16 logit across a boundary, and greedy decode then picks another of
two tied tokens. ``divergence`` finds the first token where two outputs
differ and reads a reference prefill's logits there; the divergence is a
tie when both tokens lie within ``ulps`` bf16 ulps of the top logit.
``TIE_ULPS`` is the one rule for paths whose reduction orders differ.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import lm

TIE_ULPS = 2


def bf16_ulp(x: float) -> float:
    """Spacing of bfloat16 values (8 significand bits) around ``x``."""
    return float(2.0 ** (np.floor(np.log2(max(abs(x), 2.0 ** -126))) - 7))


@dataclasses.dataclass(frozen=True)
class Divergence:
    """First differing token of two greedy outputs, read against the
    logits of a reference prefill of the prompt plus the shared tokens."""
    index: int
    got: int
    want: int
    top: float      # reference top logit at ``index``
    gap: float      # the larger of the two tokens' distances below ``top``

    @property
    def gap_ulps(self) -> float:
        return self.gap / bf16_ulp(self.top)

    def is_tie(self, ulps: float = TIE_ULPS) -> bool:
        return self.gap <= ulps * bf16_ulp(self.top)

    def describe(self) -> str:
        return (f"first divergence at token {self.index}: {self.got} vs "
                f"{self.want}, reference gap {self.gap:.5f} = "
                f"{self.gap_ulps:g} bf16 ulps of top {self.top:.4f}")


@functools.partial(jax.jit, static_argnames="cfg")
def _last_logits(params, tokens, last, *, cfg):
    logits, _ = lm.prefill(params, cfg, {"tokens": tokens},
                           last_index=last)
    return logits


def divergence(params, cfg, prompt, got, want, *,
               pad_to: Optional[int] = None) -> Optional[Divergence]:
    """Reads the first divergence of ``got`` from ``want``; None when one
    is a prefix of the other (a length mismatch is never a tie).
    ``pad_to`` right-pads the reference sequence to a multiple of it, so
    that sequences of nearby lengths share one compile; causal attention
    leaves the read row untouched by the padding."""
    i = next((j for j, (a, b) in enumerate(zip(got, want)) if a != b),
             None)
    if i is None:
        return None
    seq = np.concatenate([np.asarray(prompt, np.int32),
                          np.asarray(got[:i], np.int32)])
    n = len(seq)
    width = n if pad_to is None else -(-n // pad_to) * pad_to
    tokens = np.zeros((1, width), np.int32)
    tokens[0, :n] = seq
    logits = _last_logits(params, jnp.asarray(tokens),
                          jnp.asarray([n - 1], jnp.int32), cfg=cfg)
    row = np.asarray(logits, np.float32).reshape(-1, logits.shape[-1])
    row = row[-1, :cfg.vocab]
    top = float(row.max())
    gap = float(max(top - row[got[i]], top - row[want[i]]))
    return Divergence(i, int(got[i]), int(want[i]), top, gap)


def is_greedy_tie(params, cfg, prompt, got, want, *,
                  ulps: float = TIE_ULPS,
                  pad_to: Optional[int] = None) -> bool:
    """True when ``got`` and ``want`` first differ at a token where both
    lie within ``ulps`` bf16 ulps of the reference top logit (0: an exact
    bf16 tie)."""
    d = divergence(params, cfg, prompt, got, want, pad_to=pad_to)
    return d is not None and d.is_tie(ulps)
