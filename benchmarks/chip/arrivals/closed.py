"""Closed loop: a fixed set of clients, each sending its next request the
moment its previous one finishes (offline batch generation).

``clients`` in the traffic file is a number or ``"max_batch"`` (the
configuration's engine lanes). The loop starts in equilibrium rather
than in lockstep: client i's first request is one already ``age_i``
tokens into its answer, with ``age_i`` spread uniformly over its output
length, so it is submitted with a prompt of prompt + age tokens and
asks for the rest. Completions are then spread over time from the start,
and the window can open as soon as every first request has its first
token (``Arrivals.ready``).

The lengths and ages are the same for every seed: a window holds a few
tens of requests, far fewer than a block of stratified draws, so lengths
drawn from the seed changed how much work a window held (out_tok_s read
75.5-78.7 over six seeds of chatglm3_6b.decode_batch on a TPU v5e, and
within 1.1% on each seed run twice). Nor does the seed set the order in
which the first requests take the lanes, so every seed runs one schedule
of lengths, lanes and prefill waves; the seed sets the tokens and the
weights alone.
"""

from __future__ import annotations

import numpy as np

from benchmarks.chip import traffic

BLOCK = 512
LENGTH_SEED = 1


class Arrivals:
    closed = True

    def __init__(self, mix: dict, seed: int, *, max_batch: int):
        c = mix["clients"]
        self.clients = max_batch if c == "max_batch" else int(c)
        self.mix = mix
        self.rng = np.random.default_rng(LENGTH_SEED)
        self.pairs: list[tuple[int, int]] = []
        self.n = 0

    def _next_pair(self) -> tuple[int, int]:
        if not self.pairs:
            p = traffic.stratified(self.mix["prompt"], BLOCK, self.rng)
            o = traffic.stratified(self.mix["output"], BLOCK, self.rng)
            self.pairs = list(zip(p, o))[::-1]
        return self.pairs.pop()

    def _req(self, due_t, prompt_len, max_tokens, client) -> traffic.Req:
        r = traffic.Req(self.n, due_t, prompt_len, max_tokens, client)
        self.n += 1
        return r

    def warmup(self) -> list[traffic.Req]:
        """Every client's first request, mid-answer (see module doc)."""
        ages = (np.arange(self.clients) + self.rng.random()) / self.clients
        ages = ages[self.rng.permutation(self.clients)]
        out = []
        for c in range(self.clients):
            p, o = self._next_pair()
            a = min(o - 1, int(ages[c] * o))
            out.append(self._req(-1.0, p + a, o - a, c))
        return out

    def due(self, now: float) -> list[traffic.Req]:
        return []

    def next_due(self):
        return None

    def on_done(self, req: traffic.Req, now: float) -> list[traffic.Req]:
        p, o = self._next_pair()
        return [self._req(now, p, o, req.client)]
