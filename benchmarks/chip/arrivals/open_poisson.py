"""Open loop: independent users arriving as a Poisson process at the
fixed ``rate_per_s`` of the traffic file, whether or not earlier
requests have finished (interactive serving).

The window holds exactly round(rate x seconds) arrivals, the first at
its start, with the gaps between them scaled to fill it
(``traffic.stratified_gaps``); further
blocks keep the load on while the requests due in the window finish.
``warmup_requests`` requests with prompts of the same lengths, and a
few answer tokens each, are served to the end before the window opens,
to compile and warm every program the traffic uses.
"""

from __future__ import annotations

import numpy as np

from benchmarks.chip import traffic

WARMUP_TOKENS = 4       # enough decode steps to compile and warm decode


class Arrivals:
    closed = False

    def __init__(self, mix: dict, seed: int, *, max_batch: int):
        self.mix = mix
        self.rate = float(mix["rate_per_s"])
        self.rng = np.random.default_rng([seed, 2])
        self.n = 0
        self.queue: list[traffic.Req] = []
        self.t_next = 0.0
        self.seconds = None

    def _req(self, due_t, prompt_len, max_tokens) -> traffic.Req:
        r = traffic.Req(self.n, due_t, prompt_len, max_tokens)
        self.n += 1
        return r

    def warmup(self) -> list[traffic.Req]:
        k = int(self.mix.get("warmup_requests", 4))
        p = traffic.stratified(self.mix["prompt"], k, self.rng)
        return [self._req(-1.0, a, WARMUP_TOKENS) for a in p]

    def start(self, seconds: float) -> None:
        self.seconds = seconds
        self._block()

    def _block(self) -> None:
        n = max(1, round(self.rate * self.seconds))
        span = self.seconds if self.t_next == 0 else n / self.rate
        gaps = traffic.stratified_gaps(self.rate, n, span, self.rng)
        p = traffic.stratified(self.mix["prompt"], n, self.rng)
        o = traffic.stratified(self.mix["output"], n, self.rng)
        t = self.t_next
        for g, a, b in zip(gaps, p, o):
            self.queue.append(self._req(t, a, b))
            t += g
        self.t_next = t
        self.queue.reverse()

    def due(self, now: float) -> list[traffic.Req]:
        out = []
        while True:
            if not self.queue:
                self._block()
            if self.queue[-1].due_t > now:
                return out
            out.append(self.queue.pop())

    def next_due(self) -> float:
        if not self.queue:
            self._block()
        return self.queue[-1].due_t

    def on_done(self, req: traffic.Req, now: float) -> list[traffic.Req]:
        return []
