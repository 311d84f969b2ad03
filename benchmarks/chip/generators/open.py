"""Open loop: independent users who send on a schedule, however fast the
system answers, so a slow system builds a queue.

Gaps come from the mix's ``arrivals`` (process and rate), stratified
(see ``traffic.gaps``); the schedule covers the pre-roll and the window.
The order of the gaps, and of the requests' sizes and experts, is the
same for every seed (``order_seed``): an open loop's tail is set by which
long prompts meet which bursts, and one fixed sample path keeps that
apart from what the seed draws — token ids, images, routing features
and weights.
"""
from __future__ import annotations

import math

import numpy as np

import traffic as tr

ORDER_SEED = 0

class Generator:
    def __init__(self, mix: dict, clients: int, horizon_s: float, seed: int,
                 k: int = 1):
        arr = mix["arrivals"]
        self.n = int(math.ceil(arr["rate_per_s"] * horizon_s)) + 1
        self.order_seed = ORDER_SEED
        rng = np.random.default_rng([ORDER_SEED, 4])
        self.due = np.cumsum(rng.permutation(tr.gaps(arr, self.n)))
        self.next = 0

    def pop_due(self, now: float):
        """[(request index, due time, share of its output length, expert)]
        due by ``now`` (seconds from the start of the schedule); None keeps
        the mix's own length and expert."""
        out = []
        while self.next < self.n and self.due[self.next] <= now:
            out.append((self.next, float(self.due[self.next]), None, None))
            self.next += 1
        return out

    def next_due(self):
        return float(self.due[self.next]) if self.next < self.n else None

    def finished(self, idx: int, t: float) -> None:
        pass
