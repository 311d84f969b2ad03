"""Closed loop: a fixed number of clients, each sending its next request
as soon as its previous one has finished — an offline job that keeps
every slot busy. The harness passes one client per serving slot.

A client's first request is cut to a uniform share of its output length
(at least one token), the renewal process's residual life, so that the
clients start out of step instead of finishing together.

Sizes cycle through a small stratified population (``n``), so that a
window of a few dozen requests holds nearly the whole population
whatever the seed. With ``routing.experts == "per_client"`` client c's
images all lie near centroid c mod K: the collection is split by topic
over the clients, and each expert's pod keeps as many clients as any
other, on every seed.
"""
from __future__ import annotations

import heapq

import numpy as np


class Generator:
    n = 64               # size of the stratified length population

    def __init__(self, mix: dict, clients: int, horizon_s: float, seed: int,
                 k: int = 1):
        self.k = k if mix["routing"]["experts"] == "per_client" else None
        self.order_seed = seed
        self.heap = [(0.0, c) for c in range(clients)]
        self.client = {}
        self.count = 0
        self.share = np.random.default_rng([seed, 5]).uniform(size=clients)

    def pop_due(self, now: float):
        out = []
        while self.heap and self.heap[0][0] <= now:
            t, c = heapq.heappop(self.heap)
            idx, self.count = self.count, self.count + 1
            self.client[idx] = c
            first = idx < len(self.share)
            out.append((idx, t, float(self.share[c]) if first else None,
                        None if self.k is None else c % self.k))
        return out

    def next_due(self):
        return self.heap[0][0] if self.heap else None

    def finished(self, idx: int, t: float) -> None:
        heapq.heappush(self.heap, (t, self.client.pop(idx)))
