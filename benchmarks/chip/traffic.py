"""The one general traffic generator: a mix is a data file of parameters
(``traffic/<name>.json``) and the arrival process is a module of its own
(``generators/<kind>.py``), both found by name (``by_name.py``).

Every seed gets the same work. Lengths and gaps are drawn by stratified
quantiles — the value at probabilities (i + 0.5) / n of the stated
distribution — and permuted by the generator's ``order_seed`` (the run's
seed, or for an open loop a fixed one); the seed fills in the token ids,
image patches and routing features. So two seeds differ in content, and
at most in order, never in how much there is to do.
"""
from __future__ import annotations

import json
import math
import os
from dataclasses import dataclass

import numpy as np
from scipy import stats

HERE = os.path.dirname(os.path.abspath(__file__))
N_IMAGES = 16        # distinct images a run draws its requests' patches from


def quantiles(dist: dict, n: int) -> np.ndarray:
    """n stratified draws of a length distribution, as whole numbers
    clipped to [min, max], in ascending order. ``categorical`` lists its
    ``values`` with their ``weights``."""
    p = (np.arange(n) + 0.5) / n
    kind = dist["dist"]
    if kind == "categorical":
        cum = np.cumsum(dist["weights"]) / np.sum(dist["weights"])
        idx = np.minimum(np.searchsorted(cum, p), len(cum) - 1)
        return np.asarray(dist["values"], np.int64)[idx]
    if kind == "lognormal":
        x = dist["median"] * np.exp(dist["sigma"] * stats.norm.ppf(p))
    elif kind == "uniform":
        x = dist["min"] + p * (dist["max"] + 1 - dist["min"]) - 0.5
    else:
        raise ValueError(f"unknown length distribution {kind!r}")
    return np.clip(np.rint(x), dist["min"], dist["max"]).astype(np.int64)


def gaps(arr: dict, n: int) -> np.ndarray:
    """n stratified inter-arrival gaps (seconds) of the arrival process:
    Poisson (exponential gaps) or gamma gaps with a stated coefficient of
    variation, both at ``rate_per_s``."""
    p = (np.arange(n) + 0.5) / n
    mean = 1.0 / arr["rate_per_s"]
    if arr["process"] == "poisson":
        return mean * stats.expon.ppf(p)
    if arr["process"] == "gamma":
        shape = 1.0 / arr["cv"] ** 2
        return stats.gamma.ppf(p, shape, scale=mean / shape)
    raise ValueError(f"unknown arrival process {arr['process']!r}")


@dataclass
class Request:
    idx: int
    text_len: int
    max_new: int
    expert: int           # centroid the routing features lie near


class Mix:
    """A traffic mix bound to a seed: request i's sizes and contents."""

    def __init__(self, traffic: dict, seed: int, n: int, k: int,
                 feature_dim: int, vocab: int, n_patches: int,
                 vision_dim: int, order_seed: int):
        self.t, self.seed, self.n = traffic, seed, n
        rng = np.random.default_rng([seed, 2])
        order = rng if order_seed == seed else \
            np.random.default_rng([order_seed, 2])
        self.text = order.permutation(quantiles(traffic["prompt_tokens"], n))
        self.new = order.permutation(quantiles(traffic["output_tokens"], n))
        self.expert = order.permutation(np.arange(n) % k)
        self.centroids = rng.normal(size=(k, feature_dim))
        self.centroids /= np.linalg.norm(self.centroids, axis=-1,
                                         keepdims=True)
        self.vocab, self.feature_dim = vocab, feature_dim
        self.images = rng.standard_normal(
            (N_IMAGES, n_patches, vision_dim), dtype=np.float32)

    def request(self, i: int, expert=None) -> Request:
        j = i % self.n
        return Request(i, int(self.text[j]), int(self.new[j]),
                       int(self.expert[j]) if expert is None else expert)

    def content(self, r: Request):
        """(tokens, patches, features) of request ``r``."""
        rng = np.random.default_rng([self.seed, 3, r.idx])
        tokens = rng.integers(0, self.vocab, r.text_len).astype(np.int32)
        noise = self.t["routing"]["noise"]
        feats = self.centroids[r.expert] + noise * rng.normal(
            size=self.feature_dim) / math.sqrt(self.feature_dim)
        return (tokens, self.images[r.idx % N_IMAGES],
                feats.astype(np.float32))

    def bounds(self):
        """(min, max) text length and (min, max) output length."""
        return (extent(self.t["prompt_tokens"]),
                extent(self.t["output_tokens"]))


def extent(dist: dict):
    """(min, max) of a length distribution."""
    if dist["dist"] == "categorical":
        return min(dist["values"]), max(dist["values"])
    return dist["min"], dist["max"]


def load(name: str, root: str = HERE) -> dict:
    path = os.path.join(root, "traffic", f"{name}.json")
    if not os.path.isfile(path):
        raise FileNotFoundError(f"no traffic mix named {name!r} ({path})")
    with open(path) as f:
        return json.load(f)
