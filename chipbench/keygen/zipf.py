"""YCSB's Zipfian generator (Gray et al.) with its constant over
``universe`` ranks, scrambled to ids by a bijection so hot keys are not
numerically adjacent."""

from __future__ import annotations

import numpy as np

from ..mix import fmix32_np, seed_words
from . import KEY_STREAM


class Zipf:
    """YCSB ZipfianGenerator over ``universe`` ranks with constant
    ``constant`` (YCSB's default 0.99)."""

    def __init__(self, params: dict, seed: int):
        self.n = int(params["universe"])
        self.theta = float(params["constant"])
        self.salt = np.uint32(seed_words(seed, 1, KEY_STREAM)[0])
        self.rng = np.random.default_rng([int(seed), KEY_STREAM])
        th = self.theta
        zetan = 0.0
        for lo in range(1, self.n + 1, 1 << 20):
            i = np.arange(lo, min(self.n, lo + (1 << 20) - 1) + 1,
                          dtype=np.float64)
            zetan += float(np.sum(i ** -th))
        self.zetan = zetan
        zeta2 = 1.0 + 0.5 ** th
        self.alpha = 1.0 / (1.0 - th)
        self.eta = (1.0 - (2.0 / self.n) ** (1.0 - th)) / (1.0 - zeta2 / zetan)

    def ranks(self, m: int) -> np.ndarray:
        u = self.rng.random(m)
        uz = u * self.zetan
        r = np.floor(self.n * (self.eta * u - self.eta + 1.0) ** self.alpha)
        r = np.where(uz < 1.0 + 0.5 ** self.theta, 1, r)
        r = np.where(uz < 1.0, 0, r)
        return np.minimum(r, self.n - 1).astype(np.uint64)

    def next(self, m: int) -> np.ndarray:
        return fmix32_np(self.ranks(m).astype(np.uint32) ^ self.salt)


def make(params: dict, seed: int, chunk: int = 0) -> Zipf:
    return Zipf(params, seed)
