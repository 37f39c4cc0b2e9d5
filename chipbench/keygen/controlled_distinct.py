"""The paper's synthetic stream (Section 6): exactly
round(distinct_fraction * chunk) new keys per chunk at seeded positions,
fresh ids from a 32-bit bijection of a counter, repeats redrawn uniformly
from the ids emitted so far (the prefix). Every seed gives the same chunk
sizes and distinct counts, so the work of a run does not depend on it."""

from __future__ import annotations

import numpy as np

from ..mix import fmix32_np, seed_words
from . import KEY_STREAM


class ControlledDistinct:
    """Chunked controlled-distinct keys, deterministic in (seed, chunk
    index); chunks must be drawn in order (the prefix grows)."""

    def __init__(self, params: dict, seed: int, chunk: int):
        self.frac = float(params["distinct_fraction"])
        self.chunk = int(chunk)
        self.seed = int(seed)
        self.salt = np.uint32(seed_words(seed, 1, KEY_STREAM)[0])
        self.count = 0          # distinct ids emitted so far
        self.index = 0

    def next(self) -> np.ndarray:
        n = self.chunk
        rng = np.random.default_rng([self.seed, KEY_STREAM, self.index])
        n_new = max(1, int(round(n * self.frac)))
        new = np.zeros(n, bool)
        new[rng.permutation(n)[:n_new]] = True
        if self.count == 0 and not new[0]:
            # the stream's first key is new: move one new slot to lane 0
            new[np.flatnonzero(new)[-1]] = False
            new[0] = True
        seen = self.count + np.cumsum(new)          # ids emitted so far
        ids = np.empty(n, np.uint64)
        ids[new] = self.count + np.arange(n_new, dtype=np.uint64)
        rep = ~new
        ids[rep] = np.floor(rng.random(int(rep.sum())) * seen[rep]
                            ).astype(np.uint64)
        self.count += n_new
        self.index += 1
        return fmix32_np(ids.astype(np.uint32) ^ self.salt)


def make(params: dict, seed: int, chunk: int) -> ControlledDistinct:
    return ControlledDistinct(params, seed, chunk)
