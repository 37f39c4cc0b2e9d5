"""Shared fixtures. NOTE: no XLA_FLAGS here — tests must see 1 CPU device;
a multi-device test sets its own device count in a subprocess."""

import numpy as np
import pytest


@pytest.fixture
def rng():
    return np.random.default_rng(0)


def make_stream(n=20_000, universe=50_000, seed=0):
    r = np.random.default_rng(seed)
    keys = r.integers(0, universe, size=n).astype(np.uint32)
    _, first = np.unique(keys, return_index=True)
    truth = np.ones(n, bool)
    truth[first] = False
    return keys, truth


@pytest.fixture
def stream():
    return make_stream()
