"""Pinned determinism digests of the batched engine (DESIGN.md §2/§3.8).

sha256 over (per-batch dup + inserted reports, final bits/load/position/
rng-key-data/ring) at memory_bits=1<<14, batch=256, 1024 mixed keys with a
ragged final batch. First captured from the hand-written per-variant steps
before the template refactor; re-pinned once on jax 0.9.0, whose
jax.random streams differ from the earlier JAX's (swbf draws no randomness
and kept its digest). ``tests/test_sketch_template.py`` holds the jnp steps
to them on the CPU and ``chip_smoke.py`` on the TPU.
"""

from __future__ import annotations

import hashlib

import jax
import jax.numpy as jnp
import numpy as np

from .config import DedupConfig
from .engine import Dedup

PINNED_DIGESTS = {
    "bsbf": "edc4e0416aabf697",
    "bsbfsd": "dd966b112ec90adc",
    "rlbsbf": "1d293b68091e6142",
    "rsbf": "e8af8813298e89c5",
    "sbf": "1b6f18ccd37e8e3f",
    "sbf_d1": "cf1d79e55361ae14",
    "swbf": "4580749bdb028080",
}


def pinned_config(name: str, backend: str = "jnp", **over) -> DedupConfig:
    """The config a digest is pinned at: ``name`` is a variant, or
    ``sbf_d1`` (SBF with Max = 1, one plane)."""
    base, kw = name, {}
    if name in ("rsbf", "bsbf", "bsbfsd", "rlbsbf"):
        kw = dict(packed=True)
    elif name == "sbf":
        kw = dict(layout="planes")
    elif name == "sbf_d1":
        base, kw = "sbf", dict(layout="planes", sbf_max=1)
    elif name == "swbf":
        kw = dict(window=4)
    merged = dict(memory_bits=1 << 14, batch_size=256, **kw)
    merged.update(over)
    return DedupConfig.for_variant(base, backend=backend, **merged)


def run_digest(cfg: DedupConfig) -> str:
    """Step ``cfg``'s engine over the pinned stream; the 16-hex digest."""
    eng = Dedup(cfg)
    state = eng.init()
    keys = np.random.RandomState(7).randint(0, 400, size=1024) \
        .astype(np.uint32)
    b = cfg.batch_size
    h = hashlib.sha256()
    for i in range(0, len(keys), b):
        kb = jnp.asarray(keys[i:i + b])
        valid = np.ones((b,), bool)
        if i + b >= len(keys):
            valid[b // 2:] = False          # ragged final batch
        state, res = eng.process(state, kb, jnp.asarray(valid))
        h.update(np.asarray(res.dup).tobytes())
        h.update(np.asarray(res.inserted).tobytes())
    h.update(np.asarray(state.bits).tobytes())
    h.update(np.asarray(state.load).tobytes())
    h.update(np.asarray(state.position).tobytes())
    h.update(np.asarray(jax.random.key_data(state.rng)).tobytes())
    if state.ring is not None:
        h.update(np.asarray(state.ring.events).tobytes())
        h.update(np.asarray(state.ring.slot).tobytes())
    return h.hexdigest()[:16]
