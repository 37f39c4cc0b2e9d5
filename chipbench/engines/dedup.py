"""One ``Dedup`` engine on one chip (``engine: dedup``): its filled
starting state and its entry point ``run_stream``."""

from __future__ import annotations

import numpy as np

from .. import fill as fillmod
from ..reference import cell_count, words_digest


def dedup_config(spec: dict):
    from repro.core import DedupConfig
    return DedupConfig(**spec).validate()


class System:
    chips = 1

    def __init__(self, config: dict, devices):
        from repro.core import Dedup
        self.config = config
        self.spec = config["dedup"]
        self.cfg = dedup_config(self.spec)
        self.engine = Dedup(self.cfg)
        self.device = devices[0]
        self.batch = int(self.spec["batch_size"])
        self.n_shards = 1

    def initial_state(self, seed: int):
        import jax
        from repro.core.state import FilterState
        rows, d = fillmod.rows_planes(self.spec)
        words = cell_count(self.spec) // 32
        planes = fillmod.packed_planes(fillmod.row_salts(seed, 0, rows),
                                       words, fillmod.config_cuts(self.config),
                                       d, self.device)
        load = fillmod.nonzero_count(planes)
        bits = planes[0] if d == 1 else planes
        # every leaf committed to the chip, as the engine's outputs are, so
        # the first call and the later ones share one compiled program
        put = lambda x: jax.device_put(x, self.device)  # noqa: E731
        return FilterState(
            bits=bits,
            position=put(np.int32(self.config["fill"]["position"])),
            load=put(load),
            rng=put(np.asarray(fillmod.start_rng(seed), np.uint32)))

    def run_chunk(self, state, keys: np.ndarray):
        import jax.numpy as jnp
        state, dup = self.engine.run_stream(state, jnp.asarray(keys))
        return state, dup, None

    def digests(self, state):
        """[(uint32 digest of the filter words, per-row load)]."""
        bits = state.bits
        if bits.ndim == 2:
            bits = bits[None]                     # (planes, rows, W)
        return [(int(words_digest(bits)), np.asarray(state.load).tolist())]
