"""``ShardedDedup`` over a mesh of the cell's chips (``engine: sharded``):
static hash routing, the pipelined scan."""

from __future__ import annotations

import numpy as np

from .. import fill as fillmod
from ..reference import cell_count, words_digest
from .dedup import dedup_config


class System:
    def __init__(self, config: dict, devices):
        from repro.compat import make_mesh
        from repro.dedup import ShardedDedup, ShardedDedupConfig
        self.config = config
        self.spec = config["dedup"]
        self.cfg = dedup_config(self.spec)
        sh = config["sharded"]
        self.chips = int(config["chips"])
        self.mesh = make_mesh(tuple(sh["mesh"]), ("data", "model"),
                              devices=devices[:self.chips])
        self.scfg = ShardedDedupConfig(
            base=self.cfg, capacity_factor=float(sh["capacity_factor"]),
            pipeline=bool(sh["pipeline"]))
        self.engine = ShardedDedup(self.scfg, self.mesh)
        self.batch = int(self.spec["batch_size"])
        self.n_shards = self.engine.n_shards
        self.cap = self.scfg.capacity(self.batch // self.n_shards, self.mesh)

    def initial_state(self, seed: int):
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.core.state import FilterState
        rows, d = fillmod.rows_planes(self.spec)
        if d != 1:
            raise ValueError("the sharded cell runs a bitset variant")
        n = self.n_shards
        words = cell_count(self.spec, n) // 32
        cuts = fillmod.config_cuts(self.config)
        shard = NamedSharding(self.mesh, P(("data", "model")))
        parts = []
        for j, dev in enumerate(self.mesh.devices.reshape(-1)):
            parts.append(fillmod.packed_planes(
                fillmod.row_salts(seed, j, rows), words, cuts, 1, dev)[0])
        bits = jax.make_array_from_single_device_arrays(
            (n, rows, words), shard, [p[None] for p in parts])
        load = np.stack([np.asarray(fillmod.nonzero_count(p[None]))
                         for p in parts])
        rng = np.stack([fillmod.start_rng(seed, j) for j in range(n)])
        pos = int(self.config["fill"]["position"])
        return FilterState(
            bits=bits,
            position=jax.device_put(jnp.full((n,), pos, jnp.int32), shard),
            load=jax.device_put(load, shard),
            rng=jax.device_put(jnp.asarray(rng, jnp.uint32), shard))

    def run_chunk(self, state, keys: np.ndarray):
        import jax.numpy as jnp
        from repro.compat import set_mesh
        with set_mesh(self.mesh):
            state, dup, ovf = self.engine.run_stream(state, jnp.asarray(keys))
        return state, dup, ovf

    def digests(self, state):
        out = []
        for j, sh in enumerate(sorted(state.bits.addressable_shards,
                                      key=lambda s: s.index[0].start)):
            out.append((int(words_digest(sh.data)),
                        np.asarray(state.load)[j].tolist()))
        return out
