"""Distributed dedup across 8 (simulated) devices — the production layout.

    PYTHONPATH=src python examples/sharded_dedup_multidevice.py

Key-space-partitioned RLBSBF filters over a (data=4, model=2) mesh with
MoE-style all-to-all routing (DESIGN.md §4): every device ingests a slice of
the stream, routes keys to their owner shard, and the ensemble behaves
bit-identically to one filter with the aggregate memory. The whole stream is
ingested with ONE dispatch — ``ShardedDedup.run_stream`` scans the
shard-mapped step over batches with the sharded state donated in place — and
all version-sensitive jax surfaces go through ``repro.compat``. The eight
devices are simulated on the host CPU (``JAX_PLATFORMS=cpu``), so the
example never contends for an accelerator. Run on a real pod, the
same code spans (pod, data, model) = 512 chips — see repro/launch/dryrun.py
for the compile-level proof.
"""

import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
os.environ["JAX_PLATFORMS"] = "cpu"

import jax                                                    # noqa: E402
import jax.numpy as jnp                                       # noqa: E402
import numpy as np                                            # noqa: E402

from repro.compat import make_mesh, set_mesh                  # noqa: E402
from repro.core import Dedup, DedupConfig                     # noqa: E402
from repro.dedup import (ShardedDedup, ShardedDedupConfig,    # noqa: E402
                         StreamMetrics, truth_from_stream)

BATCH = 8192
STEPS = 40
MEMORY = 1 << 20

mesh = make_mesh((4, 2), ("data", "model"))
print(f"mesh: {dict(mesh.shape)} -> {len(jax.devices())} devices")

cfg = DedupConfig.for_variant("rlbsbf", memory_bits=MEMORY, batch_size=BATCH)
sd = ShardedDedup(ShardedDedupConfig(base=cfg), mesh)
print(f"{sd.n_shards} shards x {sd.local_cfg.s} bits x k={sd.local_cfg.k}")

rng = np.random.default_rng(0)
keys = rng.integers(0, 120_000, STEPS * BATCH).astype(np.uint32)
metrics = StreamMetrics()
with set_mesh(mesh):
    state, dup, ovf = sd.run_stream(sd.init(), jnp.asarray(keys))

dup = np.asarray(dup)
truth = truth_from_stream(keys)
metrics.update(dup, truth, load=state.load, s_bits=sd.n_shards *
               sd.local_cfg.k * sd.local_cfg.s, overflow=ovf)
m = metrics.summary()
print(f"sharded  : FPR={m['fpr']:.4f} FNR={m['fnr']:.4f} "
      f"overflow={m['overflow']} "
      f"(one dispatch for {STEPS} batches; scan cache="
      f"{sd.stream_cache_size()})")

single = Dedup(DedupConfig.for_variant("rlbsbf", memory_bits=MEMORY,
                                       batch_size=BATCH))
_, dup1 = single.run_stream(single.init(), jnp.asarray(keys))
dup1 = np.asarray(dup1)
print(f"1 filter : FPR={(dup1 & ~truth).sum()/(~truth).sum():.4f} "
      f"FNR={(~dup1 & truth).sum()/truth.sum():.4f}  (same aggregate memory)")
