"""Dynamic-batching serving front-end (DESIGN.md §5.2).

    PYTHONPATH=src python examples/serving_frontend.py

The paper's request-shaped applications (URL probes, online transactions —
Section 1) are many CONCURRENT small requests, while the engine underneath
is fastest fed wide fixed-shape batches. ``ServeFrontend`` is the adapter:
concurrent ``submit()`` calls coalesce into micro-batches padded to fixed
BUCKETS (one jit trace per bucket, ever), one donated engine step yields
the dedup verdicts, a vectorized response cache answers repeats without
recomputing, and admission control sheds overload with an explicit
``"retry"`` verdict instead of queueing without bound.

Below: 32 closed-loop clients drive a zipf-heavy request mix through the
front-end; then the same requests replay one-at-a-time through the
synchronous ``ServeSession`` loop, and the recorded admitted schedule is
re-run through a fresh synchronous engine to prove verdict parity.
"""

import asyncio
import time

import numpy as np

from repro.core import DedupConfig
from repro.data.streams import zipf_stream
from repro.serve import ServeFrontend, ServeSession, replay_schedule

N = 6_000
N_CLIENTS = 32
BUCKETS = (64, 256)

cfg = DedupConfig.for_variant("rlbsbf", memory_bits=1 << 20, batch_size=64)


def score_fn(batch):
    """Stands in for the expensive per-request model (DESIGN.md §5)."""
    return np.asarray(batch["key"], np.float64) * 2.0


rng = np.random.default_rng(0)
hot, _ = zipf_stream(N * 7 // 10, universe=800, a=1.2, seed=0)
cold = rng.integers(0, 1 << 32, N - hot.size, dtype=np.uint64).astype(np.uint32)
keys = np.concatenate([hot, cold])[rng.permutation(N)]


async def drive():
    fe = ServeFrontend(cfg, score_fn, buckets=BUCKETS, max_live_batches=4,
                       flush_timeout=2e-3, record_schedule=True)

    async def client(c):
        for k in keys[c::N_CLIENTS]:
            res = await fe.submit(int(k))
            if res.verdict == "ok":
                assert float(res.value) == 2.0 * int(k)   # answers stay exact

    async with fe:
        t0 = time.perf_counter()
        await asyncio.gather(*(client(c) for c in range(N_CLIENTS)))
        dt = time.perf_counter() - t0
    return fe, dt


fe, dt = asyncio.run(drive())
st = fe.stats()
print(f"frontend: {st['completed']:,} served in {dt:.2f}s "
      f"({st['completed'] / dt:,.0f} qps), {st['batches']} micro-batches, "
      f"mean fill {st['mean_fill']:.0f}")
print(f"  shed rate {st['shed_rate']:.3f}   cache hit rate "
      f"{st['cache_hit_rate']:.3f}   dup rate {st['dup_rate']:.3f}")
print(f"  compiled engine traces: {st['process_cache']} "
      f"(<= one per bucket x donation flag — the §5.2 no-retrace contract)")
print(f"  mean queue wait {st['queue_wait_ms']:.2f} ms; stage spans "
      f"(mean / longest ms, DESIGN.md §5.2):")
for stage, v in st["stages"].items():
    print(f"    {stage:<13}{v['mean_ms']:8.3f} {v['max_ms']:9.3f}")

# the pre-frontend story: one synchronous serve() call per request
sess = ServeSession(cfg, score_fn, buckets=BUCKETS)
t0 = time.perf_counter()
for k in keys:
    sess.serve({"key": np.asarray([k], np.uint32)})
dt_seq = time.perf_counter() - t0
print(f"per-request loop: {N / dt_seq:,.0f} qps -> coalescing speedup "
      f"{(st['completed'] / dt) / (N / dt_seq):.1f}x")

# verdict parity: replay the recorded admitted schedule synchronously
digest = replay_schedule(cfg, fe.executor.schedule)
assert digest == fe.executor.digest()
print("schedule-replay parity: async verdicts == synchronous replay "
      "(DESIGN.md §5.2)")
