"""The program's spans and counters (``repro.tracing``, DESIGN §7): the
registry's arithmetic, one ``dedup.serve.step`` per micro-batch and one
``dedup.serve.queue_wait`` per request taken, and one
``dedup.stream.enqueue`` per ``run_stream`` call on ``Dedup`` and on
``ShardedDedup`` over four devices. Counts are read as differences of two
snapshots: the registry is the process's."""

import asyncio
import json
import os
import subprocess
import sys
import textwrap

import jax.numpy as jnp
import numpy as np
import pytest

from repro import tracing
from repro.core import DedupConfig
from repro.core.engine import Dedup
from repro.serve import ServeFrontend


def _delta(after, before, name):
    a = after.get(name, {"count": 0, "total_s": 0.0})
    b = before.get(name, {"count": 0, "total_s": 0.0})
    return a["count"] - b["count"], a["total_s"] - b["total_s"]


def test_registry_counts_totals_and_longest():
    before = tracing.snapshot()
    for _ in range(3):
        with tracing.span("dedup.test.block", batch=1):
            pass
    tracing.add("dedup.test.counter", 0.5, 4, 0.25)
    tracing.add("dedup.test.counter", 0.125)
    after = tracing.snapshot()
    assert _delta(after, before, "dedup.test.block")[0] == 3
    n, total = _delta(after, before, "dedup.test.counter")
    assert (n, total) == (5, pytest.approx(0.625))
    assert after["dedup.test.counter"]["max_s"] >= 0.25
    after["dedup.test.counter"]["count"] = -1      # a copy, not the registry
    assert tracing.snapshot()["dedup.test.counter"]["count"] >= 5


def test_a_span_that_raises_is_still_counted():
    before = tracing.snapshot()
    with pytest.raises(KeyError):
        with tracing.span("dedup.test.raises"):
            raise KeyError("x")
    assert _delta(tracing.snapshot(), before, "dedup.test.raises")[0] == 1


def _serve(n_requests, buckets=(8, 32)):
    cfg = DedupConfig.for_variant("rlbsbf", memory_bits=1 << 14,
                                  batch_size=8)

    async def drive():
        fe = ServeFrontend(cfg, lambda b: np.asarray(b["key"], np.float64),
                           buckets=buckets, flush_timeout=1e-3,
                           queue_limit=n_requests)
        async with fe:
            res = await asyncio.gather(*(fe.submit(k % 50)
                                         for k in range(n_requests)))
        return fe, res

    return asyncio.run(drive())


def test_serving_counts_one_step_per_batch_and_one_wait_per_request():
    before = tracing.snapshot()
    fe, res = _serve(200)
    after = tracing.snapshot()
    ex = fe.executor
    assert all(r.verdict == "ok" for r in res)
    assert ex.n_batches > 1
    for stage in ("step", "post", "take", "admit", "dispatch",
                  "verdict_wait", "resolve"):
        assert _delta(after, before, f"dedup.serve.{stage}")[0] == \
            ex.n_batches, stage
    n, total = _delta(after, before, "dedup.serve.queue_wait")
    assert n == ex.fill_sum == 200 and total > 0
    st = fe.stats()
    assert st["queue_wait_ms"] == st["stages"]["queue_wait"]["mean_ms"] > 0
    assert st["stages"]["step"]["max_ms"] >= st["stages"]["step"]["mean_ms"]


def test_run_stream_makes_one_enqueue_span_per_call():
    eng = Dedup(DedupConfig.for_variant("rlbsbf", memory_bits=1 << 14,
                                        batch_size=64))
    st = eng.init()
    before = tracing.snapshot()
    for n in (640, 640, 100):
        st, dup = eng.run_stream(st, jnp.arange(n, dtype=jnp.uint32))
    dup.block_until_ready()
    assert _delta(tracing.snapshot(), before,
                  "dedup.stream.enqueue")[0] == 3


_SHARDED = """
import json
import jax.numpy as jnp
from repro import tracing
from repro.compat import make_mesh, set_mesh
from repro.core import DedupConfig
from repro.dedup import ShardedDedup, ShardedDedupConfig
mesh = make_mesh((2, 2), ("data", "model"))
cfg = DedupConfig.for_variant("rlbsbf", memory_bits=1 << 15, batch_size=256)
sd = ShardedDedup(ShardedDedupConfig(base=cfg), mesh)
st = sd.init()
with set_mesh(mesh):
    for _ in range(2):
        st, dup, ovf = sd.run_stream(st, jnp.arange(1024, dtype=jnp.uint32))
dup.block_until_ready()
print(json.dumps({"devices": len(mesh.devices.flat),
                  "enqueue": tracing.snapshot()["dedup.stream.enqueue"]}))
"""


@pytest.mark.subprocess
def test_sharded_run_stream_makes_one_enqueue_span_per_call():
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH="src",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(_SHARDED)],
                         capture_output=True, text=True, env=env, cwd=root,
                         timeout=300)
    assert out.returncode == 0, out.stderr[-4000:]
    r = json.loads(out.stdout.strip().splitlines()[-1])
    assert r["devices"] == 4
    assert r["enqueue"]["count"] == 2
    assert r["enqueue"]["max_s"] <= r["enqueue"]["total_s"]
