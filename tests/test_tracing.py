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


_SHARDED_SCOPES = """
import contextlib, hashlib, json, re
import jax, jax.numpy as jnp, numpy as np
from repro import tracing
from repro.compat import make_mesh, set_mesh
from repro.core import DedupConfig
from repro.dedup import ShardedDedup, ShardedDedupConfig
from repro.dedup import sharded as sharded_mod

STAGES = ("route", "exchange", "step", "return")
META = re.compile(r", metadata=\\{[^}]*\\}")
mesh = make_mesh((4, 1), ("data", "model"))
cfg = DedupConfig.for_variant("rlbsbf", memory_bits=1 << 16, batch_size=256,
                              layout="planes")
keys = jnp.asarray(np.random.default_rng(3).integers(
    0, 2 ** 32, 4 * 256 + 100, dtype=np.uint32))


def instructions(text):
    keep = [l for l in text.splitlines()
            if l.startswith(("%", "ENTRY", " ", "}", "HloModule"))]
    return META.sub("", "\\n".join(keep))


def compiled(pipeline):
    sd = ShardedDedup(ShardedDedupConfig(base=cfg, pipeline=pipeline), mesh)
    kb = jax.ShapeDtypeStruct((3, 256), jnp.uint32)
    vb = jax.ShapeDtypeStruct((3, 256), jnp.bool_)
    with set_mesh(mesh):
        return sd._make_stream(64).lower(sd.init(), kb, vb).compile(
            ).as_text()


out = {}
for name, pipeline in (("pipelined", True), ("serial", False)):
    sd = ShardedDedup(ShardedDedupConfig(base=cfg, pipeline=pipeline), mesh)
    st = sd.init()
    before = tracing.snapshot()
    with set_mesh(mesh):
        for _ in range(2):
            st, dup, ovf = sd.run_stream(st, keys)
    dup.block_until_ready()
    after = tracing.snapshot()
    h = hashlib.sha256()
    for x in (dup, st.bits, st.load, st.position, st.rng, ovf):
        h.update(np.asarray(x).tobytes())
    text = compiled(pipeline)
    ops = " ".join(re.findall(r'op_name="([^"]*)"', text))
    real = sharded_mod.scope
    sharded_mod.scope = lambda name: contextlib.nullcontext()
    bare = compiled(pipeline)
    sharded_mod.scope = real
    out[name] = {
        "digest": h.hexdigest()[:16],
        "stages": [s for s in STAGES if f"dedup.sharded.{s}/" in ops],
        "bare_stages": [s for s in STAGES if f"dedup.sharded.{s}" in bare],
        "same_instructions": instructions(text) == instructions(bare),
        "spans": {s: after[f"dedup.sharded.{s}"]["count"]
                  - before.get(f"dedup.sharded.{s}", {"count": 0})["count"]
                  for s in ("pad", "launch")},
    }
print(json.dumps(out))
"""

# the sharded stream's verdicts and final state (filter words, load,
# position, rng, overflow) on the parent of the device scopes: the scopes
# are metadata, so these stay bit-identical
SHARDED_DIGESTS = {"pipelined": "ce8b6ff0d76a64d9",
                   "serial": "ce8b6ff0d76a64d9"}


@pytest.fixture(scope="module")
def sharded_scopes():
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH="src",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(_SHARDED_SCOPES)],
        capture_output=True, text=True, env=env, cwd=root, timeout=600)
    assert out.returncode == 0, out.stderr[-4000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


@pytest.mark.subprocess
@pytest.mark.parametrize("path", ["pipelined", "serial"])
def test_sharded_scopes_name_all_four_stages(sharded_scopes, path):
    """Each stage of the static sharded body lands in the compiled stream's
    ``op_name`` metadata under its ``dedup.sharded.*`` scope."""
    r = sharded_scopes[path]
    assert r["stages"] == ["route", "exchange", "step", "return"]
    assert r["bare_stages"] == []


@pytest.mark.subprocess
@pytest.mark.parametrize("path", ["pipelined", "serial"])
def test_sharded_scopes_change_no_compiled_instruction(sharded_scopes,
                                                       path):
    """With metadata stripped, the compiled stream with scopes and with
    each scope a no-op are the same program."""
    assert sharded_scopes[path]["same_instructions"]


@pytest.mark.subprocess
def test_sharded_run_stream_makes_one_pad_and_launch_span_per_call(
        sharded_scopes):
    for path in ("pipelined", "serial"):
        assert sharded_scopes[path]["spans"] == {"pad": 2, "launch": 2}


@pytest.mark.subprocess
@pytest.mark.parametrize("path", ["pipelined", "serial"])
def test_sharded_stream_digest_is_pinned(sharded_scopes, path):
    assert sharded_scopes[path]["digest"] == SHARDED_DIGESTS[path]
