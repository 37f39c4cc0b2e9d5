"""The sharded cell's comparison on four virtual CPU devices: a sound run
is correct, and each fault the cell can have makes it not correct — a
step that returns its state unchanged, half of each batch left out, the
exchange between chips left out, an answer altered where it is produced.
The cell (``rlbsbf-2gb-4chip.stream-u60``) is prepared in its files and
not yet listed in BENCHMARK.json. Four devices need a fresh process (the
device count is fixed when JAX starts)."""

import json
import os
import subprocess
import sys

import pytest

from chipbench import harness

SCRIPT = r'''
import json, sys, time
import jax, jax.numpy as jnp
from chipbench import harness
from chipbench.tests.conftest import small_cell
from repro.dedup import sharded as sharded_mod

NAME = "rlbsbf-2gb-4chip.stream-u60"
fault = sys.argv[1]
if fault == "no_exchange":
    jax.lax.all_to_all = lambda x, *a, **k: x
elif fault in ("unchanged_state", "half_batch"):
    make = sharded_mod.make_batched_step

    def broken(cfg):
        step = make(cfg)

        def run(st, keys, valid):
            if fault == "half_batch":
                valid = valid & (jnp.arange(keys.shape[0]) < keys.shape[0] // 2)
            new, res = step(st, keys, valid)
            return (st if fault == "unchanged_state" else new), res
        return run
    sharded_mod.make_batched_step = broken
elif fault == "altered_answer":
    orig = sharded_mod.ShardedDedup.run_stream

    def run_stream(self, state, keys):
        state, dup, ovf = orig(self, state, keys)
        return state, dup.at[0].set(~dup[0]), ovf
    sharded_mod.ShardedDedup.run_stream = run_stream
r, _ = harness.run_cell(NAME, 2 ** 31 + 9, 0.3, False,
                        t_start=time.perf_counter(), devices=jax.devices(),
                        cell=small_cell(NAME))
print(json.dumps({"correct": r["correct"], "checks": r["checks"],
                  "count": r["device"]["count"]}))
'''


def run(fault: str) -> dict:
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join([harness.ROOT,
                                           os.path.join(harness.ROOT, "src")]))
    p = subprocess.run([sys.executable, "-c", SCRIPT, fault], env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


@pytest.mark.subprocess
def test_sound_sharded_run_is_correct():
    r = run("none")
    assert r["count"] == 4
    assert r["correct"], r["checks"]


@pytest.mark.subprocess
@pytest.mark.parametrize("fault", ["unchanged_state", "half_batch",
                                   "no_exchange", "altered_answer"])
def test_broken_sharded_path_is_not_correct(fault):
    r = run(fault)
    assert not r["correct"], r["checks"]
