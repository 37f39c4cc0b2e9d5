"""The comparison that decides ``correct``, on the CPU at a small size:
a sound run of each single-chip cell passes, and a run whose timed path is
broken underneath fails — a step that returns its state unchanged, half of
each batch left out, an answer altered where it is produced. The control
(the reference with its guarantee broken, in the program's place) fails
the same numbers."""

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import harness, replay
from chipbench.tests.conftest import small_cell
from repro.core import engine as engine_mod
from repro.serve import frontend as frontend_mod

STREAM_CELLS = ("rlbsbf-512mb.stream-u60", "sbf-128mb.stream-u60")
SERVE = "rlbsbf-512mb.serve-zipf"


def run(name, seconds=0.3, seed=2 ** 31 + 5):
    return harness.run_cell(name, seed, seconds, False,
                            t_start=time.perf_counter(),
                            devices=jax.devices(), cell=small_cell(name))[0]


def _unchanged_state(self, state, kb, vb):
    def body(st, xs):
        _, res = self._step(st, *xs)
        return st, res.dup
    return jax.lax.scan(body, state, (kb, vb))


def _half_batch(self, state, kb, vb):
    half = jnp.arange(kb.shape[1]) < kb.shape[1] // 2

    def body(st, xs):
        st, res = self._step(st, xs[0], xs[1] & half)
        return st, res.dup
    return jax.lax.scan(body, state, (kb, vb))


def _altered_answer(orig):
    def run_stream(self, state, keys):
        state, dup = orig(self, state, keys)
        return state, dup.at[0].set(~dup[0])
    return run_stream


@pytest.mark.parametrize("name", STREAM_CELLS)
def test_sound_stream_run_is_correct(name):
    r = run(name)
    assert r["correct"], r["checks"]
    assert r["attempted"] > 0 and r["failed"] == 0
    assert set(r["metrics"]) == {"keys_per_s", "setup_s"}
    assert list(r)[-1] == "checks"


@pytest.mark.parametrize("name", STREAM_CELLS)
@pytest.mark.parametrize("fault", ["unchanged_state", "half_batch",
                                   "altered_answer"])
def test_broken_stream_path_is_not_correct(name, fault, monkeypatch):
    if fault == "altered_answer":
        monkeypatch.setattr(engine_mod.Dedup, "run_stream",
                            _altered_answer(engine_mod.Dedup.run_stream))
    else:
        monkeypatch.setattr(engine_mod.Dedup, "_stream_impl",
                            {"unchanged_state": _unchanged_state,
                             "half_batch": _half_batch}[fault])
    r = run(name)
    assert not r["correct"], r["checks"]


def test_sound_serve_run_is_correct():
    r = run(SERVE, seconds=0.5)
    assert r["correct"], r["checks"]
    assert {"verdict_p50_ms", "verdict_p95_ms", "setup_s"} <= set(
        r["metrics"])


def _serve_fault(fault):
    orig = frontend_mod.MicroBatchExecutor.dedup_chunk

    def dedup_chunk(self, keys, tenants=None):
        if fault == "unchanged_state":
            before = self.state
            self.engine, eng = None, self.engine
            try:
                st, res = eng.process_padded(before, keys,
                                             width=self.bucket_for(len(keys)))
            finally:
                self.engine = eng
            self.schedule.append((self.bucket_for(len(keys)), keys.copy()))
            self.n_batches += 1
            self.fill_sum += len(keys)
            return np.asarray(res.dup)
        if fault == "half_batch":
            h = max(1, len(keys) // 2)
            dup = orig(self, keys[:h], None if tenants is None
                       else tenants[:h])
            return np.concatenate([dup, np.zeros(len(keys) - h, bool)])
        dup = orig(self, keys, tenants).copy()
        dup[0] = ~dup[0]
        return dup
    return dedup_chunk


@pytest.mark.parametrize("fault", ["unchanged_state", "half_batch",
                                   "altered_answer"])
def test_broken_serve_path_is_not_correct(fault, monkeypatch):
    monkeypatch.setattr(frontend_mod.MicroBatchExecutor, "dedup_chunk",
                        _serve_fault(fault))
    r = run(SERVE, seconds=0.5)
    assert not r["correct"], r["checks"]


@pytest.mark.parametrize("name", STREAM_CELLS)
def test_control_fails_on_a_stream(name):
    """The control replays the same keys as the reference and must differ
    from it in at least one compared number."""
    cell = small_cell(name)
    rng = np.random.default_rng(7)
    batch = cell["config"]["dedup"]["batch_size"]
    keys = rng.integers(0, 1 << 20, size=(64, batch), dtype=np.uint32)
    ref = replay.replay(cell["config"], 11, keys)
    ctl = replay.replay(cell["config"], 11, keys, control=True)
    numbers = replay.compare(ctl[0], ctl[1], ref[0], ref[1])
    assert any(v > 0 for v in numbers.values()), numbers


def test_control_fails_on_a_schedule():
    cell = small_cell(SERVE)
    rng = np.random.default_rng(8)
    schedule = [(w, rng.integers(0, 1 << 20, size=int(rng.integers(1, w)),
                                 dtype=np.uint32))
                for w in rng.choice([64, 256, 1024], size=96)]
    ref = replay.replay_schedule(cell["config"], 12, schedule)
    ctl = replay.replay_schedule(cell["config"], 12, schedule, control=True)
    numbers = replay.compare(np.concatenate(ctl[0]), ctl[1],
                             np.concatenate(ref[0]), ref[1])
    assert any(v > 0 for v in numbers.values()), numbers
