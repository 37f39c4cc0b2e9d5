"""The program's spans as the benchmark reads them: they land on the
profiler's clock with their metadata as stats, ``tools/stages.py`` puts
each idle gap on the innermost program span open over it before any
benchmark span, and each reader of a program metric gives its value from
the registry's counts and nothing where they are missing or disagree with
the loop's own."""

import asyncio
import glob
import sys

import jax
import numpy as np
import pytest

from chipbench import harness
from chipbench.tools import stages
from repro.core import DedupConfig
from repro.serve import ServeFrontend

SERVE_SPANS = {"idle", "flush", "admit", "take", "step", "post", "score",
               "resolve", "dispatch", "verdict_wait"}


def test_a_gap_goes_to_the_program_span_over_a_later_benchmark_span():
    busy = np.array([[0.0, 100.0], [300.0, 400.0], [700.0, 800.0],
                     [950.0, 1000.0]])
    program = [("dedup.serve.flush", 90.0, 260.0, {"batch": 3})]
    host = [("cb.loop", 150.0, 100.0), ("cb.send", 500.0, 150.0)]
    gaps = stages.gaps_by_program(busy, program, host, 0.0, 1000.0)
    # 100-300 lies under the flush (and a cb.loop that starts later);
    # 400-700 under cb.send alone; 800-950 under no span at all
    assert gaps == pytest.approx({"dedup.serve.flush": 200e-9,
                                  "cb.send": 300e-9,
                                  stages.NO_SPAN: 150e-9})


def test_the_innermost_program_span_takes_the_gap():
    busy = np.array([[0.0, 10.0], [90.0, 100.0]])
    program = [("dedup.serve.post", 5.0, 90.0, {}),
               ("dedup.serve.score", 20.0, 60.0, {})]
    assert stages.gaps_by_program(busy, program, [], 0.0, 100.0) == \
        pytest.approx({"dedup.serve.score": 80e-9})


def test_serving_spans_land_on_the_trace_with_the_batch_as_a_stat(tmp_path):
    cfg = DedupConfig.for_variant("rlbsbf", memory_bits=1 << 14,
                                  batch_size=8)

    async def drive():
        fe = ServeFrontend(cfg, lambda b: np.asarray(b["key"], np.float64),
                           buckets=(8, 32), flush_timeout=1e-3,
                           queue_limit=256)
        async with fe:
            await asyncio.gather(*(fe.submit(k) for k in range(256)))
        return fe

    jax.profiler.start_trace(str(tmp_path))
    try:
        fe = asyncio.run(drive())
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    spans = stages.program_spans(path)
    names = {n for n, *_ in spans}
    assert {f"dedup.serve.{s}" for s in SERVE_SPANS} <= names
    assert not any("#" in n or "batch" in n for n in names)
    batches = {st["batch"] for n, *_, st in spans if n == "dedup.serve.step"}
    assert batches == set(range(fe.executor.n_batches))
    for n, _, _, st in spans:
        assert ("batch" in st) == (n != "dedup.serve.idle"), n


def _stat(count, total_s, max_s=0.0):
    return {"count": count, "total_s": total_s, "max_s": max_s}


SERVE_READERS = [
    ("queue_wait_ms.serve", "dedup.serve.queue_wait", "fill"),
    ("step_wall_ms.serve", "dedup.serve.step", "batches"),
    ("post_ms.serve", "dedup.serve.post", "batches"),
]


@pytest.mark.parametrize("metric,name,key", SERVE_READERS)
def test_serving_readers_read_the_window_registry(metric, name, key):
    read = harness.metric_reader(metric)
    ctx = {key: 40, "program": {name: _stat(40, 0.2, 0.01)}}
    assert read(ctx) == pytest.approx(5.0)
    assert read(dict(ctx, program={})) is None
    assert read(dict(ctx, **{key: 41})) is None        # not this window
    assert read(dict(ctx, **{key: 0})) is None


STREAM_CTX = {"steps": 24, "traffic": {"arrival": {"batches_per_chunk": 8}}}


def test_enqueue_reader_reads_the_window_registry():
    read = harness.metric_reader("enqueue_ms.stream")
    name = "dedup.stream.enqueue"
    assert read(dict(STREAM_CTX, program={name: _stat(3, 0.006)})) == \
        pytest.approx(2.0)
    assert read(dict(STREAM_CTX, program={})) is None
    assert read(dict(STREAM_CTX, steps=0, program={name: _stat(3, 1)})) \
        is None


def test_enqueue_reader_leaves_out_the_set_up_chunk(monkeypatch):
    """Read from the process's registry, the count holds the set-up chunk
    too, the longest call: the mean is over the window's chunks."""
    import repro.tracing
    read = harness.metric_reader("enqueue_ms.stream")
    reg = {"dedup.stream.enqueue": _stat(4, 2.006, 2.0)}
    monkeypatch.setattr(repro.tracing, "snapshot", lambda: reg)
    assert read(STREAM_CTX) == pytest.approx(2.0)
    reg["dedup.stream.enqueue"] = _stat(5, 2.006, 2.0)   # another run's too
    assert read(STREAM_CTX) is None


@pytest.mark.parametrize("metric", ["queue_wait_ms.serve",
                                    "step_wall_ms.serve", "post_ms.serve",
                                    "enqueue_ms.stream"])
def test_a_program_without_spans_reads_as_nothing(metric, monkeypatch):
    monkeypatch.setitem(sys.modules, "repro.tracing", None)
    ctx = dict(STREAM_CTX, fill=40, batches=4)
    assert harness.metric_reader(metric)(ctx) is None
