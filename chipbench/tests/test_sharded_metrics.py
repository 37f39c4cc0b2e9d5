"""The four-chip cell's metrics: ``rlbsbf-2gb-4chip.stream-u60`` resolves
to the stream metrics and the sharded dispatch's own, ``launch_ms.sharded``
reads the program's ``dedup.sharded.launch`` span (and nothing where the
program has none), a sound run on four virtual CPU devices reports it, and
``tools/sharded_stages.py`` splits op time by the device scopes."""

import json
import os
import subprocess
import sys
import textwrap

import pytest

from chipbench import harness
from chipbench.tools import sharded_stages

NAME = "rlbsbf-2gb-4chip.stream-u60"
LAYER = {"step_ms.stream", "step_roofline.stream", "host_io_share.stream",
         "idle_share.stream", "enqueue_ms.stream", "collective_ms.sharded",
         "launch_ms.sharded"}
CTX = {"steps": 24, "traffic": {"arrival": {"batches_per_chunk": 8}}}
SPAN = "dedup.sharded.launch"


def _stat(count, total_s, max_s=0.0):
    return {"count": count, "total_s": total_s, "max_s": max_s}


def test_cell_resolves_to_stream_and_sharded_metrics():
    cell = harness.resolve(NAME)
    assert cell["workload"]["chips"] == 4
    assert {m["name"] for m in cell["end_to_end"]} == {"keys_per_s",
                                                       "setup_s"}
    assert {m["name"] for m in cell["per_layer"]} == LAYER
    for m in cell["per_layer"]:
        if m["name"].endswith(".sharded"):
            assert m["layer"] == "sharded dispatch"
            assert m["workloads"] == [NAME]


def test_launch_reader_reads_the_window_registry():
    read = harness.metric_reader("launch_ms.sharded")
    assert read(dict(CTX, program={SPAN: _stat(3, 0.009)})) == \
        pytest.approx(3.0)
    assert read(dict(CTX, program={})) is None
    assert read(dict(CTX, program={SPAN: _stat(4, 0.009)})) is None
    assert read(dict(CTX, steps=0, program={SPAN: _stat(3, 1.0)})) is None


def test_launch_reader_leaves_out_the_set_up_chunk(monkeypatch):
    import repro.tracing
    read = harness.metric_reader("launch_ms.sharded")
    reg = {SPAN: _stat(4, 3.009, 3.0)}
    monkeypatch.setattr(repro.tracing, "snapshot", lambda: reg)
    assert read(CTX) == pytest.approx(3.0)
    reg[SPAN] = _stat(5, 3.009, 3.0)
    assert read(CTX) is None
    reg.clear()                              # a program without the span
    assert read(CTX) is None


def test_launch_reader_without_the_tracing_module(monkeypatch):
    monkeypatch.setitem(sys.modules, "repro.tracing", None)
    assert harness.metric_reader("launch_ms.sharded")(CTX) is None


HLO = textwrap.dedent("""\
    %body (p: u32[8]) -> u32[8] {
      %fusion.3 = u32[8]{0} fusion(%p), kind=kLoop, calls=%f, metadata={op_name="jit(stream)/while/body/shard_map/dedup.sharded.step/add"}
      %all-to-all.1 = u32[8]{0} all-to-all(%fusion.3), metadata={op_name="jit(stream)/while/body/shard_map/dedup.sharded.exchange/all_to_all"}
      ROOT %copy.2 = u32[8]{0} copy(%all-to-all.1), metadata={op_name="jit(stream)/while/body/shard_map/squeeze"}
    }
    """)


def test_scopes_are_read_from_compiled_op_names():
    assert sharded_stages.scopes_from_hlo(HLO) == {
        "fusion.3": "step", "all-to-all.1": "exchange"}


def test_op_time_splits_by_scope_within_the_stream_program():
    by_name = sharded_stages.scopes_from_hlo(HLO)
    modules = [("jit_stream(7)", 0.0, 1000.0), ("jit_slice(2)", 1000.0,
                                                 100.0)]
    ops = [("%while.1 = (u32[8]) while(...)", 0.0, 900.0, None),
           ("%fusion.3 = u32[8]{0} fusion(%p)", 100.0, 300.0, None),
           ("%all-to-all.1 = u32[8]{0} all-to-all(%fusion.3)", 400.0,
            200.0, None),
           ("%copy.2 = u32[8]{0} copy(%all-to-all.1)", 600.0, 100.0,
            None),
           ("%fusion.9 = u32[8]{0} fusion(%q)", 700.0, 100.0, "route"),
           ("%slice.1 = u32[4]{0} slice(%x)", 1000.0, 50.0, None)]
    sec = sharded_stages.split_by_scope(ops, modules, 0.0, 2000.0, by_name)
    assert sec == pytest.approx({
        "step": 300e-9, "exchange": 200e-9, "route": 100e-9,
        # the loop's own time and the unnamed copy
        sharded_stages.UNSCOPED: 300e-9,
        sharded_stages.OTHER: 50e-9})


SCRIPT = r'''
import json, time
import jax
from chipbench import harness
from chipbench.tests.conftest import small_cell


class NoTrace(harness.Tracer):
    def start(self):
        pass

    def stop(self):
        return None


harness.Tracer = NoTrace
NAME = "rlbsbf-2gb-4chip.stream-u60"
r, _ = harness.run_cell(NAME, 2 ** 31 + 11, 0.3, True,
                        t_start=time.perf_counter(), devices=jax.devices(),
                        cell=small_cell(NAME))
print(json.dumps({"correct": r["correct"], "metrics": r["metrics"],
                  "count": r["device"]["count"]}))
'''


@pytest.mark.subprocess
def test_sound_sharded_run_reports_launch_ms():
    """Four virtual CPU devices, the trace left out (the CPU has no device
    plane): the program-span metrics of the cell still read."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join([harness.ROOT,
                                           os.path.join(harness.ROOT, "src")]))
    p = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    r = json.loads(p.stdout.strip().splitlines()[-1])
    assert r["count"] == 4 and r["correct"]
    assert r["metrics"]["launch_ms.sharded"]["value"] > 0
    assert r["metrics"]["launch_ms.sharded"]["unit"] == "ms"
    assert r["metrics"]["enqueue_ms.stream"]["value"] >= \
        r["metrics"]["launch_ms.sharded"]["value"]
