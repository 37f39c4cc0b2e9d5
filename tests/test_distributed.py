"""Distributed pieces that need >1 device run in a subprocess with
xla_force_host_platform_device_count (the pytest process must keep 1 device);
single-device-safe pieces (specs, compression math) run inline."""

import json
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import all_arch_ids, get_arch
from repro.distributed.collectives import dequantize_int8, quantize_int8
from repro.launch.analysis import collective_bytes, collective_counts
from repro.compat import make_mesh


def _run_subprocess(code: str, devices: int = 8) -> str:
    env = {"XLA_FLAGS": f"--xla_force_host_platform_device_count={devices}",
           "PYTHONPATH": "src", "PATH": "/usr/bin:/bin"}
    import os
    env = {**os.environ, **env}
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env,
                         cwd=os.path.dirname(os.path.dirname(__file__)) or ".")
    assert out.returncode == 0, out.stderr[-4000:]
    return out.stdout


@pytest.mark.slow
@pytest.mark.subprocess
def test_sharded_dedup_equals_single_filter():
    """8 simulated devices: the sharded run_stream (ONE dispatch for the
    whole 12-batch stream, donated state) matches the single aggregate
    filter's FPR/FNR, overflows nothing, and compiles exactly once."""
    out = _run_subprocess("""
        import numpy as np, jax, jax.numpy as jnp, json
        from repro.compat import make_mesh, set_mesh
        from repro.core import DedupConfig, Dedup
        from repro.dedup import ShardedDedup, ShardedDedupConfig, truth_from_stream
        mesh = make_mesh((4, 2), ("data", "model"))
        cfg = DedupConfig.for_variant("rlbsbf", memory_bits=1 << 17,
                                      batch_size=4096)
        sd = ShardedDedup(ShardedDedupConfig(base=cfg), mesh)
        rng = np.random.default_rng(0)
        keys = rng.integers(0, 30_000, 12 * 4096).astype(np.uint32)
        with set_mesh(mesh):
            state, dup, ovf = sd.run_stream(sd.init(), jnp.asarray(keys))
            # second stream of the same length: cached scan, no retrace
            _state2, _d, _o = sd.run_stream(sd.init(), jnp.asarray(keys))
        dup = np.asarray(dup)
        truth = truth_from_stream(keys)
        fpr = float((dup & ~truth).sum() / (~truth).sum())
        fnr = float((~dup & truth).sum() / truth.sum())
        d1 = Dedup(DedupConfig.for_variant("rlbsbf", memory_bits=1 << 17,
                                           batch_size=4096))
        _, dup1 = d1.run_stream(d1.init(), jnp.asarray(keys))
        dup1 = np.asarray(dup1)
        fpr1 = float((dup1 & ~truth).sum() / (~truth).sum())
        fnr1 = float((~dup1 & truth).sum() / truth.sum())
        print(json.dumps({"fpr": fpr, "fnr": fnr, "fpr1": fpr1, "fnr1": fnr1,
                          "overflow": int(np.asarray(ovf).sum()),
                          "stream_cache": sd.stream_cache_size()}))
    """)
    r = json.loads(out.strip().splitlines()[-1])
    assert abs(r["fpr"] - r["fpr1"]) < 0.02
    assert abs(r["fnr"] - r["fnr1"]) < 0.02
    assert r["overflow"] == 0
    assert r["stream_cache"] == 1


@pytest.mark.slow
@pytest.mark.subprocess
def test_sharded_rsbf_positions_are_per_shard():
    """RSBF's reservoir probability s/i is per-shard under key partitioning:
    each shard's position counts only its own arrivals, and the sum of
    positions equals the number of routed (non-overflow) keys."""
    out = _run_subprocess("""
        import numpy as np, jax, jax.numpy as jnp, json
        from repro.compat import make_mesh, set_mesh
        from repro.core import DedupConfig
        from repro.dedup import ShardedDedup, ShardedDedupConfig
        mesh = make_mesh((4, 2), ("data", "model"))
        cfg = DedupConfig.for_variant("rsbf", memory_bits=1 << 15)
        sd = ShardedDedup(ShardedDedupConfig(base=cfg), mesh)
        state = sd.init()
        step = sd.make_step(2048 // 8)
        rng = np.random.default_rng(0)
        total, ovf_total = 0, 0
        with set_mesh(mesh):
            for _ in range(6):
                keys = rng.integers(0, 100_000, 2048).astype(np.uint32)
                state, dup, ovf = step(state, jnp.asarray(keys))
                total += 2048
                ovf_total += int(np.asarray(ovf).sum())
        pos = np.asarray(state.position)
        print(json.dumps({"sum_pos": int((pos - 1).sum()),
                          "expected": total - ovf_total,
                          "spread": float(pos.std() / pos.mean())}))
    """)
    r = json.loads(out.strip().splitlines()[-1])
    assert r["sum_pos"] == r["expected"]
    assert r["spread"] < 0.2     # router balances the key space


@pytest.mark.slow
@pytest.mark.subprocess
def test_compressed_psum_error_feedback():
    out = _run_subprocess("""
        import jax, jax.numpy as jnp, numpy as np, json
        from repro.compat import make_mesh, set_mesh, shard_map
        from repro.distributed.collectives import compressed_psum
        from jax.sharding import PartitionSpec as P
        mesh = make_mesh((8,), ("data",))
        g_global = jax.random.normal(jax.random.PRNGKey(0), (8, 64))

        def f(g):
            synced, err = compressed_psum({"g": g}, "data")
            return synced["g"], err["g"]

        fn = shard_map(f, mesh=mesh, in_specs=P("data", None),
                       out_specs=(P("data", None), P("data", None)),
                       check_vma=False)
        with set_mesh(mesh):
            synced, err = fn(g_global)
        want = jnp.mean(g_global, axis=0)
        got = np.asarray(synced)[0]
        rel = float(np.abs(got - np.asarray(want)).max() /
                    (np.abs(np.asarray(want)).max() + 1e-9))
        print(json.dumps({"rel": rel}))
    """)
    r = json.loads(out.strip().splitlines()[-1])
    assert r["rel"] < 0.02      # int8 quantization: ~1% error, fed back


def test_quantize_int8_roundtrip():
    x = jnp.asarray(np.random.default_rng(0).normal(size=(128,)) * 3)
    q, s = quantize_int8(x)
    back = dequantize_int8(q, s)
    assert float(jnp.abs(back - x).max()) <= float(s) * 1.01


def test_param_specs_divisible_everywhere():
    """Every sharded param dim must divide the production mesh axes —
    checked for all 10 archs on the 512-chip mesh shape (metadata only,
    no devices needed)."""
    from jax.sharding import Mesh
    import numpy as np
    devs = np.empty((2, 16, 16), dtype=object)   # abstract mesh for specs
    from repro.distributed import sharding as shr

    class FakeMesh:
        axis_names = ("pod", "data", "model")
        shape = {"pod": 2, "data": 16, "model": 16}

    mesh = FakeMesh()
    for aid in all_arch_ids():
        arch = get_arch(aid)
        pshape = (arch.params_shape("full_graph_sm")
                  if arch.family == "gnn" else arch.params_shape())
        specs = (arch.param_specs(mesh, "full_graph_sm")
                 if arch.family == "gnn" else arch.param_specs(mesh))
        for (path, sd), (_, spec) in zip(
                jax.tree_util.tree_flatten_with_path(pshape)[0],
                jax.tree_util.tree_flatten_with_path(specs)[0]):
            entries = list(spec) + [None] * (len(sd.shape) - len(spec))
            for dim, e in zip(sd.shape, entries):
                if e is None:
                    continue
                size = shr.axis_size(mesh, e)
                assert dim % size == 0, (aid, path, sd.shape, spec)


# ---------------- in-process sharded coverage (1 device, tier-1) -------- //
# The multi-device tests above run in subprocesses and exercise real
# collectives; these run in the pytest process on a 1x1 mesh so the sharded
# path (compat shard_map, routing, scan/donation, overflow plumbing) can
# never silently rot behind an API drift again.

def _sharded_one_by_one(cfg):
    from repro.dedup import ShardedDedup, ShardedDedupConfig
    mesh = make_mesh((1, 1), ("data", "model"))
    return ShardedDedup(ShardedDedupConfig(base=cfg), mesh)


def test_sharded_parity_inprocess_single_shard():
    """1x1 mesh: run_stream (scan, donated) is bit-identical to the
    per-batch make_step loop, statistically matches the single-device
    engine, masks the ragged tail, and compiles the scan exactly once."""
    from repro.core import Dedup, DedupConfig
    from repro.dedup import truth_from_stream

    cfg = DedupConfig.for_variant("rlbsbf", memory_bits=1 << 15,
                                  batch_size=512)
    sd = _sharded_one_by_one(cfg)
    rng = np.random.default_rng(0)
    keys = rng.integers(0, 5_000, 5_000).astype(np.uint32)   # 5000 % 512 != 0

    state, dup, ovf = sd.run_stream(sd.init(), jnp.asarray(keys))
    dup = np.asarray(dup)
    assert dup.shape == keys.shape
    assert int(np.asarray(ovf).sum()) == 0

    # scan path == per-batch step path, bit for bit (same shapes, same rng
    # threading), on the multiple-of-batch prefix the step path can express
    n_whole = (keys.shape[0] // 512) * 512
    step = sd.make_step(512)
    st = sd.init()
    per_step = []
    for i in range(n_whole // 512):
        st, d, _ = step(st, jnp.asarray(keys[i * 512:(i + 1) * 512]))
        per_step.append(np.asarray(d))
    np.testing.assert_array_equal(dup[:n_whole], np.concatenate(per_step))

    # one shard, same aggregate memory -> statistically the single filter
    truth = truth_from_stream(keys)
    fpr = (dup & ~truth).sum() / (~truth).sum()
    fnr = (~dup & truth).sum() / truth.sum()
    d1 = Dedup(cfg)
    _, dup1 = d1.run_stream(d1.init(), jnp.asarray(keys))
    dup1 = np.asarray(dup1)
    fpr1 = (dup1 & ~truth).sum() / (~truth).sum()
    fnr1 = (~dup1 & truth).sum() / truth.sum()
    assert abs(fpr - fpr1) < 0.02
    assert abs(fnr - fnr1) < 0.02

    # same stream length again: the cached jitted scan is reused
    sd.run_stream(sd.init(), jnp.asarray(keys))
    assert sd.stream_cache_size() == 1


def test_sharded_routes_through_fused_pallas_step():
    """cfg.backend='pallas' reaches the fused kernel below the shard axis
    and stays bit-identical to the jnp backend through routing + scan."""
    from repro.core import DedupConfig

    keys = np.random.default_rng(1).integers(0, 2_000, 768).astype(np.uint32)
    dups = {}
    for backend in ("jnp", "pallas"):
        cfg = DedupConfig.for_variant("rlbsbf", memory_bits=1 << 12,
                                      batch_size=256, packed=True,
                                      backend=backend)
        sd = _sharded_one_by_one(cfg)
        _st, dup, ovf = sd.run_stream(sd.init(), jnp.asarray(keys))
        dups[backend] = np.asarray(dup)
        assert int(np.asarray(ovf).sum()) == 0
    np.testing.assert_array_equal(dups["pallas"], dups["jnp"])


def test_sharded_overflow_accumulates_into_metrics_devicewise():
    """capacity_factor < 1 forces overflow; the (n_batches, n_shards) device
    counter feeds StreamMetrics without a host sync and overflowed keys are
    conservatively reported distinct."""
    from repro.core import DedupConfig
    from repro.dedup import (ShardedDedup, ShardedDedupConfig, StreamMetrics,
                             truth_from_stream)

    mesh = make_mesh((1, 1), ("data", "model"))
    cfg = DedupConfig.for_variant("rlbsbf", memory_bits=1 << 14,
                                  batch_size=256)
    sd = ShardedDedup(ShardedDedupConfig(base=cfg, capacity_factor=0.5), mesh)
    keys = np.random.default_rng(2).integers(0, 10_000, 2_048).astype(np.uint32)
    state, dup, ovf = sd.run_stream(sd.init(), jnp.asarray(keys))
    m = StreamMetrics()
    m.update(dup, truth_from_stream(keys), overflow=ovf)
    assert not m._pending_ovf or isinstance(m._pending_ovf[0], jnp.ndarray)
    s = m.summary()                       # read-out folds the device counter
    # cap = max(8, ceil(256 * 0.5)) = 128 -> exactly 128 of each 256-batch kept
    assert s["overflow"] == int(np.asarray(ovf).sum()) == 2_048 - 8 * 128
    assert m._pending_ovf == []


@pytest.mark.slow
@pytest.mark.subprocess
def test_pipelined_stream_matches_serial_bitwise():
    """§4.5: the pipelined scan changes schedule, not math. Three paths at
    4 devices on a zipf-skewed stream: static compacted counter (swbf —
    compacted step width + shrunken ring), static flat random (rlbsbf —
    lane-indexed draws forbid compaction), and the elastic bucket router
    (swbf). Pipelined == serial dup verdicts and overflow, bit for bit."""
    out = _run_subprocess("""
        import hashlib, json
        import jax, jax.numpy as jnp, numpy as np
        from repro.compat import make_mesh, set_mesh
        from repro.core import DedupConfig
        from repro.data.streams import zipf_range_stream
        from repro.dedup import ShardedDedup, ShardedDedupConfig
        mesh = make_mesh((4, 1), ("data", "model"))
        keys, _ = zipf_range_stream(4096, universe=1 << 11, a=1.2, seed=7)
        jk = jnp.asarray(keys)
        def run(cfg, pipe, **kw):
            sd = ShardedDedup(
                ShardedDedupConfig(base=cfg, pipeline=pipe, **kw), mesh)
            with set_mesh(mesh):
                _st, dup, ovf = sd.run_stream(sd.init(), jk)
            return [hashlib.sha256(np.asarray(dup).tobytes()).hexdigest(),
                    int(np.asarray(ovf).sum())]
        elastic = dict(rebalance_buckets=8, rebalance_threshold=1.3)
        res = {}
        for name, cfg, kw in (
            ("swbf_static",
             DedupConfig.for_variant("swbf", window=3, memory_bits=1 << 15,
                                     batch_size=512, packed=True), {}),
            ("rlbsbf_static",
             DedupConfig.for_variant("rlbsbf", memory_bits=1 << 15,
                                     batch_size=512, packed=True), {}),
            ("swbf_elastic",
             DedupConfig.for_variant("swbf", window=3, memory_bits=1 << 15,
                                     batch_size=512, packed=True, **elastic),
             {"capacity_factor": 8.0}),
        ):
            res[name] = [run(cfg, True, **kw), run(cfg, False, **kw)]
        print(json.dumps(res))
    """, devices=4)
    res = json.loads(out.strip().splitlines()[-1])
    for name, (pipelined, serial) in res.items():
        assert pipelined == serial, (name, pipelined, serial)


@pytest.mark.slow
@pytest.mark.subprocess
def test_pipelined_stream_donates_filter_planes():
    """§4.5: the double-buffered scan must not copy the filter planes.
    The sharded state is donated and buffer-aliased through the pipelined
    stream exactly like the serial one — the InFlight half of the carry
    adds only exchange-buffer-sized arrays, never a plane-stack copy.
    Extends the single-device donation family
    (test_swbf_stream_donates_planes_and_ring)."""
    out = _run_subprocess("""
        import json
        import jax, jax.numpy as jnp
        from repro.analysis import aliased_param_indices, entry_param_types
        from repro.compat import make_mesh, set_mesh
        from repro.core import DedupConfig
        from repro.dedup import ShardedDedup, ShardedDedupConfig
        S = 4
        mesh = make_mesh((S, 1), ("data", "model"))
        cfg = DedupConfig.for_variant("swbf", window=3, memory_bits=1 << 16,
                                      batch_size=512, packed=True)
        sd = ShardedDedup(ShardedDedupConfig(base=cfg), mesh)
        assert sd.scfg.pipeline          # the default path under test
        with set_mesh(mesh):
            state = sd.init()
            kb = jax.ShapeDtypeStruct((4, cfg.batch_size), jnp.uint32)
            vb = jax.ShapeDtypeStruct((4, cfg.batch_size), jnp.bool_)
            lowered = sd._make_stream(cfg.batch_size // S).lower(
                state, kb, vb)
            txt = lowered.compile().as_text()
        # per-device SPMD module: the leading shard axis collapses to 1
        def perdev(arr, dt):
            return dt + "[" + ",".join(
                ["1"] + [str(d) for d in arr.shape[1:]]) + "]"
        shapes = {"planes": perdev(state.bits, "u32"),
                  "ring": perdev(state.ring.events, "s32")}
        params = entry_param_types(txt)
        aliased = aliased_param_indices(txt)
        print(json.dumps({k: params.index(s) in aliased
                          for k, s in shapes.items()}))
    """, devices=4)
    res = json.loads(out.strip().splitlines()[-1])
    assert res["planes"], "filter plane stack copied by the pipelined carry"
    assert res["ring"], "ring events copied by the pipelined carry"


def test_hlo_collective_parser():
    hlo = """
  %all-reduce.26 = (f32[32,16]{1,0}, f32[32,16]{1,0}, /*index=2*/f32[8]{0}) all-reduce(%a, %b, %c), replica_groups=...
  %ag = bf16[64,128]{1,0} all-gather(%x), dimensions={0}
  %rs.1 = f32[16]{0} reduce-scatter(%y), dimensions={0}
  %a2a = u32[512,8]{1,0} all-to-all(%z), dimensions={0}
  %cp = s32[4]{0} collective-permute(%w), source_target_pairs={{0,1}}
  %done = f32[9999]{0} all-gather-done(%ag_start)
  %notacoll = f32[2]{0} add(%p, %q)
"""
    b = collective_bytes(hlo)
    assert b["all-reduce"] == 32 * 16 * 4 * 2 + 8 * 4
    assert b["all-gather"] == 64 * 128 * 2
    assert b["reduce-scatter"] == 16 * 4
    assert b["all-to-all"] == 512 * 8 * 4
    assert b["collective-permute"] == 4 * 4
    assert b["total"] == sum(v for k, v in b.items() if k != "total")
    c = collective_counts(hlo)
    assert c["all-reduce"] == 1 and "add" not in c
