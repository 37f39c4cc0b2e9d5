"""Multi-tenant filter fleets (DESIGN.md §4.6) — the isolation theorem.

The headline contract pinned here: an interleaved MIXED-TENANT stream
through one fleet launch produces verdicts BIT-IDENTICAL to T isolated
single-tenant engines each fed only its own elements — across the full
spec × layout × backend grid, under heterogeneous per-tenant params, on
the donated fleet stream, and on the sharded (elastic, one-bucket-per-
tenant) path. Isolation is by construction (disjoint state rows, tenant-
folded rng) but every seam that could break it — slot routing, the shared
vmapped trace, the per-tenant param broadcast, overflow accounting, the
ring advance — is exercised.

The isolated reference steps EVERY global batch, including ones where the
tenant has no elements (the fleet's vmapped step runs all T rows every
launch — rng draws and ring advances happen regardless of traffic), via
``Dedup.process_padded(width=C)`` at the fleet's slot width with the
tenant-folded rng. That width-pinned determinism contract (DESIGN §5.2) is
exactly what makes the theorem checkable bit-for-bit.

Multi-device pieces run in subprocesses (xla_force_host_platform_device_count
is locked at first jax init), tagged ``subprocess``.
"""

import dataclasses
import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import DedupConfig
from repro.core.engine import Dedup
from repro.core.fleet import (FleetDedup, TenantParams, default_tenant_params,
                              init_fleet_state, tenant_rank,
                              tenant_tagged_keys, validate_params)
from repro.core.state import init_state

SEED = 11


def _cfg(variant, backend="jnp", layout="planes", T=4, **kw):
    kw.setdefault("memory_bits", 4096)
    kw.setdefault("k", 4)
    kw.setdefault("batch_size", 16)
    if variant == "swbf":
        kw.setdefault("window", 4)
    if variant in ("cms", "hh"):
        kw.setdefault("count_threshold", 2)
    return DedupConfig(variant=variant, backend=backend, layout=layout,
                       n_tenants=T, seed=SEED, **kw).validate()


def _mixed_stream(T, B, steps, key_space=64, seed=SEED):
    """Interleaved per-tenant traffic with guaranteed intra-tenant repeats
    (the second half replays the first half's keys)."""
    rng = np.random.default_rng(seed)
    kb = rng.integers(0, key_space, size=(steps, B)).astype(np.uint32)
    tb = rng.integers(0, T, size=(steps, B)).astype(np.int32)
    kb[steps // 2:] = kb[:steps - steps // 2]
    return kb, tb


def _isolated_verdicts(cfg, capacity, kb, tb, params=None):
    """The reference side of the theorem: T separate single-tenant engines,
    tenant t's rng folded on t, EVERY global step run at the fleet's slot
    width (empty groups included)."""
    T, steps = cfg.n_tenants, kb.shape[0]
    out = [np.zeros_like(k, dtype=bool) for k in kb]
    for t in range(T):
        scfg = dataclasses.replace(
            cfg, n_tenants=1,
            **({} if params is None else {
                "sbf_max": int(params.max_value[t]),
                "count_threshold": int(params.threshold[t]),
                "window": (int(params.window[t]) if cfg.variant == "swbf"
                           else cfg.window)})).validate()
        eng = Dedup(scfg)
        kw = {"event_capacity": capacity} if cfg.variant == "swbf" else {}
        st = init_state(scfg, SEED, **kw)
        st = st._replace(rng=jax.random.fold_in(st.rng, t))
        for i in range(steps):
            sel = tb[i] == t
            st, res = eng.process_padded(st, kb[i][sel], width=capacity)
            out[i][sel] = np.asarray(res.dup)
    return np.stack(out)


def _fleet_verdicts(fleet, kb, tb):
    st = fleet.init(SEED)
    dups = []
    for i in range(kb.shape[0]):
        st, res = fleet.process(st, jnp.asarray(kb[i]), jnp.asarray(tb[i]))
        assert int(res.overflow) == 0
        dups.append(np.asarray(res.dup))
    return np.stack(dups), st


# ------------------------------------------------------------ the grid //
GRID = [
    ("rsbf", "jnp", "planes"),
    ("bsbf", "jnp", "planes"), ("bsbf", "jnp", "dense8"),
    ("bsbf", "pallas", "planes"),
    ("bsbfsd", "jnp", "planes"),
    ("rlbsbf", "jnp", "planes"), ("rlbsbf", "jnp", "dense8"),
    ("rlbsbf", "pallas", "planes"),
    ("sbf", "jnp", "planes"), ("sbf", "pallas", "planes"),
    ("swbf", "jnp", "planes"), ("swbf", "pallas", "planes"),
    ("cms", "jnp", "planes"), ("cms", "pallas", "planes"),
    ("hh", "jnp", "planes"), ("hh", "pallas", "planes"),
]


@pytest.mark.parametrize("variant,backend,layout", GRID,
                         ids=[f"{v}-{b}-{l}" for v, b, l in GRID])
def test_fleet_matches_isolated_runs(variant, backend, layout):
    """THE isolation theorem: mixed-tenant fleet verdicts == T isolated
    single-tenant runs, bit for bit, across spec × layout × backend."""
    cfg = _cfg(variant, backend, layout)
    # capacity = the full batch width: no tenant can overflow its slot row,
    # so the theorem is checked on every lane
    fleet = FleetDedup(cfg, capacity=cfg.batch_size)
    kb, tb = _mixed_stream(cfg.n_tenants, cfg.batch_size, steps=6)
    got, _ = _fleet_verdicts(fleet, kb, tb)
    want = _isolated_verdicts(cfg, fleet.capacity, kb, tb)
    np.testing.assert_array_equal(got, want)


HETERO_GRID = [("sbf", "jnp"), ("sbf", "pallas"),
               ("cms", "jnp"), ("cms", "pallas"),
               ("swbf", "jnp"), ("swbf", "pallas")]


@pytest.mark.parametrize("variant,backend", HETERO_GRID,
                         ids=[f"{v}-{b}" for v, b in HETERO_GRID])
def test_fleet_heterogeneous_params(variant, backend):
    """Per-tenant config broadcast: tenants running DIFFERENT Max /
    threshold / window values in one launch each match an isolated engine
    configured with their own values. sbf_p is pinned so the per-tenant Max
    doesn't shift the decrement fan-out (same d, same trace)."""
    kw = {"sbf_p": 7} if variant == "sbf" else {}
    cfg = _cfg(variant, backend, T=4, **kw)
    cap = cfg.batch_size
    params = default_tenant_params(cfg, cap)
    if variant == "sbf":
        # same bit_length as cfg.sbf_max — the plane count is fleet-static
        lo, hi = (1 << (cfg.sbf_max.bit_length() - 1)), cfg.sbf_max
        params = params._replace(max_value=jnp.asarray(
            [hi, lo, hi, max(lo, hi - 1)], jnp.int32))
    elif variant == "cms":
        params = params._replace(threshold=jnp.asarray([1, 2, 3, 2],
                                                       jnp.int32))
    else:
        params = params._replace(window=jnp.asarray([4, 1, 2, 3], jnp.int32))
    fleet = FleetDedup(cfg, capacity=cap, params=params)
    kb, tb = _mixed_stream(cfg.n_tenants, cfg.batch_size, steps=8)
    got, _ = _fleet_verdicts(fleet, kb, tb)
    want = _isolated_verdicts(cfg, cap, kb, tb, params=params)
    np.testing.assert_array_equal(got, want)


# ------------------------------------------------- routing & mechanics //
def test_tenant_rank_is_arrival_rank():
    rng = np.random.default_rng(0)
    tenant = jnp.asarray(rng.integers(0, 5, 64), jnp.int32)
    valid = jnp.asarray(rng.random(64) < 0.8)
    rank = np.asarray(tenant_rank(tenant, valid, 8))
    t_np, v_np = np.asarray(tenant), np.asarray(valid)
    for i in np.flatnonzero(v_np):
        want = int(np.sum(v_np[:i] & (t_np[:i] == t_np[i])))
        assert rank[i] == want, (i, rank[i], want)


def test_tenant_rank_overflow_guard():
    with pytest.raises(ValueError, match="composite key overflow"):
        tenant_rank(jnp.zeros((1 << 8,), jnp.int32),
                    jnp.ones((1 << 8,), bool), 1 << 30)


def test_tenant_tagged_keys_roundtrip():
    from repro.core.hashing import range_bucket
    keys = jnp.arange(64, dtype=jnp.uint32) * 1000
    tens = jnp.arange(64, dtype=jnp.int32) % 8
    tagged = tenant_tagged_keys(keys, tens, 8)
    # the top bits ARE the tenant: range routing recovers the id exactly
    np.testing.assert_array_equal(np.asarray(range_bucket(tagged, 8)),
                                  np.asarray(tens))
    # low bits preserved (keys below 2^29 here)
    np.testing.assert_array_equal(
        np.asarray(tagged & jnp.uint32((1 << 29) - 1)), np.asarray(keys))
    # T=1 is a no-op
    np.testing.assert_array_equal(
        np.asarray(tenant_tagged_keys(keys, tens, 1)), np.asarray(keys))


def test_fleet_overflow_is_counted_and_distinct():
    """Lanes beyond a tenant's per-step capacity are reported distinct and
    counted — never silently dropped, never written (§4.2 contract)."""
    cfg = _cfg("bsbf", T=2, batch_size=16)
    fleet = FleetDedup(cfg, capacity=8)
    params = fleet.params._replace(capacity=jnp.asarray([2, 8], jnp.int32))
    fleet = FleetDedup(cfg, capacity=8, params=params)
    st = fleet.init(SEED)
    keys = jnp.arange(16, dtype=jnp.uint32)
    tens = jnp.zeros((16,), jnp.int32)            # all tenant 0, cap 2
    st, res = fleet.process(st, keys, tens)
    assert int(res.overflow) == 14
    routed = np.asarray(res.routed)
    assert routed[:2].all() and not routed[2:].any()
    assert not np.asarray(res.dup)[2:].any()      # conservative: distinct


def test_fleet_run_stream_matches_stepwise():
    """The donated one-dispatch stream == the per-batch process loop."""
    cfg = _cfg("rlbsbf", T=4, batch_size=16)
    fleet = FleetDedup(cfg)
    kb, tb = _mixed_stream(4, 16, steps=6)
    step_dups, _ = _fleet_verdicts(fleet, kb, tb)
    fleet2 = FleetDedup(cfg)
    st = fleet2.init(SEED)
    st, dups, ovfs = fleet2.run_stream(st, jnp.asarray(kb.reshape(-1)),
                                       jnp.asarray(tb.reshape(-1)))
    np.testing.assert_array_equal(np.asarray(dups),
                                  step_dups.reshape(-1))
    assert int(np.asarray(ovfs).sum()) == 0
    assert fleet2.stream_cache_size() == 1


def test_fleet_stream_cache_stable():
    """One compiled specialization per mixed-batch width, ever (§3.5)."""
    cfg = _cfg("bsbf", T=4, batch_size=16)
    fleet = FleetDedup(cfg)
    st = fleet.init(SEED)
    kb, tb = _mixed_stream(4, 16, steps=4)
    for i in range(4):
        st, _ = fleet.process(st, jnp.asarray(kb[i]), jnp.asarray(tb[i]))
    assert fleet.process_cache_size() == 1


def test_fleet_param_validation():
    cfg = _cfg("sbf", T=2, sbf_p=7)
    fleet = FleetDedup(cfg)
    good = default_tenant_params(cfg, fleet.capacity)
    validate_params(cfg, good, fleet.capacity)
    with pytest.raises(ValueError, match="shape"):
        validate_params(cfg, good._replace(
            max_value=jnp.ones((3,), jnp.int32)), fleet.capacity)
    with pytest.raises(ValueError, match="bit_length"):
        validate_params(cfg, good._replace(
            max_value=jnp.asarray([1, cfg.sbf_max], jnp.int32)),
            fleet.capacity)
    with pytest.raises(ValueError, match="capacity"):
        validate_params(cfg, good._replace(
            capacity=jnp.asarray([0, 1], jnp.int32)), fleet.capacity)
    wcfg = _cfg("swbf", T=2)
    wfleet = FleetDedup(wcfg)
    wgood = default_tenant_params(wcfg, wfleet.capacity)
    with pytest.raises(ValueError, match="window"):
        validate_params(wcfg, wgood._replace(
            window=jnp.asarray([1, wcfg.window + 1], jnp.int32)),
            wfleet.capacity)


def test_fleet_config_validation():
    with pytest.raises(ValueError, match="power of two"):
        DedupConfig(variant="bsbf", memory_bits=4096, k=4,
                    n_tenants=3).validate()
    with pytest.raises(ValueError, match=">= 1"):
        DedupConfig(variant="bsbf", memory_bits=4096, k=4,
                    n_tenants=0).validate()
    with pytest.raises(ValueError, match="dense8"):
        FleetDedup(_cfg("sbf", layout="dense8"))


def test_fleet_rng_rows_are_tenant_folds():
    """Stacked rng row t == fold_in(base, t) — the elastic bucket fold
    (§4.4) applied to tenants; tenant randomness travels with the tenant."""
    cfg = _cfg("rlbsbf", T=4)
    st = init_fleet_state(cfg, SEED)
    base = init_state(cfg, SEED)
    def raw(k):
        try:
            return np.asarray(jax.random.key_data(k))
        except TypeError:          # legacy uint32 keys are plain arrays
            return np.asarray(k)

    for t in range(4):
        np.testing.assert_array_equal(
            raw(st.rng[t]), raw(jax.random.fold_in(base.rng, t)))


# ------------------------------------------------- checkpoint round-trip //
def test_fleet_checkpoint_roundtrip(tmp_path):
    from repro.checkpoint import CheckpointManager
    from repro.checkpoint.migrate import (check_tenant_meta, layout_meta,
                                          tenant_meta)
    cfg = _cfg("swbf", T=4)
    fleet = FleetDedup(cfg)
    kb, tb = _mixed_stream(4, 16, steps=4)
    st = fleet.init(SEED)
    for i in range(2):
        st, _ = fleet.process(st, jnp.asarray(kb[i]), jnp.asarray(tb[i]))
    mgr = CheckpointManager(str(tmp_path))
    mgr.save(2, st, extra_meta={**layout_meta(cfg),
                               **tenant_meta(cfg, fleet.params)})
    meta = mgr.load_meta(2)
    check_tenant_meta(meta, cfg)
    assert meta["tenant_count"] == 4
    assert meta["tenant_layout"] == "stacked"
    restored = mgr.restore(2, jax.eval_shape(lambda: st))
    # resume both, bit-identical verdicts
    for i in range(2, 4):
        st, a = fleet.process(st, jnp.asarray(kb[i]), jnp.asarray(tb[i]))
        restored, b = fleet.process(restored, jnp.asarray(kb[i]),
                                    jnp.asarray(tb[i]))
        np.testing.assert_array_equal(np.asarray(a.dup), np.asarray(b.dup))


def test_fleet_export_import_tenant():
    """export_tenant slices a runnable single-tenant filter; import_tenant
    grafts it into another fleet row with the other rows untouched."""
    from repro.checkpoint.migrate import export_tenant, import_tenant
    cfg = _cfg("bsbf", T=4)
    fleet = FleetDedup(cfg)
    kb, tb = _mixed_stream(4, 16, steps=4)
    got, st = _fleet_verdicts(fleet, kb, tb)
    sub = export_tenant(st, 2)
    scfg = dataclasses.replace(cfg, n_tenants=1).validate()
    eng = Dedup(scfg)
    ref = init_state(scfg, SEED)
    ref = ref._replace(rng=jax.random.fold_in(ref.rng, 2))
    for i in range(kb.shape[0]):
        ref, _ = eng.process_padded(ref, kb[i][tb[i] == 2],
                                    width=fleet.capacity)
    np.testing.assert_array_equal(np.asarray(sub.bits), np.asarray(ref.bits))
    st2 = import_tenant(fleet.init(SEED), 1, sub)
    np.testing.assert_array_equal(np.asarray(st2.bits[1]),
                                  np.asarray(sub.bits))
    np.testing.assert_array_equal(np.asarray(st2.bits[0]),
                                  np.asarray(fleet.init(SEED).bits[0]))


# ----------------------------------------------------- sharded fleets //
def _run_subprocess(code: str, devices: int = 8) -> str:
    env = {"XLA_FLAGS": f"--xla_force_host_platform_device_count={devices}",
           "PYTHONPATH": "src", "PATH": "/usr/bin:/bin"}
    env = {**os.environ, **env}
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env,
                         cwd=os.path.dirname(os.path.dirname(__file__)) or ".")
    assert out.returncode == 0, out.stderr[-4000:]
    return out.stdout


_SHARDED_WORKER = """
import json
import numpy as np, jax, jax.numpy as jnp
from repro.compat import make_mesh, set_mesh
from repro.core.config import DedupConfig
from repro.dedup.sharded import ShardedDedup, ShardedDedupConfig

T, N = 8, 512
base = DedupConfig(variant="{variant}", memory_bits=1 << 15, k=4,
                   batch_size=64, n_tenants=T, rebalance_buckets=T, seed=11)
devices = len(jax.devices())
mesh = make_mesh((devices, 1), ("data", "model"))
# capacity_factor covers the worst tenant-concentration a batch can carry
# (all 64 lanes on one tenant -> one shard): dispatch overflow would drop
# lanes and break the cross-mesh bit-identity this worker asserts
svc = ShardedDedup(ShardedDedupConfig(base=base, capacity_factor=64.0),
                   mesh)

rng = np.random.default_rng(11)
keys = rng.integers(0, 1 << 20, N).astype(np.uint32)
tens = rng.integers(0, T, N).astype(np.int32)
keys[N // 2:] = keys[:N // 2]
tens[N // 2:] = tens[:N // 2]

with set_mesh(mesh):
    st = svc.init(11)
    st, dup, ovf = svc.run_tenant_stream(st, jnp.asarray(keys),
                                         jnp.asarray(tens))
    dup = np.asarray(dup)

    # isolation probe: rewrite every OTHER tenant's keys; tenant 3 unchanged
    keys2 = keys.copy()
    other = tens != 3
    keys2[other] = rng.integers(0, 1 << 20,
                                int(other.sum())).astype(np.uint32)
    st2 = svc.init(11)
    st2, dup2, _ = svc.run_tenant_stream(st2, jnp.asarray(keys2),
                                         jnp.asarray(tens))
sel = tens == 3
print(json.dumps({
    "dup": dup.astype(int).tolist(),
    "isolated": bool((dup[sel] == np.asarray(dup2)[sel]).all()),
    "overflow": int(np.asarray(ovf).sum()),
}))
"""

@pytest.mark.slow
@pytest.mark.subprocess
@pytest.mark.parametrize("variant", ["rlbsbf", "sbf"])
def test_sharded_tenant_stream_device_invariant_and_isolated(variant):
    """The sharded fleet (elastic path, one bucket per tenant, §4.6):
    verdicts identical on 8 devices vs 1 (the bucket step width is
    device-count-invariant), and tenant t's verdicts independent of every
    other tenant's traffic."""
    code = _SHARDED_WORKER.replace("{variant}", variant)
    r8 = json.loads(_run_subprocess(code, devices=8))
    r1 = json.loads(_run_subprocess(code, devices=1))
    # zero dispatch overflow is the precondition for cross-mesh bit-identity
    # (per-shard receive capacity depends on the device count)
    assert r8["overflow"] == 0 and r1["overflow"] == 0
    assert r8["dup"] == r1["dup"]
    assert r8["isolated"] and r1["isolated"]


def test_sharded_tenant_stream_requires_bucket_per_tenant():
    from jax.sharding import Mesh
    from repro.dedup.sharded import ShardedDedup, ShardedDedupConfig
    base = DedupConfig(variant="bsbf", memory_bits=8192, k=4, batch_size=64,
                       n_tenants=4, rebalance_buckets=8)
    mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
    svc = ShardedDedup(ShardedDedupConfig(base=base, mesh_axes=("data",)),
                       mesh)
    with pytest.raises(ValueError, match="one bucket per tenant"):
        svc.run_tenant_stream(svc.init(0), jnp.zeros((64,), jnp.uint32),
                              jnp.zeros((64,), jnp.int32))


# ---------------------------------------------------------- serving //
def test_serving_fleet_isolated_and_replayable():
    """Tenant-tagged requests through the micro-batcher: fleet verdicts in
    the serving path match the isolated reference, the recorded schedule
    replays bit-identically, and the response cache is tenant-scoped."""
    from repro.serve.frontend import MicroBatchExecutor, replay_schedule
    cfg = _cfg("bsbf", T=4, batch_size=16)
    score = lambda b: np.asarray(b["key"], np.float64)  # noqa: E731
    ex = MicroBatchExecutor(cfg, score, buckets=(16,), record_schedule=True)
    kb, tb = _mixed_stream(4, 16, steps=6)
    dups = []
    for i in range(kb.shape[0]):
        _, d, _ = ex.run({"key": kb[i], "tenant": tb[i]})
        dups.append(d)
    assert ex.digest() == replay_schedule(cfg, ex.schedule)
    want = _isolated_verdicts(cfg, 16, kb, tb)
    np.testing.assert_array_equal(np.stack(dups), want)
    # same key, different tenants -> distinct cache rows
    k = ex.cache_keys(np.asarray([5, 5], np.uint32),
                      np.asarray([0, 1], np.int32))
    assert k[0] != k[1]
