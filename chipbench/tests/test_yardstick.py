"""The benchmark's shared arithmetic: the peak table, the bytes count, the
fill numbers the configuration files state, and the trace reduction."""

import gzip
import json
import os

import numpy as np
import pytest

from chipbench import algo_bytes, fill, harness, peaks, reference, xtrace

HERE = os.path.dirname(os.path.abspath(__file__))


def test_peak_table_knows_the_v5e():
    p = peaks.peak_for("TPU v5 lite")
    assert p["hbm_bytes_per_s"] == 819e9
    assert p["bf16_flops_per_s"] == 197e12
    assert p["hbm_bytes"] == 16 * 2 ** 30
    assert "TPU v5e" in p["source"]


@pytest.mark.parametrize("kind", ["cpu", "TPU v4", "TPU v5", ""])
def test_unknown_device_kind_is_refused(kind):
    with pytest.raises(peaks.UnknownDevice):
        peaks.peak_for(kind)


@pytest.mark.parametrize("config", ["rlbsbf-512mb", "sbf-128mb",
                                    "rlbsbf-2gb-4chip"])
def test_bytes_count_ignores_the_backend(config):
    cfg = harness.load_json(os.path.join(harness.HERE, "configs",
                                         config + ".json"))
    jnp_spec = dict(cfg["dedup"], backend="jnp")
    pallas_spec = dict(cfg["dedup"], backend="pallas")
    a = algo_bytes.step_bytes(jnp_spec, cfg["fill"], 8192)
    b = algo_bytes.step_bytes(pallas_spec, cfg["fill"], 8192)
    assert a == b > 8192 * 5


def test_rlbsbf_fill_is_the_recurrence_load():
    for name in ("rlbsbf-512mb", "rlbsbf-2gb-4chip"):
        cfg = harness.load_json(os.path.join(harness.HERE, "configs",
                                             name + ".json"))
        f, d = cfg["fill"], cfg["dedup"]
        shards = 4 if cfg["engine"] == "sharded" else 1
        s = d["memory_bits"] // shards // d["k"]
        want = fill.rlbsbf_load_fraction(f["records"] / shards, s,
                                         f["distinct_fraction"], d["k"],
                                         steps=20_000)
        assert f["load_fraction"] == pytest.approx(want, rel=1e-6)
    # direct iteration of the per-element recurrence at a small s agrees
    s, n, load = 1 << 14, int(1e9 / 2 ** 31 * (1 << 14)), 0.0
    for _ in range(n):
        x = load / s
        load += 0.6 * (1 - x * x) * ((1 - x) - x * x)
    assert load / s == pytest.approx(
        fill.rlbsbf_load_fraction(n, s, 0.6, 2, steps=2000), rel=1e-3)


def test_sbf_fill_is_the_stable_point():
    cfg = harness.load_json(os.path.join(harness.HERE, "configs",
                                         "sbf-128mb.json"))
    d = cfg["dedup"]
    m = d["memory_bits"] // 2
    probs = cfg["fill"]["value_probabilities"]
    assert probs == pytest.approx(fill.sbf_stable_distribution(
        d["sbf_p"], d["k"], m, d["sbf_max"]), rel=1e-12)
    assert sum(probs) == pytest.approx(1.0)
    # Deng & Rafiei Thm 2: the zero fraction, and the FPR target it meets
    denom = 1.0 + 1.0 / (d["sbf_p"] * (1.0 / d["k"] - 1.0 / m))
    assert probs[0] == pytest.approx((1.0 / denom) ** d["sbf_max"])
    assert (1 - probs[0]) ** d["k"] <= d["fpr_t"]


def _pack(cells, d):
    """Reference cells -> (d, rows, W) words, written out plainly."""
    out = np.zeros((d, len(cells), cells[0].size // 32), np.uint32)
    for r, c in enumerate(cells):
        v = np.asarray(c).reshape(-1, 32).astype(np.uint32)
        for p in range(d):
            out[p, r] = np.sum(((v >> p) & 1) << np.arange(32, dtype=np.uint32),
                               axis=1, dtype=np.uint32)
    return out


def test_packed_and_dense_fills_are_the_same_draws():
    import jax.numpy as jnp
    salts = np.array([7, 99], np.uint32)
    for probs, d in (([0.7, 0.3], 1), ([0.5, 0.1, 0.15, 0.25], 2)):
        cuts = fill.cut_points(probs)
        packed = fill.packed_planes(salts, 1 << 10, cuts, d)
        dense = tuple(fill.dense_cells(int(s), 1 << 15, cuts, jnp.int32)
                      for s in salts)
        np.testing.assert_array_equal(_pack(dense, d), np.asarray(packed))
        share = np.mean(np.asarray(dense[0]) == 0)
        assert share == pytest.approx(probs[0], abs=0.02)
        # the two layouts' digests agree, and one changed cell moves it
        assert int(reference.words_digest(packed)) == \
            int(reference.cells_digest(dense, d))
        bumped = (dense[0].at[3, 5].set((dense[0][3, 5] + 1) % (1 << d)),
                  dense[1])
        assert int(reference.cells_digest(bumped, d)) != \
            int(reference.cells_digest(dense, d))


# ------------------------------------------------------------ the trace //
def _synthetic():
    ms = 1e6
    return {
        "window_ns": [0.0, 100 * ms],
        "devices": {
            "/device:TPU:0": {
                "ops": [["%while.5 = (u32[8]) while(...)", 0.0, 30 * ms],
                        ["%fusion.1 = u32[8] fusion(...)", 0.0, 20 * ms],
                        ["%sort.3 = u32[8] sort(...)", 20 * ms, 10 * ms],
                        ["%fusion.22 = u32[8] fusion(...)", 50 * ms,
                         10 * ms],
                        ["%all-to-all.2 = u32[8] all-to-all(...)", 90 * ms,
                         20 * ms]],                          # clipped
                "modules": [["jit_step(7)", 0.0, 30 * ms],
                            ["jit_step(7)", 50 * ms, 10 * ms]]},
            "/device:TPU:1": {
                "ops": [["fusion.1", 0.0, 50 * ms]],
                "modules": [["jit_step(7)", 0.0, 50 * ms]]},
        },
        "host": [["cb.wait", 30 * ms, 20 * ms],
                 ["cb.readback", 60 * ms, 5 * ms],
                 ["cb.handoff", 65 * ms, 30 * ms]],
    }


def test_reduction_of_a_known_trace():
    r = xtrace.reduce(_synthetic())
    # device 0 busy: [0, 30] + [50, 60] + [90, 100] = 50 ms; device 1: 50
    assert r["busy_s"] == pytest.approx(0.050)
    assert r["window_s"] == pytest.approx(0.100)
    assert r["idle_share"] == pytest.approx(0.5)
    # self times: the loop holds fusion.1 and sort.3, so it owns none;
    # fusion: 20 + 10 on device 0, 50 on device 1, averaged over 2
    assert r["op_s"]["while"] == pytest.approx(0.0)
    assert r["op_s"]["fusion"] == pytest.approx(0.040)
    assert r["op_s"]["sort"] == pytest.approx(0.005)
    assert r["op_s"]["all-to-all"] == pytest.approx(0.005)
    assert sum(r["op_s"].values()) == pytest.approx(r["busy_s"])
    assert r["module_s"]["jit_step(7)"] == pytest.approx(0.045)
    assert r["module_n"]["jit_step(7)"] == pytest.approx(1.5)
    gaps = dict(r["idle_gaps"])
    # device 0 gaps: [30, 50] in cb.wait, [60, 90] mid 75 in cb.handoff;
    # device 1 gap [50, 100] mid 75 in cb.handoff
    assert gaps["cb.wait"] == pytest.approx(0.020 / 2)
    assert gaps["cb.handoff"] == pytest.approx((0.030 + 0.050) / 2)
    assert r["device_ops"][0][0] == "fusion"


def test_gap_outside_every_span_is_named_so():
    tr = _synthetic()
    tr["host"] = []
    assert dict(xtrace.reduce(tr)["idle_gaps"]) == pytest.approx(
        {"no host span": 0.050})


def test_stable_names_drop_instance_numbers():
    assert xtrace.stable_name("%fusion.12 = u32[8] fusion(x)") == "fusion"
    assert xtrace.stable_name("%all-to-all.3.1") == "all-to-all"
    assert xtrace.stable_name("%broadcast.2830.clone.1 = pred[] b()") == \
        "broadcast"
    assert xtrace.stable_name("jit__stream_impl(2022494)") == \
        "jit__stream_impl(2022494)"


def test_reduction_of_a_recorded_chip_trace():
    """0.65 s of a traced rlbsbf-512mb.stream-u60 run on one v5e, as
    ``load_xplane`` keeps it: two chunks of eight steps and the host spans
    around them."""
    path = os.path.join(HERE, "data", "trace_v5e_rlbsbf512_stream.json.gz")
    with gzip.open(path, "rt") as f:
        tr = json.load(f)
    r = xtrace.reduce(tr)
    assert r["n_devices"] == 1
    assert r["window_s"] == pytest.approx(0.65)
    assert r["busy_s"] == pytest.approx(0.644833489, rel=1e-9)
    assert r["idle_share"] == pytest.approx(1 - 0.644833489 / 0.65)
    # self times partition the busy time: the scan's loop op owns nothing
    assert sum(r["op_s"].values()) == pytest.approx(r["busy_s"], rel=1e-6)
    assert r["device_ops"][0][0] == "dynamic-update-slice"
    assert r["device_ops"][0][1] == pytest.approx(0.290295582, rel=1e-9)
    scan = max(r["module_s"], key=r["module_s"].get)
    assert scan.startswith("jit__stream_impl")
    gaps = dict(r["idle_gaps"])
    assert set(gaps) <= {"cb.generate", "cb.handoff", "cb.wait",
                         "cb.readback", "no host span"}
    assert sum(gaps.values()) == pytest.approx(r["window_s"] - r["busy_s"])
