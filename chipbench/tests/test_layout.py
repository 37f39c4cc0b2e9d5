"""Every cell of BENCHMARK.json resolves to its files by name, and the
traffic generator keeps its stated shape and is deterministic in the
seed."""

import os

import numpy as np
import pytest

from chipbench import harness, kinds
from chipbench.keygen import zipf
from chipbench.loops import poisson

BENCH = harness.load_json(os.path.join(harness.ROOT, "BENCHMARK.json"))
CELLS = [w["name"] for w in BENCH["workloads"]]
SEEDS = (0, 2 ** 31 + 17)


@pytest.mark.parametrize("name", CELLS)
def test_cell_resolves_by_name(name):
    cell = harness.resolve(name)
    w = cell["workload"]
    assert cell["config"]["chips"] == w["chips"]
    assert callable(kinds.load("loops",
                               cell["traffic"]["arrival"]["kind"]).run)
    assert callable(kinds.load("keygen", cell["traffic"]["keys"]["kind"]).make)
    assert callable(kinds.load("engines", cell["config"]["engine"]).System)
    e2e = {m["name"] for m in cell["end_to_end"]}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert cell["per_layer"], "each cell reports a per-layer metric"
    for m in cell["per_layer"]:
        assert m["moves"] in e2e
        assert callable(harness.metric_reader(m["name"]))


def test_every_listed_cell_exists_and_reports_what_a_metric_moves():
    names = set(CELLS)
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert set(m.get("workloads", names)) <= names
    for m in BENCH["per_layer"]:
        moved = e2e[m["moves"]]
        assert set(m["workloads"]) <= set(moved.get("workloads", names))
    for c in BENCH["configs"]:
        assert os.path.isfile(os.path.join(harness.ROOT, c["file"]))
        assert any(w["config"] == c["name"] for w in BENCH["workloads"])


@pytest.mark.parametrize("group,kind", [("loops", "no_such_kind"),
                                        ("keygen", "../harness"),
                                        ("configs", "sbf-128mb")])
def test_a_kind_without_a_file_is_refused(group, kind):
    with pytest.raises((ValueError, ModuleNotFoundError)):
        kinds.load(group, kind)


def test_readers_find_nothing_without_a_trace():
    for m in BENCH["per_layer"]:
        assert harness.metric_reader(m["name"])({"trace": None}) is None


def _stream(seed, n_chunks=4, chunk=1 << 14):
    gen = kinds.make_keys(
        {"kind": "controlled_distinct", "distinct_fraction": 0.6}, seed,
        chunk)
    return [gen.next() for _ in range(n_chunks)]


@pytest.mark.parametrize("seed", SEEDS)
def test_u60_keys_are_deterministic_and_60pct_distinct(seed):
    a, b = _stream(seed), _stream(seed)
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x, y)
    assert not np.array_equal(a[0], _stream(seed + 1)[0])
    keys = np.concatenate(a)
    _, first = np.unique(keys, return_index=True)
    # every chunk holds exactly round(0.6 * chunk) new ids
    seen = set()
    for c in a:
        new = 0
        for k in c.tolist():
            if k not in seen:
                seen.add(k)
                new += 1
        assert new == round(0.6 * c.size)
    assert first.size == len(seen)


def _zipf(seed, n=200_000):
    gen = kinds.make_keys({"kind": "zipf", "constant": 0.99,
                           "universe": 1_000_000}, seed)
    return gen, gen.next(n)


@pytest.mark.parametrize("seed", SEEDS)
def test_zipf_keys_are_deterministic_and_keep_the_constant(seed):
    gen, keys = _zipf(seed)
    np.testing.assert_array_equal(keys, _zipf(seed)[1])
    assert not np.array_equal(keys, _zipf(seed + 1)[1])
    ranks = zipf.Zipf({"kind": "zipf", "constant": 0.99,
                       "universe": 1_000_000}, seed).ranks(400_000)
    counts = np.bincount(ranks.astype(np.int64))[:64]
    r = np.arange(1, 65)
    slope = np.polyfit(np.log(r[2:]), np.log(counts[2:]), 1)[0]
    assert -slope == pytest.approx(0.99, abs=0.06)


@pytest.mark.parametrize("seed", SEEDS)
def test_poisson_times_are_deterministic_at_the_rate(seed):
    t = poisson.poisson_times(5000.0, 4.0, seed)
    np.testing.assert_array_equal(t, poisson.poisson_times(5000.0, 4.0,
                                                           seed))
    assert t.size == pytest.approx(20_000, rel=0.05)
    assert np.all(np.diff(t) > 0) and t[-1] < 4.0
