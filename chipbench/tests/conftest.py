"""Shared fixtures of the benchmark's CPU tests: cells resolved from
``BENCHMARK.json`` and shrunk to sizes a test run holds (the timed path,
the reference and the comparison are the chip run's own)."""

import pytest

from chipbench import harness

SMALL = {"rlbsbf": 1 << 17, "sbf": 1 << 18}


def cell_from_files(config: str, traffic: str) -> dict:
    """A cell built from its files alone, as ``harness.resolve`` builds one
    listed in BENCHMARK.json: for a prepared cell not listed there yet."""
    import os
    cfg = harness.load_json(os.path.join(harness.HERE, "configs",
                                         config + ".json"))
    return {"workload": {"name": f"{config}.{traffic}", "config": config,
                         "traffic": traffic, "chips": cfg["chips"]},
            "config": cfg,
            "traffic": harness.load_json(os.path.join(
                harness.HERE, "traffic", traffic + ".json")),
            "end_to_end": [], "per_layer": []}


def small_cell(name: str, seconds_rate: float = 2000.0) -> dict:
    config, traffic = name.split(".", 1)
    cell = (harness.resolve(name) if name in harness.cell_names()
            else cell_from_files(config, traffic))
    spec = cell["config"]["dedup"]
    if cell["config"]["engine"] == "sharded":
        spec["memory_bits"] = 4 * SMALL[spec["variant"]]
        spec["batch_size"] = 1024
    else:
        spec["memory_bits"] = SMALL[spec["variant"]]
        spec["batch_size"] = 512
    arr = cell["traffic"]["arrival"]
    if arr["kind"] == "backlog":
        arr["batches_per_chunk"] = 2
    else:
        arr["rate_per_s"] = seconds_rate
        cell["traffic"]["keys"]["universe"] = 5000
        cell["traffic"]["frontend"]["buckets"] = [8, 16, 32]
    return cell


@pytest.fixture
def small():
    return small_cell
