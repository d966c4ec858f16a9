"""The per-layer metric ``refresh_lowrank_pct`` (PR 33): its reader on sets
of counters, and where ``BENCHMARK.json`` lists it.  (``test_sslp_cell.py``
is a file the benchmark already had, which this PR may not edit: what the
issue asked of it is held here.)"""

import json
import os

import pytest

from benchmarks.harness import core

METRIC = "refresh_lowrank_pct"


@pytest.mark.parametrize("counters, want", [
    ({}, None),                                  # no refresh in the window
    ({"phase.hub.refresh.count": 0.0,
      "refresh.lowrank_kinv": 0.0}, None),
    # the parent's program: refreshes, no such counter
    ({"phase.hub.refresh.count": 5.0,
      "phase.spoke1.refresh.count": 3.0}, 0.0),
    ({"phase.hub.refresh.count": 5.0, "phase.spoke1.refresh.count": 3.0,
      "refresh.lowrank_kinv": 8.0}, 100.0),
    # every cylinder's refreshes under the share, another counter beside it
    ({"phase.hub.refresh.count": 5.0, "phase.spoke2.refresh.count": 5.0,
      "refresh.lowrank_kinv": 5.0, "refresh.lanes_linalg": 5.0}, 50.0),
], ids=["empty", "no_refresh", "no_counter", "all", "half"])
def test_reader(counters, want):
    read = core.load_reader(METRIC)
    got = read({"counters": dict(counters), "window_s": 50.0,
                "iterations": 40})
    assert got is None if want is None else got == pytest.approx(want)


def test_listed_for_sslps_cell_and_not_for_farmers():
    with open(os.path.join(core.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = bench["per_layer"][-1]
    assert entry == {
        "name": METRIC, "unit": "%", "better": "higher",
        "source": "program_counter",
        "layer": "megastep program and sweep kernels",
        "moves": "hub_iter_s", "workloads": ["sslp_10_50_2000.wheel"]}
    # the layer is one the benchmark already names, letter for letter
    assert entry["layer"] in {m["layer"] for m in bench["per_layer"][:-1]}
    reports = lambda cell: {m["name"] for m in bench["per_layer"]
                            if cell in m.get("workloads", ())}
    assert METRIC in reports("sslp_10_50_2000.wheel")
    assert METRIC not in reports("farmer_cm4_s1000.wheel")
    assert METRIC not in reports("farmer_cm4_s1000.serve1")
    assert "refresh_lanes_pct" not in reports("sslp_10_50_2000.wheel")
