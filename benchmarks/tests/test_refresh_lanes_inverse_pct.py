"""The per-layer metric ``refresh_lanes_inverse_pct`` (PR 43): its reader
on sets of counters, and where ``BENCHMARK.json`` lists it."""

import json
import os

import pytest

from benchmarks.harness import core

METRIC = "refresh_lanes_inverse_pct"


@pytest.mark.parametrize("counters, want", [
    ({}, None),                                  # no refresh in the window
    ({"phase.hub.refresh.count": 0.0,
      "refresh.lanes_inverse": 0.0}, None),
    # the parent's program: refreshes, its polish on the kernel, no such
    # counter
    ({"phase.hub.refresh.count": 46.0, "phase.spoke1.refresh.count": 30.0,
      "refresh.lanes_linalg": 76.0}, 0.0),
    ({"phase.hub.refresh.count": 46.0, "phase.spoke1.refresh.count": 30.0,
      "refresh.lanes_linalg": 76.0, "refresh.lanes_inverse": 76.0}, 100.0),
    # every cylinder's refreshes under the share
    ({"phase.hub.refresh.count": 5.0, "phase.spoke2.refresh.count": 5.0,
      "refresh.lanes_inverse": 5.0}, 50.0),
], ids=["empty", "no_refresh", "no_counter", "all", "half"])
def test_reader(counters, want):
    read = core.load_reader(METRIC)
    got = read({"counters": dict(counters), "window_s": 26.0,
                "iterations": 736})
    assert got is None if want is None else got == pytest.approx(want)


def test_listed_for_farmers_wheel_alone():
    with open(os.path.join(core.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    (entry,) = [m for m in bench["per_layer"] if m["name"] == METRIC]
    assert entry == {
        "name": METRIC, "unit": "%", "better": "higher",
        "source": "program_counter",
        "layer": "megastep program and sweep kernels",
        "moves": "hub_iter_s", "workloads": ["farmer_cm4_s1000.wheel"]}
    others = [m for m in bench["per_layer"] if m["name"] != METRIC]
    # the layer is one the benchmark already names, letter for letter
    assert entry["layer"] in {m["layer"] for m in others}
    # beside the polish's share, in the same cell and no other
    (polish,) = [m for m in others if m["name"] == "refresh_lanes_pct"]
    assert polish["workloads"] == entry["workloads"]
    cell = core.load_cell("farmer_cm4_s1000.wheel")
    assert METRIC in {m["name"] for m in cell["per_layer"]}
    for name in ("farmer_cm4_s1000.serve1", "sslp_10_50_2000.wheel"):
        assert METRIC not in {m["name"]
                              for m in core.load_cell(name)["per_layer"]}
