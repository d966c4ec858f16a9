"""The per-layer metric ``sweep_kernel_checkpoint_pct`` (PR 48): its reader
on sets of counters, and where ``BENCHMARK.json`` lists it."""

import json
import os

import pytest

from benchmarks.harness import core

METRIC = "sweep_kernel_checkpoint_pct"


@pytest.mark.parametrize("counters, want", [
    ({}, None),                                  # no refresh in the window
    ({"phase.hub.refresh.count": 0.0,
      "refresh.kernel_checkpoint": 0.0}, None),
    # the parent's program: refreshes, its inverses on the lanes kernel,
    # its checkpoints in XLA and no such counter
    ({"phase.hub.refresh.count": 46.0, "phase.spoke1.refresh.count": 30.0,
      "refresh.lanes_inverse": 76.0}, 0.0),
    ({"phase.hub.refresh.count": 46.0, "phase.spoke1.refresh.count": 30.0,
      "refresh.lanes_inverse": 76.0, "refresh.kernel_checkpoint": 76.0},
     100.0),
    # every cylinder's refreshes under the share
    ({"phase.hub.refresh.count": 5.0, "phase.spoke2.refresh.count": 5.0,
      "refresh.kernel_checkpoint": 5.0}, 50.0),
], ids=["empty", "no_refresh", "no_counter", "all", "half"])
def test_reader(counters, want):
    read = core.load_reader(METRIC)
    got = read({"counters": dict(counters), "window_s": 17.6,
                "iterations": 737})
    assert got is None if want is None else got == pytest.approx(want)


def test_listed_for_farmers_wheel_alone():
    with open(os.path.join(core.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    # found by name, wherever later PRs append theirs
    (entry,) = [m for m in bench["per_layer"] if m["name"] == METRIC]
    assert entry == {
        "name": METRIC, "unit": "%", "better": "higher",
        "source": "program_counter",
        "layer": "megastep program and sweep kernels",
        "moves": "hub_iter_s", "workloads": ["farmer_cm4_s1000.wheel"]}
    others = [m for m in bench["per_layer"] if m["name"] != METRIC]
    # the layer is one the benchmark already names, letter for letter
    assert entry["layer"] in {m["layer"] for m in others}
    # beside the other shares of the dense engine's refresh, in the same
    # cell and no other
    (inverse,) = [m for m in others
                  if m["name"] == "refresh_lanes_inverse_pct"]
    assert inverse["workloads"] == entry["workloads"]
    cell = core.load_cell("farmer_cm4_s1000.wheel")
    assert METRIC in {m["name"] for m in cell["per_layer"]}
    for name in ("farmer_cm4_s1000.serve1", "sslp_10_50_2000.wheel"):
        assert METRIC not in {m["name"]
                              for m in core.load_cell(name)["per_layer"]}
