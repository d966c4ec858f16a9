"""``sweep_width_pct``: the reader on a recorded set of outcome counters,
on a program that counts no widths (the parent's) and on an empty window,
and its ``BENCHMARK.json`` entry, found by name."""

import json
import os

import pytest

from benchmarks.harness import core

WHEELS = ["farmer_cm4_s1000.wheel", "sslp_10_50_2000.wheel"]
SERVED = ["farmer_cm4_s1000.serve1"]
# what a program that counts no widths says of such a window (PR 44's)
RECORDED = {
    "solve.hub.refresh.sweeps": 4000.0, "solve.hub.refresh.budget": 4000.0,
    "solve.hub.mega.sweeps": 14000.0, "solve.hub.mega.budget": 15000.0,
    "solve.spoke2.refresh.sweeps": 9000.0, "solve.spoke2.refresh.rows": 3e3,
    "phase.hub.refresh.count": 1.0, "dispatch.mega_iterations": 15.0,
}
# at S=1000: the hub's refresh ran its last two restarts at one block of
# 128, its megastep iterations the second half of their budgets at 256; a
# spoke's cold refreshes never narrowed
WIDTHS = {
    "solve.hub.refresh.narrow_sweeps": 2000.0,
    "solve.hub.refresh.row_sweeps": 2000.0 * 1000 + 2000.0 * 128,
    "solve.hub.refresh.full_row_sweeps": 4000.0 * 1000,
    "solve.hub.mega.narrow_sweeps": 7000.0,
    "solve.hub.mega.row_sweeps": 7000.0 * 1000 + 7000.0 * 256,
    "solve.hub.mega.full_row_sweeps": 14000.0 * 1000,
    "solve.spoke2.refresh.narrow_sweeps": 0.0,
    "solve.spoke2.refresh.row_sweeps": 9000.0 * 1000,
    "solve.spoke2.refresh.full_row_sweeps": 9000.0 * 1000,
}


def _obs(counters):
    return {"counters": dict(counters), "iterations": 16, "window_s": 0.8,
            "requests": [], "records": [], "trace": None, "_progtrace": None}


def test_reader():
    read = core.load_reader("sweep_width_pct")
    want = 100.0 * (2256e3 + 8792e3 + 9000e3) / (4000e3 + 14000e3 + 9000e3)
    assert read(_obs(dict(RECORDED, **WIDTHS))) == pytest.approx(want)
    # an engine that never narrows reports its full width
    full = {"solve.hub.mega.row_sweeps": 5e6,
            "solve.hub.mega.full_row_sweeps": 5e6}
    assert read(_obs(full)) == 100.0
    # a program that counts no widths, and an empty window: nothing, no error
    assert read(_obs(RECORDED)) is None
    assert read(_obs({})) is None


def test_entry_found_by_name():
    with open(os.path.join(core.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    (entry,) = [m for m in bench["per_layer"]
                if m["name"] == "sweep_width_pct"]
    assert entry == {
        "name": "sweep_width_pct", "unit": "%", "better": "lower",
        "source": "program_counter",
        "layer": "megastep program and sweep kernels",
        "moves": "hub_iter_s", "workloads": WHEELS}
    older = bench["per_layer"][:bench["per_layer"].index(entry)]
    assert entry["layer"] in {m["layer"] for m in older}
    for cell in WHEELS + SERVED:
        listed = "sweep_width_pct" in {
            m["name"] for m in core.load_cell(cell)["per_layer"]}
        assert listed == (cell in WHEELS), cell
