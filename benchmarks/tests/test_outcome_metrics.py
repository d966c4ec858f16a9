"""The nine per-layer metrics that read the program's outcome counters
(``solve.<cylinder>.<kind>.<field>``, ``xhat.*``): each reader on a
recorded set of counters, on the parent's set (which has none of them) and
on an empty window, and where ``BENCHMARK.json`` lists each, found by name.
"""

import json
import os

import pytest

from benchmarks.harness import core, outcomes

WHEELS = ["farmer_cm4_s1000.wheel", "sslp_10_50_2000.wheel"]
SERVED = ["farmer_cm4_s1000.serve1"]
KERNELS = "megastep program and sweep kernels"

# a window of 16 hub iterations at S=1000: a refresh and a megastep window
# of 15 on the hub (one more iterate discarded), a Lagrangian whose frozen
# attempt is thrown away, three candidates of which two are refused
RECORDED = {
    "solve.hub.refresh.sweeps": 4000.0,
    "solve.hub.refresh.budget": 4000.0, "solve.hub.refresh.rows": 1000.0,
    "solve.hub.refresh.rows_done": 940.0,
    "solve.hub.refresh.rows_in_tol": 1000.0, "solve.hub.refresh.age": 1.0,
    "solve.hub.mega.sweeps": 14000.0,
    "solve.hub.mega.budget": 15000.0, "solve.hub.mega.all_done": 2.0,
    "solve.hub.mega.rejected_sweeps": 1000.0,
    "solve.spoke1.frozen.count": 1.0, "solve.spoke1.frozen.sweeps": 1000.0,
    "solve.spoke1.frozen.budget": 1000.0, "solve.spoke1.frozen.rows": 1000.0,
    "solve.spoke1.frozen.rows_done": 10.0,
    "solve.spoke1.frozen.rows_in_tol": 60.0,
    "solve.spoke1.frozen.accepted": 0.0,
    "solve.spoke1.refresh.sweeps": 3052.0,
    "solve.spoke1.refresh.budget": 4000.0,
    "solve.spoke1.refresh.rows": 1000.0,
    "solve.spoke1.refresh.rows_done": 50.0,
    "solve.spoke1.refresh.declined": 1.0,
    "solve.spoke2.refresh.sweeps": 9000.0,
    "solve.spoke2.refresh.budget": 12000.0,
    "solve.spoke2.refresh.rows": 3000.0,
    "solve.spoke2.refresh.rows_done": 2000.0,
    "solve.spoke2.refresh.cold": 3.0,
    "solve.main.refresh.sweeps": 948.0,
    "solve.main.refresh.budget": 4000.0, "solve.main.refresh.rows": 1000.0,
    "solve.main.refresh.rows_done": 1000.0,
    # the program's other keys under the same heads are not outcomes
    "solve.divergence_freezes": 7.0, "xhat.dive_rounds": 11.0,
    "xhat.candidates": 3.0, "xhat.infeasible": 2.0,
    "xhat.infeasible_rows": 500.0, "xhat.infeasible_of_rows": 2000.0,
    "xhat.improved": 1.0,
    "phase.hub.refresh.count": 1.0, "dispatch.mega_iterations": 15.0,
}
# what the parent's program counts in such a window: phases, no outcome
PARENT = {k: RECORDED[k] for k in (
    "solve.divergence_freezes", "xhat.dive_rounds", "phase.hub.refresh.count",
    "dispatch.mega_iterations")}

HUB = 4000.0 + 14000.0 + 1000.0
SPOKES = 1000.0 + 3052.0 + 9000.0
ALL = 4000.0 + 14000.0 + 1000.0 + 3052.0 + 9000.0 + 948.0

METRICS = [
    # name, unit, better, source, layer, moves, cells, value on RECORDED
    ("hub_sweeps_per_iter", "sweeps/iter", "lower", "program_counter",
     KERNELS, "hub_iter_s", WHEELS, HUB / 16),
    ("spoke_sweeps_per_iter", "sweeps/iter", "lower", "program_counter",
     "spoke bound passes", "hub_iter_s", WHEELS, SPOKES / 16),
    ("sweep_budget_spent_pct", "%", "lower", "program_counter", KERNELS,
     "hub_iter_s", WHEELS, 100.0 * ALL / 40000.0),
    ("solve_rows_done_pct", "%", "higher", "program_counter", KERNELS,
     "hub_iter_s", WHEELS, 100.0 * 4000.0 / 7000.0),
    ("frozen_accept_pct", "%", "higher", "program_counter", "hub loop",
     "hub_iter_s", WHEELS, 0.0),
    # the hub's runs held the device 0.25 s of the slice's 8 whole-turn
    # iterations
    ("device_us_per_sweep", "us/sweep", "lower", "device_trace", KERNELS,
     "hub_iter_s", WHEELS, 1e6 * 0.25 / 8 / (HUB / 16)),
    ("xhat_infeasible_pct", "%", "lower", "program_counter",
     "spoke bound passes", "hub_iter_s", WHEELS, 100.0 * 2 / 3),
    ("xhat_infeasible_rows_pct", "%", "lower", "program_counter",
     "spoke bound passes", "hub_iter_s", WHEELS, 25.0),
    ("sweeps_per_request", "sweeps/request", "lower", "program_counter",
     "service and hub linger", "request_s", SERVED, (ALL + 1000.0) / 2),
]
NAMES = [m[0] for m in METRICS]


# the trace's reduction by cylinder and phase (``progtrace.reduce``): the
# whole slice, and the whole turns of the hub's cycle inside it
DEVICE_S = {"hub": {"megastep": 0.24, "refresh": 0.06},
            "spoke1": {"frozen": 0.02, "refresh": 0.07},
            "before_slice": {"None": 0.01}}
TURNS = {"count": 8, "span_s": 0.4,
         "device_s": {"hub": {"megastep": 0.2, "refresh": 0.05},
                      "spoke1": {"frozen": 0.02, "refresh": 0.07}}}


def _obs(counters, turns=TURNS):
    return {
        "counters": dict(counters), "iterations": 16, "window_s": 0.8,
        "requests": [{"iters": 8}, {"iters": 8}], "records": [],
        "trace": {"busy_s": 4.5, "window_s": 5.0, "idle_pct": 10.0,
                  "iterations": {"count": 10, "span_s": 0.5,
                                 "program_busy_s": {}}},
        "_progtrace": {"device_s": DEVICE_S, "iterations": turns},
    }


@pytest.mark.parametrize("metric", METRICS, ids=NAMES)
def test_reader_and_entry(metric):
    name, unit, better, source, layer, moves, cells, want = metric
    read = core.load_reader(name)
    assert read(_obs(RECORDED)) == pytest.approx(want, rel=1e-12)
    # the parent's program and an empty window: nothing to read, no error
    assert read(_obs(PARENT)) is None
    assert read(_obs({})) is None
    untraced = dict(_obs(RECORDED), trace=None, _progtrace=None)
    if source == "device_trace":
        assert read(untraced) is None
    else:
        assert read(untraced) == pytest.approx(want, rel=1e-12)
    # the entry, found by name wherever later entries put it
    with open(os.path.join(core.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    (entry,) = [m for m in bench["per_layer"] if m["name"] == name]
    assert entry == {"name": name, "unit": unit, "better": better,
                     "source": source, "layer": layer, "moves": moves,
                     "workloads": cells}
    older = bench["per_layer"][:bench["per_layer"].index(entry)]
    assert layer in {m["layer"] for m in older if m["name"] not in NAMES}
    for cell in WHEELS + SERVED:
        listed = name in {m["name"]
                          for m in core.load_cell(cell)["per_layer"]}
        assert listed == (cell in cells), cell


def test_frozen_accept_pct_counts_every_cylinder_and_needs_an_attempt():
    read = core.load_reader("frozen_accept_pct")
    both = dict(RECORDED, **{"solve.hub.frozen.count": 3.0,
                             "solve.hub.frozen.accepted": 3.0})
    assert read(_obs(both)) == pytest.approx(75.0)
    none = {k: v for k, v in RECORDED.items() if ".frozen." not in k}
    assert read(_obs(none)) is None


def test_device_us_per_sweep_falls_back_on_the_whole_slice():
    """Where a run launched before the trace began leaves the slice no
    whole turn of its own, the slice itself is the turns: the harness cuts
    it at two hub boundaries of one kind."""
    read = core.load_reader("device_us_per_sweep")
    assert read(_obs(RECORDED, turns=None)) == pytest.approx(
        1e6 * 0.30 / 10 / (HUB / 16), rel=1e-12)
    no_hub = {k: v for k, v in RECORDED.items() if ".hub." not in k}
    assert read(_obs(no_hub)) is None


def test_xhat_infeasible_rows_pct_reads_zero_where_none_was_refused():
    read = core.load_reader("xhat_infeasible_rows_pct")
    priced = {"xhat.candidates": 6.0, "xhat.improved": 2.0}
    assert read(_obs(priced)) == 0.0
    assert core.load_reader("xhat_infeasible_pct")(_obs(priced)) == 0.0
    assert read(_obs({"xhat.improved": 2.0})) is None


def test_outcomes_total_reads_four_part_keys_alone():
    obs = _obs(RECORDED)
    assert outcomes.total(obs, "sweeps", "hub") == 18000.0
    assert outcomes.total(obs, "sweeps", "spoke") == SPOKES
    assert outcomes.total(obs, "count", kinds=("frozen",)) == 1.0
    assert outcomes.total(obs, "freezes") is None
    assert outcomes.total(obs, "sweeps", "spoke3") is None
    assert outcomes.sweeps(obs) == ALL + 1000.0
