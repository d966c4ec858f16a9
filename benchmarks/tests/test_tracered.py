"""The trace reduction: on made-up intervals, and on a small trace recorded
on the v5e (``data/small.xplane.pb``, written by ``record_trace.py``)."""

import json
import os

import pytest

from benchmarks.harness import tracered as T

HERE = os.path.dirname(os.path.abspath(__file__))
MS = 1e6


def test_union_merges_nested_and_touching():
    assert T.union([(5, 7), (0, 3), (1, 2), (3, 4), (9, 9)]) == [(0, 4), (5, 7)]


def test_busy_and_gaps_inside_a_window():
    merged = T.union([(0, 10), (20, 30), (25, 40), (70, 90)])
    assert T.busy_ns(merged, 5, 80) == 5 + 20 + 10
    assert T.gaps(merged, 5, 80) == [(40, 70), (10, 20)]     # longest first
    assert T.gaps([], 0, 10) == [(0, 10)]
    assert T.gaps(merged, 0, 100)[-1] in [(90, 100), (10, 20)]


def test_gaps_go_to_the_innermost_annotation():
    notes = [("bench:request", 0, 100), ("bench:hub_step", 10, 30),
             ("bench:hub_linger", 60, 90)]
    out = T.attribute([(12, 20), (40, 50), (65, 85), (120, 130)], notes)
    assert out == {"bench:hub_step": 8, "bench:request": 10,
                   "bench:hub_linger": 20, "unannotated": 10}


def _planes(ops, host):
    return {"/device:TPU:0": {"XLA Ops": ops,
                              "XLA Modules": [("jit_mega(123)", 0, 40 * MS)]},
            "/host:CPU": {"python3": host}}


def test_reduce_planes_between_the_markers():
    ops = [("%fusion.1", 0, 10 * MS), ("%while.2", 20 * MS, 40 * MS),
           ("%fusion.3", 25 * MS, 30 * MS), ("%late", 150 * MS, 160 * MS)]
    host = [(T.START, -1, 0), (T.STOP, 100 * MS, 100 * MS + 5),
            ("bench:hub_step", 5 * MS, 45 * MS)]
    red = T.reduce_planes(_planes(ops, host))
    assert red["window_from_markers"] and red["devices"] == 1
    assert red["iterations"] is None
    assert red["window_s"] == pytest.approx(0.100)
    assert red["busy_s"] == pytest.approx(0.030)
    assert red["idle_pct"] == pytest.approx(70.0)
    assert red["device_ops"] == [["jit_mega", pytest.approx(0.040)]]
    assert dict(map(tuple, red["idle_gaps"])) == {
        "bench:hub_step": pytest.approx(0.010),
        "unannotated": pytest.approx(0.060)}


def test_iterations_and_program_time_between_the_hub_boundaries():
    # boundaries at 10, 30 and 90 ms; the one at 150 ms is past the slice
    marks = [(10 * MS, 48), (30 * MS, 49), (90 * MS, 64), (150 * MS, 65)]
    assert T.iteration_span(marks, 0, 100 * MS) == (10 * MS, 90 * MS, 16)
    assert T.iteration_span(marks[:1], 0, 100 * MS) is None
    # the next request's hub starts at 0 again: a falling count adds nothing
    assert T.iteration_span([(0, 49), (1, 50), (2, 0), (3, 5)], 0, 9)[2] == 6
    progs = [("jit_mega", 0, 20 * MS), ("jit_mega", 40 * MS, 50 * MS),
             ("jit_solve_batch_factored", 50 * MS, 95 * MS)]
    assert T.program_busy(progs, 10 * MS, 90 * MS) == {
        "jit_mega": 20 * MS, "jit_solve_batch_factored": 40 * MS}
    ops = [("%fusion.1", 0, 95 * MS)]
    host = [(T.START, -1, 0), (T.STOP, 100 * MS, 100 * MS + 5),
            ("bench:hub_step", 5 * MS, 45 * MS)] + [
        (T.ITER + str(it), t, t + 10) for t, it in marks]
    planes = _planes(ops, host)
    planes["/device:TPU:0"]["XLA Modules"] = [
        (n + "(77)", s, e) for n, s, e in progs]
    red = T.reduce_planes(planes)
    assert red["iterations"]["count"] == 16
    assert red["iterations"]["span_s"] == pytest.approx(0.080)
    assert red["iterations"]["program_busy_s"] == {
        "jit_mega": pytest.approx(0.020),
        "jit_solve_batch_factored": pytest.approx(0.040)}
    # the instants are no spans: no idle time goes to them
    assert not any(n.startswith(T.ITER) for n, _ in red["idle_gaps"])

    from benchmarks.harness import core
    read = core.load_reader("device_ms_per_iter")
    obs = {"trace": red, "workload": {"device_programs": ["jit_mega"]}}
    assert read(obs) == pytest.approx(20.0 / 16)
    obs["workload"]["device_programs"] = ["jit_mega", "jit_solve_batch"]
    assert read(obs) == pytest.approx(60.0 / 16)
    assert read({"trace": dict(red, iterations=None),
                 "workload": obs["workload"]}) is None


def test_a_trace_without_device_operations_is_an_error():
    with pytest.raises(RuntimeError, match="no operation on a TPU plane"):
        T.reduce_planes({"/host:CPU": {"python3": [(T.START, 0, 1)]}})


def test_short_names():
    assert T.short("%fused_sweeps.5 = (f32[44,1000]{1,0}) custom-call(...)") \
        == "%fused_sweeps.5"
    assert len(T.short("x" * 500)) == 80


def test_recorded_trace_reduces_to_what_was_recorded():
    """The trace was recorded on the chip around three bursts of matrix
    products with sleeps between them, inside ``bench:hub_step`` spans;
    ``small.expected.json`` holds what the recorder itself measured on the
    host clock."""
    path = os.path.join(HERE, "data", "small.xplane.pb")
    want = json.load(open(os.path.join(HERE, "data", "small.expected.json")))
    red = T.reduce_planes(T.load_file(path))
    assert red["window_from_markers"] and red["devices"] == 1
    # the slice as the trace's markers bound it is the slice the recorder
    # timed on the host clock, to a few milliseconds
    assert red["window_s"] == pytest.approx(want["host_window_s"], abs=0.02)
    # the device idles through the sleeps and no longer than the slice
    assert want["slept_s"] - 0.02 <= red["window_s"] - red["busy_s"] \
        <= red["window_s"]
    assert 0.0 < red["busy_s"] <= want["host_busy_bound_s"] + 0.02
    assert red["idle_pct"] == pytest.approx(
        100 * (1 - red["busy_s"] / red["window_s"]))
    names = [n for n, _ in red["device_ops"]]
    assert any(n.startswith("jit_burst") for n in names)
    gaps = dict(map(tuple, red["idle_gaps"]))
    # the sleeps sit inside the recorder's spans, so the idle time is theirs
    assert gaps.get("bench:hub_step", 0.0) >= want["slept_s"] - 0.02
