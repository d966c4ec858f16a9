"""``harness/progtrace.py``: the join of device runs to the threads that
launched them and the sums by cylinder, on made-up tuples and on a
two-thread trace recorded on the chip (``data/threads.xplane.pb``, made by
``record_trace_threads.py``, what was launched and what the chip's own run
of the reduction read beside it in ``threads.expected.json``)."""

import json
import os

import pytest

from benchmarks.harness import progtrace as pt

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
MAIN = [("main", "build", 0, 10), ("hub", "iter0", 12, 30),
        ("hub", "refresh", 13, 20), ("hub", "fetch", 14, 15),
        ("hub", "sync", 25, 29), ("main", "teardown", 40, 50)]


def line(phases=(), produces=(), consumes=(), enqueues=(), marks=()):
    return {"phases": list(phases), "produces": list(produces),
            "consumes": list(consumes), "enqueues": list(enqueues),
            "marks": list(marks)}


@pytest.mark.parametrize("t, want", [
    (5, ("main", "build")), (12.5, ("hub", "iter0")),
    (14.5, ("hub", "fetch")), (17, ("hub", "refresh")),
    (22, ("hub", "iter0")), (27, ("hub", "sync")),
    # outside any phase: the cylinder of the phase that started last
    (11, ("main", None)), (35, ("hub", None)), (60, ("main", None)),
    (-1, ("main", None)),
])
def test_a_thread_s_cylinder_is_its_innermost_phase_s(t, want):
    assert pt.cylinder_at(pt.nest(MAIN), t) == want
    assert pt.cylinder_at([], t) == (None, None)


def two_threads():
    """The hub's thread (line 0) and a spoke's (line 2) launch through
    their runtime lines (1 and 3).  Launch ids are of type 14, the
    runtime's own links of type 7.  The hub's first launch (id 7, run 70)
    and the spoke's (9, 90) are enqueued inside their own Execute; the
    hub's second (8, run 80) had to wait and a worker (line 4) enqueues
    it, consuming what the Execute produced."""
    return [
        line(phases=[("hub", "megastep", 100, 200)],
             produces=[(110, (14, 7)), (150, (14, 8))]),
        line(consumes=[((14, 7), 111, 120), ((7, 17), 113, 118),
                       ((14, 8), 151, 160)],
             produces=[(112, (7, 17)), (152, (7, 18))],
             enqueues=[(115, 70)]),
        line(phases=[("spoke1", "pass", 90, 300), ("spoke1", "refresh",
                                                   120, 180)],
             produces=[(130, (14, 9))]),
        line(consumes=[((14, 9), 131, 140)], enqueues=[(135, 90)]),
        line(consumes=[((7, 18), 170, 180)], enqueues=[(175, 80)]),
    ]


def test_runs_join_the_thread_that_launched_them_and_nothing_is_guessed():
    runs = [("jit_mega", 1000, 1400, 70), ("jit_solve", 1400, 2000, 90),
            ("jit_mega", 2000, 2300, 80),
            ("jit_lost", 2300, 2400, 99),       # no enqueue with its run_id
            ("jit_none", 2400, 2500, None),     # no run_id at all
            # enqueued before the trace began: a lower run_id than any
            # enqueue the trace holds
            ("jit_early", 900, 1000, 69)]
    got = pt.join(two_threads(), runs)
    assert [(p, c, ph) for p, _, _, c, ph in got] == [
        ("jit_mega", "hub", "megastep"), ("jit_solve", "spoke1", "refresh"),
        ("jit_mega", "hub", "megastep"), ("jit_lost", None, None),
        ("jit_none", None, None), ("jit_early", pt.BEFORE, None)]
    by = pt.device_time(got, 950, 2450)
    assert by == {"hub": {"megastep": 400.0 + 300.0},
                  "spoke1": {"refresh": 600.0},
                  None: {None: 100.0 + 50.0}, pt.BEFORE: {None: 50.0}}


def test_a_broken_or_doubled_link_leaves_the_run_unjoined():
    first, waited = ("jit_mega", 1000, 1400, 70), ("jit_mega", 2000, 2300, 80)

    def cylinders(lines):
        return [j[3] for j in pt.join(lines, [first, waited])]

    assert cylinders(two_threads()) == ["hub", "hub"]
    lines = two_threads()
    lines[1]["enqueues"][0] = (125, 70)         # outside its Execute
    assert cylinders(lines) == [None, "hub"]
    lines = two_threads()
    lines[3]["enqueues"].append((136, 70))      # the run_id twice
    assert cylinders(lines) == [None, "hub"]
    lines = two_threads()
    lines[2]["produces"].append((131, (14, 7)))  # the launch id twice
    assert cylinders(lines) == [None, "hub"]
    lines = two_threads()
    lines[1]["produces"].pop()                  # the worker's link lost
    assert cylinders(lines) == ["hub", None]
    lines = two_threads()
    lines[0]["phases"] = []                     # a thread with no phase
    assert cylinders(lines) == [None, None]


def test_idle_goes_to_the_shortest_phase_of_any_thread_or_to_nobody():
    nested = [pt.nest([("hub", "linger", 0, 100), ("hub", "sync", 40, 50)]),
              pt.nest([("spoke1", "wait", 30, 80)])]
    gaps = [(10, 20), (42, 46), (60, 70), (100, 130)]
    assert pt.idle_by_phase(gaps, nested) == {
        "hub:linger": 10.0, "hub:sync": 4.0, "spoke1:wait": 10.0, None: 30.0}


def test_reduce_sums_by_cylinder_between_the_markers_and_the_boundaries():
    lines = two_threads()
    lines[0]["marks"] = [("bench:trace_start", 50, 51),
                         ("bench:iter=4", 1000, 1000),
                         ("bench:iter=6", 2000, 2000),
                         ("bench:trace_stop", 2600, 2601)]
    runs = {"/device:TPU:0": [("jit_mega", 1000, 1400, 70),
                              ("jit_solve", 1400, 1900, 90),
                              ("jit_mega", 2100, 2300, 80),
                              ("jit_lost", 2300, 2400, 99)]}
    red = pt.reduce({"lines": lines, "runs": runs})
    assert (red["runs"], red["unjoined"], red["before_slice"]) == (4, 1, 0)
    assert red["device_s"]["hub"]["megastep"] == pytest.approx(600e-9)
    assert red["device_s"]["None"]["None"] == pytest.approx(100e-9)
    assert red["iterations"]["count"] == 2
    assert red["iterations"]["device_s"] == {
        "hub": {"megastep": pytest.approx(400e-9)},
        "spoke1": {"refresh": pytest.approx(500e-9)}}
    # idle: 51..1000 (nobody's: the phases end at 300), 1900..2100, 2400..2600
    assert red["idle_s"] == pytest.approx((949 + 200 + 200) * 1e-9)
    assert red["idle_unexplained_s"] == pytest.approx(red["idle_s"])
    obs = {"_progtrace": red}
    assert pt.device_ms_per_iter(obs, "hub") == pytest.approx(400e-6 / 2)
    assert pt.device_ms_per_iter(obs, "spoke*") == pytest.approx(500e-6 / 2)


def test_iterations_are_counted_in_whole_turns_behind_what_cannot_be_joined():
    from benchmarks.harness import tracered

    # two marks a boundary; the hub's cycle: one iteration, then a window
    marks = [(0, 50), (1, 50), (10, 51), (11, 51), (20, 66), (21, 66),
             (30, 67), (40, 82), (41, 82)]
    # nothing in the way: the slice's first boundary to its last
    assert pt.whole_turns(marks, 0, 100, clear=0) == (0, 41, 32)
    assert pt.whole_turns(marks, 0, 100, 0) == tracered.iteration_span(
        marks, 0, 100)
    # a run launched before the trace holds the device until 5: the next
    # boundary that a one-iteration step follows is iteration 66
    assert pt.whole_turns(marks, 0, 100, clear=5) == (20, 41, 16)
    assert pt.whole_turns(marks, 0, 100, clear=25) is None
    assert pt.whole_turns([], 0, 100, 0) is None
    # ... and reduce() starts its iterations there
    lines = two_threads()
    lines[0]["marks"] = [("bench:trace_start", 50, 51)] + [
        (f"bench:iter={it}", t, t) for t, it in
        [(1000, 4), (1500, 5), (2000, 20), (2500, 21), (3000, 36)]
    ] + [("bench:trace_stop", 3100, 3101)]
    runs = {"/device:TPU:0": [("jit_early", 900, 1200, 60),
                              ("jit_mega", 1200, 1400, 70),
                              ("jit_solve", 2100, 2600, 90),
                              ("jit_mega", 2600, 2900, 80)]}
    red = pt.reduce({"lines": lines, "runs": runs})
    assert (red["unjoined"], red["before_slice"]) == (0, 1)
    assert red["iterations"]["count"] == 16             # 20 ... 36
    assert red["iterations"]["device_s"] == {
        "spoke1": {"refresh": pytest.approx(500e-9)},
        "hub": {"megastep": pytest.approx(300e-9)}}
    assert red["device_s"][pt.BEFORE]["None"] == pytest.approx(300e-9)


def test_no_phase_or_no_device_run_reads_as_nothing():
    lines = two_threads()
    runs = {"/device:TPU:0": [("jit_mega", 1000, 1400, 70)]}
    assert pt.reduce({"lines": lines, "runs": {}}) is None
    for rec in lines:
        rec["phases"] = []
    assert pt.reduce({"lines": lines, "runs": runs}) is None
    # an untraced run, and the parent's: no tracer, no counter
    obs = {"tracer": None, "trace": None, "counters": {"host_sync.count": 3},
           "requests": [{}], "iterations": 5}
    assert pt.of(obs) is None and pt.device_ms_per_iter(obs, "hub") is None
    assert pt.phase_counter(obs, "hub.sync", "secs") is None
    assert pt.phase_mean_s(obs, "hub.ph_iter") is None
    assert pt.phase_per_request_s(obs, "*.ingest") is None


def test_phase_counters_are_summed_over_the_cylinders_asked_for():
    obs = {"counters": {"phase.hub.sync.secs": 2.0, "phase.hub.sync.count": 4,
                        "phase.spoke1.pass.count": 3.0,
                        "phase.spoke2.pass.count": 5.0,
                        "phase.main.ingest.secs": 1.5,
                        "phase.hub.ingest.secs": 0.5,
                        "phase.hub.passage.count": 99.0},
           "requests": [{}, {}]}
    assert pt.phase_counter(obs, "spoke*.pass", "count") == 8.0
    assert pt.phase_counter(obs, "hub.pass", "count") is None
    assert pt.phase_mean_s(obs, "hub.sync") == 0.5
    assert pt.phase_per_request_s(obs, "*.ingest") == 1.0


def test_recorded_two_thread_trace_joins_every_run_to_its_thread():
    import jax

    expected = json.load(open(os.path.join(DATA, "threads.expected.json")))
    loaded = pt.load(jax.profiler.ProfileData.from_file(
        os.path.join(DATA, "threads.xplane.pb")))
    (runs,) = loaded["runs"].values()
    joined = pt.join(loaded["lines"], runs)
    got = {}
    for program, _, _, cyl, phase in joined:
        by = got.setdefault(cyl, {}).setdefault(str(phase), {})
        by[program] = by.get(program, 0) + 1
    # what each thread launched, by construction: in its phase its own
    # program and the one both share, then one launch outside any phase,
    # each run on the launching thread's cylinder
    assert got == expected["launched"]
    # both shapes of the chain are in the recording: enqueued by the
    # launching thread's own runtime line, and by a worker's
    launch_lines = {i for i, rec in enumerate(loaded["lines"])
                    if rec["phases"]}
    direct = {i for i, rec in enumerate(loaded["lines"]) if rec["enqueues"]
              and any(c[0][0] == 14 for c in rec["consumes"])}
    worker = {i for i, rec in enumerate(loaded["lines"]) if rec["enqueues"]
              and not any(c[0][0] == 14 for c in rec["consumes"])}
    assert len(launch_lines) == 2 and direct and worker
    red = pt.reduce(loaded)
    assert red["unjoined"] == 0 and red["before_slice"] == 0
    assert red["runs"] == sum(n for c in expected["launched"].values()
                              for by in c.values() for n in by.values())
    on_chip = expected["reduced_on_the_chip"]
    assert red["device_s"] == {
        c: {ph: pytest.approx(s) for ph, s in by.items()}
        for c, by in on_chip["device_s"].items()}
    total = sum(e - s for _, s, e, _ in runs) / 1e9
    assert sum(s for by in red["device_s"].values()
               for s in by.values()) == pytest.approx(total)
    # each round sleeps 30 ms inside a phase and, a launch later, 20 ms
    # outside any, both threads at once: the second sleep is nobody's, and
    # so is the host's work on the launch between them
    assert red["idle_unexplained_s"] == pytest.approx(
        on_chip["idle_unexplained_s"])
    assert red["idle_unexplained_s"] >= expected["slept_outside_s"] * 0.8
    assert red["idle_s"] - red["idle_unexplained_s"] \
        >= expected["slept_in_phase_s"] * 0.8
