"""Megastep program and sweep kernels: share of the window's refresh
solves, every cylinder's, whose factors apply K^-1 as diagonal plus low
rank (the program's counter ``refresh.lowrank_kinv`` over
``phase.<cylinder>.refresh.count``).  A program without the counter made
none such: 0."""

from benchmarks.harness import progtrace


def read(obs):
    refreshes = progtrace.phase_counter(obs, "*.refresh", "count")
    if not refreshes:
        return None
    return 100.0 * obs["counters"].get("refresh.lowrank_kinv", 0) / refreshes
