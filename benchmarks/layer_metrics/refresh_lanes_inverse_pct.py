"""Megastep program and sweep kernels: share of the window's refresh
solves, every cylinder's, whose four explicit inverses of K ran on the
batched elimination kernel (the program's counter
``refresh.lanes_inverse`` over ``phase.<cylinder>.refresh.count``).  A
program without the counter ran none there: 0."""

from benchmarks.harness import progtrace


def read(obs):
    refreshes = progtrace.phase_counter(obs, "*.refresh", "count")
    if not refreshes:
        return None
    return 100.0 * obs["counters"].get("refresh.lanes_inverse", 0) / refreshes
