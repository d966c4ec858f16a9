"""Megastep program and sweep kernels: sweeps of the whole (S, n) batch
that the HUB's solves ran per hub iteration: ``solve.hub.<kind>.sweeps`` of
its frozen attempts, refresh solves and megastep windows, with the iterate
a window discarded (``solve.hub.mega.rejected_sweeps``), over the window's
hub iterations.  Fewer sweeps and faster sweeps read the same in
milliseconds; this is the first of the two."""

from benchmarks.harness import outcomes


def read(obs):
    return outcomes.sweeps_per_iter(obs, "hub")
