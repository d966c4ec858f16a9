"""Megastep program and sweep kernels: share of the sweeps they were
allowed that the window's solves spent, every cylinder's and every kind's
(100 x ``solve.*.*.sweeps`` over ``solve.*.*.budget``: a frozen solve and a
megastep iteration may spend ``max_iter``, a refresh ``restarts x
max_iter``).  Near 100: no solve ends before its budget does."""

from benchmarks.harness import outcomes


def read(obs):
    return outcomes.share(obs, "sweeps", "budget")
