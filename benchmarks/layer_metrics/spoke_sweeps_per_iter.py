"""Spoke bound passes: sweeps of the whole (S, n) batch that the SPOKES'
solves ran per hub iteration (``solve.spoke<n>.<kind>.sweeps``, all spokes,
over the window's hub iterations)."""

from benchmarks.harness import outcomes


def read(obs):
    return outcomes.sweeps_per_iter(obs, "spoke")
