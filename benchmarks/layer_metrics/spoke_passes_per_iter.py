"""Spoke bound passes: bound passes that the spokes completed per hub
iteration (``phase.spoke<n>.pass.count``, all spokes, over the window's
hub iterations)."""

from benchmarks.harness import progtrace


def read(obs):
    n = progtrace.phase_counter(obs, "spoke*.pass", "count")
    if n is None or not obs["iterations"]:
        return None
    return n / obs["iterations"]
