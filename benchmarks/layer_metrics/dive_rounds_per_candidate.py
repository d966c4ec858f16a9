"""Spoke bound passes: batch solves that one integer dive took
(``xhat.dive_rounds`` over ``phase.<cylinder>.dive.count``: a candidate
whose second stage holds integer columns is one dive of up to
``xhat_dive_rounds`` rounds, each a solve of the whole batch)."""

from benchmarks.harness import progtrace


def read(obs):
    dives = progtrace.phase_counter(obs, "*.dive", "count")
    rounds = obs["counters"].get("xhat.dive_rounds")
    if not dives or rounds is None:
        return None
    return rounds / dives
