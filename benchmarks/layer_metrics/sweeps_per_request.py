"""Service and hub linger: sweeps of the whole (S, n) batch that every
cylinder's solves ran in the window, over the window's requests
(``solve.*.*.sweeps``, discarded iterates included).  Most of a request's
30 s linger is the spokes' solves."""

from benchmarks.harness import outcomes


def read(obs):
    n = outcomes.sweeps(obs)
    if n is None or not obs["requests"]:
        return None
    return n / len(obs["requests"])
