"""Hub loop: seconds of one legacy hub iteration, the program's own phase
``ph_iter`` (``phase.hub.ph_iter.secs`` over ``.count`` in the window)."""

from benchmarks.harness import progtrace


def read(obs):
    return progtrace.phase_mean_s(obs, "hub.ph_iter")
