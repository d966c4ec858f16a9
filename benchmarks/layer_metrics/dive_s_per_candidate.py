"""Spoke bound passes: wall seconds of one integer dive, the program's own
phase ``dive`` (``phase.<cylinder>.dive.secs`` over ``.count`` in the
window).  The device time under it is in ``spoke_device_ms_per_iter``."""

from benchmarks.harness import progtrace


def read(obs):
    return progtrace.phase_mean_s(obs, "*.dive")
