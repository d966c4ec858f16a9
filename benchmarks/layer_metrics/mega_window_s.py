"""Hub loop: seconds of one megastep window, dispatch to packed fetch, the
program's own phase ``megastep`` (``phase.hub.megastep.secs`` over
``.count`` in the window)."""

from benchmarks.harness import progtrace


def read(obs):
    return progtrace.phase_mean_s(obs, "hub.megastep")
