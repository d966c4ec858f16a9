"""Service and hub linger: seconds a request's hub spends in its ``linger``
phase, syncing with the spokes after its last iteration
(``phase.hub.linger.secs`` over the window's requests)."""

from benchmarks.harness import progtrace


def read(obs):
    return progtrace.phase_per_request_s(obs, "hub.linger")
