"""Hub loop: milliseconds per hub iteration that the hub spends in its
``sync`` phase, sending W and nonants and reading the spokes' bounds
(``phase.hub.sync.secs`` over the window's hub iterations)."""

from benchmarks.harness import progtrace


def read(obs):
    secs = progtrace.phase_counter(obs, "hub.sync", "secs")
    if secs is None or not obs["iterations"]:
        return None
    return 1e3 * secs / obs["iterations"]
