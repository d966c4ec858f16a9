"""Host integer tier: share of the window that a cylinder spent in
per-scenario host MILPs on the rows that dive and retries left
(``phase.<cylinder>.host_milp.secs`` over the window): the host's share of
the integer work.  A program that has the phase and never entered it: 0."""

from benchmarks.harness import progtrace


def read(obs):
    if progtrace.phase_counter(obs, "*.dive", "count") is None \
            or not obs["window_s"]:
        return None
    secs = progtrace.phase_counter(obs, "*.host_milp", "secs") or 0.0
    return 100.0 * secs / obs["window_s"]
