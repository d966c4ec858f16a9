"""Hub loop: share of the window that the hub's thread spent blocked in
host fetches that nothing overlapped (``host_sync.blocked_secs.hub`` over
the window; the process-wide counter sums the spokes' threads too)."""


def read(obs):
    secs = obs["counters"].get("host_sync.blocked_secs.hub")
    if secs is None or not obs["window_s"]:
        return None
    return 100.0 * secs / obs["window_s"]
