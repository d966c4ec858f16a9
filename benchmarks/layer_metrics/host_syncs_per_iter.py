"""Hub loop: host-blocking fetches per hub iteration (``host_sync.count``
over hub iterations).  The registry is process-wide, so the spokes' own
fetches are counted too."""


def read(obs):
    n = obs["counters"].get("host_sync.count")
    if n is None or not obs["iterations"]:
        return None
    return n / obs["iterations"]
