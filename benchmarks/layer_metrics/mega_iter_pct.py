"""Hub loop: share of the window's hub iterations that ran inside a
megastep window (``dispatch.mega_iterations`` over hub iterations)."""


def read(obs):
    n = obs["counters"].get("dispatch.mega_iterations")
    if n is None or not obs["iterations"]:
        return None
    return 100.0 * n / obs["iterations"]
