"""Host rescue: rows that ``spopt._rescue_stragglers`` re-solved on the
host per hub iteration, every cylinder's, inside the window (the program's
counter ``rescue.rows`` over the window's hub iterations)."""


def read(obs):
    n = obs["counters"].get("rescue.rows")
    if n is None or not obs["iterations"]:
        return None
    return n / obs["iterations"]
