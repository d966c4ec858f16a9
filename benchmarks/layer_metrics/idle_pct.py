"""Device: share of the traced slice in which no operation ran on the
device (1 - union of the device's operation intervals over the slice)."""


def read(obs):
    return None if obs.get("trace") is None else obs["trace"]["idle_pct"]
