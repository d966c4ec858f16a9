import numpy as np

from benchmarks.harness import checks as H


def value(ev, spec):
    """The last iterates of every scenario against the creator's own rows
    and bounds: the worst."""
    if not H.ref_has(ev, "infeasibility"):
        return None
    x = np.asarray(ev["x"], float)
    return max(ev["ref"].infeasibility(s, x[s]) for s in range(ev["ref"].S))
