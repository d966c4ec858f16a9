import numpy as np

from benchmarks.harness import checks as H


def value(ev, spec):
    """How far the incumbent's first-stage columns differ between scenarios,
    against their size (1 at least).  An inner bound is the price of a
    NONANTICIPATIVE point."""
    inc = H._incumbent(ev)
    if inc is None:
        return None
    na = inc[:, ev["ref"].nonant]
    return float((na.max(axis=0) - na.min(axis=0)).max(initial=0.0)
                 / max(1.0, np.abs(na).max(initial=0.0)))
