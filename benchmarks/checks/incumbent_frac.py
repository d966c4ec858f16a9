import numpy as np

from benchmarks.harness import checks as H


def value(ev, spec):
    """The largest distance of an integer column of the incumbent from a
    whole number.  An inner bound is the price of an INTEGRAL point; a
    reference that reads no ``is_int`` has nothing to compare."""
    inc = H._incumbent(ev)
    if inc is None or not H.ref_has(ev, "is_int"):
        return None
    cols = inc[:, ev["ref"].is_int]
    return float(np.abs(cols - np.round(cols)).max(initial=0.0))
