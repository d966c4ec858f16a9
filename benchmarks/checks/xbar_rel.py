import numpy as np

from benchmarks.harness import checks as H


def value(ev, spec):
    """Compute_Xbar at the window's end, redone in numpy."""
    if not H.ref_has(ev, "xbar_of"):
        return None
    want = ev["ref"].xbar_of(ev["x"])
    got = np.asarray(ev["xbars"], float)
    return float(np.abs(got - want[None, :]).max()
                 / max(1.0, np.abs(want).max()))
