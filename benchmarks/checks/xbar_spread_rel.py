import numpy as np

from benchmarks.harness import checks as H


def value(ev, spec):
    """Consensus: the probability-weighted mean distance of the scenarios'
    first stages from their mean, against the mean's size."""
    if not H.ref_has(ev, "xbar_of"):
        return None
    ref, x = ev["ref"], np.asarray(ev["x"], float)
    xbar = ref.xbar_of(x)
    dev = np.abs(x[:, ref.nonant] - xbar[None, :]).mean(axis=1)
    return float(ref.probs @ dev / max(1.0, np.abs(xbar).mean()))
