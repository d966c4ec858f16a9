import math

from benchmarks.harness import checks as H


def value(ev, spec):
    """The hub's inner bound against the probability-weighted objective of
    the incumbent, priced by the reference: the bound is what the point
    costs."""
    inc, i = H._incumbent(ev), float(ev["inner"])
    if inc is None or not math.isfinite(i) or not H.ref_has(ev, "objective"):
        return None
    ref = ev["ref"]
    return H._rel(i, ref.probs @ ref.objective(inc))
