import math

from benchmarks.harness import checks as H


def value(ev, spec):
    """An outer bound may not pass the optimum."""
    ef, o = H._ef(ev), float(ev["outer"])
    if ef is None or not math.isfinite(o):
        return None
    return max(0.0, o - ef) / max(1.0, abs(ef))
