import math

from benchmarks.harness import checks as H


def value(ev, spec):
    """An inner bound may not lie under the optimum."""
    ef, i = H._ef(ev), float(ev["inner"])
    if ef is None or not math.isfinite(i):
        return None
    return max(0.0, ef - i) / max(1.0, abs(ef))
