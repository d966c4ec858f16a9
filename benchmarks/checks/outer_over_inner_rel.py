import math


def value(ev, spec):
    """An outer bound may not pass an inner bound."""
    o, i = float(ev["outer"]), float(ev["inner"])
    if not (math.isfinite(o) and math.isfinite(i)):
        return None
    return max(0.0, o - i) / max(1.0, abs(i))
