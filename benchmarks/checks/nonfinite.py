import math

import numpy as np


def value(ev, spec):
    """Count of non-finite entries in the PH state, NaN bounds included."""
    n = sum(int((~np.isfinite(np.asarray(ev[k], float))).sum())
            for k in ("x", "W", "xbars"))
    return float(n + math.isnan(float(ev["outer"]))
                 + math.isnan(float(ev["inner"])))
