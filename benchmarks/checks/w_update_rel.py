import numpy as np

from benchmarks.harness import checks as H


def value(ev, spec):
    """Update_W over every single-iteration hub step of the window, redone
    in numpy: the worst."""
    if not H.ref_has(ev, "w_after"):
        return None
    ref, rho = ev["ref"], np.asarray(ev["rho"], float)
    worst = None
    for st in H._steps(ev):
        want = ref.w_after(st["W_prev"], rho, st["x"][:, ref.nonant],
                           st["xbars"])
        gap = float(np.abs(st["W"] - want).max()
                    / max(1.0, np.abs(want).max()))
        worst = gap if worst is None else max(worst, gap)
    return worst
