from benchmarks.harness import checks as H


def value(ev, spec):
    """The subproblem solves of every single-iteration hub step of the
    window: the worst gap over steps and drawn scenarios."""
    if not H.ref_has(ev, "prox_gap"):
        return None
    g = H.prox_gaps(ev, spec["n_check"])
    return float(g.max()) if g.size else None
