from benchmarks.harness import checks as H


def value(ev, spec):
    """The incumbent against the creator's own rows and bounds: the worst
    scenario.  An inner bound is the price of a FEASIBLE point."""
    inc = H._incumbent(ev)
    if inc is None or not H.ref_has(ev, "infeasibility"):
        return None
    return max(ev["ref"].infeasibility(s, inc[s]) for s in range(ev["ref"].S))
