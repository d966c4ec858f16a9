from benchmarks.harness import checks as H


def value(ev, spec):
    """The expected objective of the last iterates against the reference's
    extensive form: PH's fixed point is the EF's optimum."""
    ref, ef = ev["ref"], H._ef(ev)
    if ef is None:
        return None
    return H._rel(ref.probs @ ref.objective(ev["x"]), ef)
