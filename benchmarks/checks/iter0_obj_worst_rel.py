from benchmarks.harness import checks as H


def value(ev, spec):
    """Ingest and the Iter0 batch solve: the worst scenario's gap.  A row
    whose data went wrong, or that was left unsolved, shows here."""
    gaps = H._iter0_gaps(ev)
    return None if gaps is None else float(gaps.max())
