import numpy as np

from benchmarks.harness import checks as H


def value(ev, spec):
    """Ingest and the Iter0 batch solve: the median scenario's gap.  A fault
    of ingest, of the solve or of its precision moves every row."""
    gaps = H._iter0_gaps(ev)
    return None if gaps is None else float(np.median(gaps))
