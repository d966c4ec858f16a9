import numpy as np


def value(ev, spec):
    """PH keeps the probability-weighted mean of W at 0."""
    W = np.asarray(ev["W"], float)
    return float(np.abs(ev["ref"].probs @ W).max()
                 / max(1.0, np.abs(W).max()))
