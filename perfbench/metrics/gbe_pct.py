"""Mean contention-degraded GBE of the graded prefix against the exact
oracle, in percent."""

import numpy as np


def read(w):
    if not w.gbe:
        return None
    return 100.0 * float(np.mean(w.gbe))
