"""95th percentile of every window admission's time from admit() entry
to commit, in milliseconds (host clock)."""

import numpy as np


def read(w):
    return 1e3 * float(np.percentile([a.admit_s for a in w.admissions], 95))
