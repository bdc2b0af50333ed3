"""descent.sync spans (a fused descent's wait for its result and the
device-to-host reads of it) per committed admission, from the traced run's
profile."""

import spans


def read(w):
    return spans.ms_per_admission(w, "descent.sync")
