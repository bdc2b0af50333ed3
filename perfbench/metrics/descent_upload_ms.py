"""descent.upload spans (a fused descent's host-to-device uploads of its
tables and arguments, _scan_args) per committed admission, from the traced
run's profile."""

import spans


def read(w):
    return spans.ms_per_admission(w, "descent.upload")
