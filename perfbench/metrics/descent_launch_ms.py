"""descent.launch spans (a fused descent's executable lookup and launch)
per committed admission, from the traced run's profile."""

import spans


def read(w):
    return spans.ms_per_admission(w, "descent.launch")
