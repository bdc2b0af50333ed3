"""cap.table spans (the contention snapshot and the analytic cap's lattice
table a fused descent gathers from) per committed admission, from the
traced run's profile."""

import spans


def read(w):
    return spans.ms_per_admission(w, "cap.table")
