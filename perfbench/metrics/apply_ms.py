"""PredictorStats.infer_seconds (per-round model applies on the host
clock, padding, upload and sync included) per committed admission."""


def read(w):
    return 1e3 * w.stats.infer_seconds / len(w.admissions)
