"""PredictorStats.scan_seconds (fused descents on the host clock, uploads
and the result sync included) per committed admission."""


def read(w):
    return 1e3 * w.stats.scan_seconds / len(w.admissions)
