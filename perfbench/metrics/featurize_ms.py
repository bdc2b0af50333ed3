"""PredictorStats.featurize_seconds per committed admission."""


def read(w):
    return 1e3 * w.stats.featurize_seconds / len(w.admissions)
