"""PredictorStats.wrapper_seconds (the contention wrapper, without the
models it calls) per committed admission."""


def read(w):
    return 1e3 * w.stats.wrapper_seconds / len(w.admissions)
