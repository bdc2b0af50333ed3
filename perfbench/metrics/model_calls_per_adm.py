"""Candidates sent through a Transformer by per-round applies
(PredictorStats.n_model_calls) per committed admission."""


def read(w):
    return w.stats.n_model_calls / len(w.admissions)
