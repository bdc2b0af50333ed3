"""Seconds spent training the cell's surrogates in set-up (train_seconds,
to block_until_ready, summed)."""


def read(w):
    return w.dep.train_s
