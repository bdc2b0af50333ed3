"""Committed admissions in the window over the window's wall seconds."""


def read(w):
    return len(w.admissions) / w.window_s
