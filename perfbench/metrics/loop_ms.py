"""Window time outside admit() per committed admission: the scheduler's
event loop, releases and records (and the harness's own bookkeeping)."""


def read(w):
    return 1e3 * (w.window_s - w.admit_s) / len(w.admissions)
