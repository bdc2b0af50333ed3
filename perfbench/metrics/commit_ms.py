"""admit() time outside dispatch() per committed admission: the
ledger commit (JobLedger.admit) and the availability read."""


def read(w):
    return 1e3 * (w.admit_s - w.dispatch_s) / len(w.admissions)
