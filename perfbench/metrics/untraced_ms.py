"""Time inside the harness's bench.admit annotation that no program span
opened inside the admission covers, per committed admission, from the
traced run's profile: what the program's spans leave unexplained."""

import spans


def read(w):
    got = spans.of(w)
    if got is None or not got["totals"]:
        return None
    return 1e3 * got["untraced_s"] / len(w.admissions)
