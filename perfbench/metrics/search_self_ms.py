"""dispatch() time per committed admission outside featurization, model
applies, fused descents and the contention wrapper: the EHA and PTS search
code itself (and the prediction memo)."""


def read(w):
    s = w.stats
    own = (w.dispatch_s - s.featurize_seconds - s.infer_seconds
           - s.scan_seconds - s.wrapper_seconds)
    return 1e3 * own / len(w.admissions)
