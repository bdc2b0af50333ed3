"""The fused descent's share of its roofline: the least time its
operations and bytes (ops.py, from the surrogate's shapes) need at the
device's peaks, over the device time of its program (XLA Modules events
named after _pts_scan) in the traced window."""

import ops


def read(w):
    if w.trace is None:
        return None
    seconds = sum(v for k, v in w.trace["modules"].items() if "pts_scan" in k)
    if seconds <= 0 or not w.scans:
        return None
    cl = w.dep.cluster
    lattice = 1
    for h in cl.hosts:
        lattice *= len(h.gpu_ids) + 1
    tables = cl.n_hosts * (1 << max(len(h.gpu_ids) for h in cl.hosts)) * 4 * 3
    c = ops.descent(w.dep.params, w.scans, cl.n_hosts, tables + 4 * lattice)
    return ops.roofline_pct(c["flops"], c["bytes"], seconds, w.extra["device_kind"])
