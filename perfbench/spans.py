"""The program's own spans in a traced run's profile.

The program opens a ``jax.profiler.TraceAnnotation`` for every telemetry
span (``repro.core.telemetry.span``), so the profiler records them on its
host plane, on the clock of the device's ``XLA Ops``.  Inside the harness's
``bench.window`` annotation this module reduces them to:

* ``totals``: per span name, the seconds its events cover (``counts``: how
  many events);
* ``untraced_s``: time inside ``bench.admit`` that no program span opened
  inside the admission covers (``sched.admit``, which encloses the
  harness's annotation, does not count);
* ``gaps``: the device's idle gaps, each named by the harness label
  ``xtrace`` gives it and the innermost program span over its midpoint,
  ``<label>/<span>`` (``dispatch/descent.sync``), else the label alone.

A profile of a program that opens no such spans gives empty ``totals`` and
the metric readers report nothing.
"""

from __future__ import annotations

import bisect
import functools
import pathlib
from typing import Dict, List, Optional, Tuple

import xtrace

# where run.py's traced run leaves its profile (run.TRACE_DIR)
TRACE_DIR = pathlib.Path(__file__).resolve().parents[1] / ".perfbench_trace"

# The program's span names on the admission path (docs/observability.md).
PROGRAM = (
    "sched.admit", "dispatcher.dispatch", "search.eha", "search.pts",
    "descent", "descent.prep", "descent.upload", "descent.launch",
    "descent.sync", "cap.table", "cap.degrade", "featurize", "apply",
    "ledger.admit", "ledger.release", "py.gc",
)

Interval = Tuple[int, int]


def _length(iv: List[Interval]) -> int:
    return sum(e - s for s, e in iv)


def _intersect(a: List[Interval], b: List[Interval]) -> List[Interval]:
    """Intersection of two sorted lists of disjoint intervals."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if s < e:
            out.append((s, e))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def innermost(spans: Dict[str, List[Interval]], t: int) -> Optional[str]:
    """The program span over ``t`` that opened last, or None.  ``spans``
    maps each name to its intervals sorted by start."""
    best, best_start = None, -1
    for name, iv in spans.items():
        i = bisect.bisect_right(iv, (t, float("inf"))) - 1
        # same-name spans do not nest, so the last one opened before t is
        # the only candidate
        if i >= 0 and iv[i][0] <= t < iv[i][1] and iv[i][0] > best_start:
            best, best_start = name, iv[i][0]
    return best


def label(harness: Dict[str, List[Interval]],
          spans: Dict[str, List[Interval]], t: int) -> str:
    """``xtrace``'s label of an instant, with the innermost program span
    appended where one covers it."""
    lab = next((lab for name, lab in xtrace.HOST_LABELS
                if xtrace._covers(harness.get(name, []), t)), "loop")
    inner = innermost(spans, t)
    return lab if inner is None else f"{lab}/{inner}"


def collect(pd, window: str = "bench.window") -> Dict:
    host: Dict[str, List[Interval]] = {}
    busy: List[List[Interval]] = []
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            for ln in plane.lines:
                if ln.name == "XLA Ops":
                    busy.append([(s, e) for _, s, e in xtrace._events(ln)])
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                for name, s, e in xtrace._events(ln):
                    if name.startswith("bench.") or name in PROGRAM:
                        host.setdefault(name, []).append((s, e))
    if not host.get(window):
        raise ValueError(f"no {window!r} annotation in the trace")
    lo = min(s for s, _ in host[window])
    hi = max(e for _, e in host[window])
    harness = {k: xtrace._union(v) for k, v in host.items()
               if k.startswith("bench.")}
    spans = {k: sorted(xtrace._clip(v, lo, hi)) for k, v in host.items()
             if k in PROGRAM}
    spans = {k: v for k, v in spans.items() if v}
    admits = harness.get("bench.admit", [])
    inside = xtrace._union([iv for v in spans.values() for iv in v
                            if xtrace._covers(admits, iv[0])])
    gaps = []
    for iv in busy:
        iv = xtrace._union(xtrace._clip(iv, lo, hi))
        edges = [lo] + [t for b in iv for t in b] + [hi]
        for s, e in zip(edges[::2], edges[1::2]):
            if e > s:
                gaps.append((label(harness, spans, (s + e) // 2),
                             (e - s) * 1e-9))
    return {
        "totals": {k: _length(xtrace._union(v)) * 1e-9
                   for k, v in spans.items()},
        "counts": {k: len(v) for k, v in spans.items()},
        "admit_s": _length(admits) * 1e-9,
        "untraced_s": (_length(admits) - _length(_intersect(admits, inside)))
        * 1e-9,
        "gaps": gaps,
    }


@functools.lru_cache(maxsize=2)
def _collect_file(path: str, mtime_ns: int) -> Dict:
    from jax.profiler import ProfileData

    return collect(ProfileData.from_file(path))


def of(w) -> Optional[Dict]:
    """The reduction of ``w``'s traced window; None in an untraced run."""
    if w.trace is None:
        return None
    path = xtrace.find(str(TRACE_DIR))
    return _collect_file(path, pathlib.Path(path).stat().st_mtime_ns)


def ms_per_admission(w, name: str) -> Optional[float]:
    """Milliseconds of span ``name`` per committed admission, or None where
    the traced program opened no such span."""
    got = of(w)
    if got is None or name not in got["totals"]:
        return None
    return 1e3 * got["totals"][name] / len(w.admissions)
