"""Reduction of a JAX profiler trace (``.xplane.pb``) to device metrics.

* busy: the union of the intervals in which an operation ran on a device
  (the device plane's ``XLA Ops`` line), inside the window the harness's
  ``bench.window`` annotation marks; averaged over the device planes;
* per-operation and per-program device time (``XLA Ops``, ``XLA Modules``);
* idle gaps, each named by the innermost harness annotation that covers it
  on the host: ``bench.dispatch`` (the search), ``bench.admit`` outside
  dispatch (the commit), else the scheduler loop.
"""

from __future__ import annotations

import glob
import os
from collections import defaultdict
from typing import Dict, List, Tuple

Interval = Tuple[int, int]   # [start, end) in ns

HOST_LABELS = (("bench.dispatch", "dispatch"), ("bench.admit", "commit"))


def find(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def _short(name: str) -> str:
    """An XLA op event's HLO text ``%fusion.12 = f32[...] ...`` -> its
    instruction name ``fusion.12``; other names unchanged."""
    head = name.split(" = ", 1)[0]
    return head[1:] if head.startswith("%") else head


def _events(line):
    for e in line.events:
        yield _short(e.name), int(e.start_ns), int(e.start_ns + e.duration_ns)


def _union(iv: List[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for s, e in sorted(iv):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def _clip(iv: List[Interval], lo: int, hi: int) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in iv if e > lo and s < hi]


def _covers(iv: List[Interval], t: int) -> bool:
    import bisect
    i = bisect.bisect_right(iv, (t, float("inf"))) - 1
    return i >= 0 and iv[i][0] <= t < iv[i][1]


def reduce(path: str, window: str = "bench.window") -> Dict:
    from jax.profiler import ProfileData

    return reduce_profile(ProfileData.from_file(path), window)


def reduce_profile(pd, window: str = "bench.window") -> Dict:
    host: Dict[str, List[Interval]] = defaultdict(list)
    devices = []
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            lines = {ln.name: list(_events(ln)) for ln in plane.lines}
            if "XLA Ops" in lines:
                devices.append(lines)
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                for name, s, e in _events(ln):
                    if name.startswith("bench."):
                        host[name].append((s, e))
    if not host.get(window):
        raise ValueError(f"no {window!r} annotation in the trace")
    if not devices:
        raise ValueError("no device plane with an 'XLA Ops' line")
    lo = min(s for s, _ in host[window])
    hi = max(e for _, e in host[window])
    spans = {k: _union(v) for k, v in host.items()}
    busy_ns, ops, modules, gaps = 0, defaultdict(int), defaultdict(int), []
    for lines in devices:
        busy = _union(_clip([(s, e) for _, s, e in lines["XLA Ops"]], lo, hi))
        busy_ns += sum(e - s for s, e in busy)
        for name, s, e in lines["XLA Ops"]:
            if s >= lo and e <= hi:
                ops[name] += e - s
        for name, s, e in lines.get("XLA Modules", []):
            if s >= lo and e <= hi:
                modules[name] += e - s
        edges = [lo] + [t for iv in busy for t in iv] + [hi]
        for s, e in zip(edges[::2], edges[1::2]):
            if e > s:
                mid = (s + e) // 2
                label = next((lab for name, lab in HOST_LABELS
                              if _covers(spans.get(name, []), mid)), "loop")
                gaps.append((label, (e - s) * 1e-9))
    n = len(devices)
    return {
        "window_s": (hi - lo) * 1e-9,
        "busy_s": busy_ns * 1e-9 / n,
        "n_devices": n,
        "ops": {k: v * 1e-9 / n for k, v in ops.items()},
        "modules": {k: v * 1e-9 / n for k, v in modules.items()},
        "gaps": gaps,
    }


def breakdown(red: Dict, top: int = 10) -> Dict:
    ops = sorted(red["ops"].items(), key=lambda kv: -kv[1])[:top]
    gaps = sorted(red["gaps"], key=lambda g: -g[1])[:top]
    return {"device_ops": [[k, v] for k, v in ops],
            "idle_gaps": [[k, v] for k, v in gaps]}


def idle_by_label(red: Dict) -> Dict[str, float]:
    out: Dict[str, float] = defaultdict(float)
    for label, s in red["gaps"]:
        out[label] += s
    return dict(out)
