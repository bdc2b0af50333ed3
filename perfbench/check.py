"""The comparison that decides ``correct``.

Three numbers are compared, over what the window itself produced:

* ``invalid``: committed placements that are not k distinct GPUs of the
  cluster, all free before the admission, or that differ from the subset
  the search returned (exact, every admission of the window);
* ``off_argmax``: decisions of the sampled admissions in which the search
  took an option that its own scores rank below another (exact);
* ``score_gap``: the widest relative gap |program - reference| / reference
  between a score the search used and the plain reference's score of the
  same placement under the same ledger: every candidate batch, every
  elimination round (fused descent or per-round applies), the EHA-against-
  PTS choice and the committed placement's score, of the sampled
  admissions.

The control puts the reference computed in three bfloat16 passes in the
program's place: its scores at every decision of the same admissions, and
its own best option taken.
"""

from __future__ import annotations

from typing import Dict, List, Sequence, Tuple

import numpy as np

import reference


def fabric(dep) -> reference.Fabric:
    return reference.Fabric.from_config(dep.cfg)


def invalid(w) -> int:
    n_gpus = w.dep.cluster.n_gpus
    bad = 0
    for a in w.admissions:
        ok = (len(a.gpus) == a.k and len(set(a.gpus)) == a.k
              and all(0 <= g < n_gpus and not a.busy >> g & 1 for g in a.gpus)
              and sorted(a.gpus) == sorted(a.result_subset))
        bad += not ok
    return bad


def _children(parent: Sequence[int], slots) -> List[Tuple[int, ...]]:
    cur = [parent[i] for i in slots]
    return [tuple(g for g in cur if g != parent[i]) for i in slots]


def _next_subset(d) -> Tuple[int, ...]:
    """The placement a record starts from: the parent of an elimination
    round or descent, or the one subset of a final scoring."""
    kind, a, _ = d
    return tuple(sorted(a[0] if kind == "predict" else a))


def decisions(a) -> List[Tuple[List[Tuple[int, ...]], np.ndarray, object]]:
    """-> [(options, program scores, index the search took or None)] of one
    admission, in search order: the EHA candidates, each elimination round
    (fused descent or per-round), EHA against PTS, the committed score."""
    out = []
    srch = a.search
    eha = tuple(sorted(srch.eha[0]))
    recs = srch.decisions
    for i, (kind, x, y) in enumerate(recs):
        if kind == "scan":                  # the device's own choices
            for r in range(y.n_rounds):
                live = np.nonzero(y.sels[r])[0]
                out.append((_children(x, live),
                            y.scores[r][live].astype(np.float64),
                            int(np.flatnonzero(live == y.elims[r])[0])))
            continue
        if kind == "children":
            subs = _children(x, range(len(x)))
            nxt = _next_subset(recs[i + 1]) if i + 1 < len(recs) else None
        else:
            subs = [tuple(sorted(s)) for s in x]
            nxt = eha if len(subs) > 1 else None
        taken = subs.index(nxt) if nxt in subs else None
        out.append((subs, np.asarray(y, np.float64), taken))
    pts = tuple(sorted(srch.pts[0]))
    out.append(([eha, pts], np.asarray([srch.eha[1], srch.pts[1]], np.float64),
                0 if srch.winner == "EHA" else 1))
    out.append(([tuple(sorted(a.gpus))], np.asarray([srch.predicted]), None))
    return out


def readings(fab, adms, mode: str, params, contended_params,
             control: bool = False) -> Dict[str, float]:
    """``score_gap`` and ``off_argmax`` of the program on ``adms``; with
    ``control``, of the control put in its place on the same decisions."""
    groups, items = [], []
    for a in adms:
        for subs, prog, taken in decisions(a):
            groups.append((len(items), len(subs), prog, taken))
            items.extend((s, a.snapshot) for s in subs)
    ref = reference.scores(fab, items, mode, params, contended_params)
    if control:
        ctl = reference.scores(fab, items, mode, params, contended_params,
                               precision="bf16x3")
        groups = [(lo, n, ctl[lo:lo + n],
                   None if taken is None else int(np.argmax(ctl[lo:lo + n])))
                  for lo, n, _, taken in groups]
    score_gap = max((float(np.max(np.abs(prog - ref[lo:lo + n])
                                  / ref[lo:lo + n]))
                     for lo, n, prog, _ in groups), default=0.0)
    off = sum(1 for _, _, prog, taken in groups
              if taken is not None and prog[taken] < prog.max())
    return {"score_gap": score_gap, "off_argmax": off, "n_scores": len(items)}
