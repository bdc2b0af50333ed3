"""Virtual-merge contention estimator (Sec. 4.4).

The dispatcher cannot measure a candidate allocation S against the live
cluster — measuring would perturb the tenants it is trying to avoid.  The
paper's answer is to *virtually merge* S with its co-tenants: collect every
live cross-host job that shares one of S's hosts (and hence its NIC rails),
form the merged rail-demand per host, and conservatively split each host's
rail capacity evenly among the competing collectives.  The result is an
upper bound on the inter-host term S can sustain:

  ``cap(S, L) = min_h (rail_bw(h) / c_h) * min_h(n_h) * 2(k-1)/k * eta``

with ``c_h`` = 1 (S itself) + the number of GPU-disjoint live cross-host
jobs on host h in ledger L.  :class:`ContentionAwarePredictor` then wraps
*any* isolated-bandwidth predictor — the hierarchical surrogate or the
ground truth — as ``min(B_iso(S), cap(S, L))``, so the hybrid search ranks
candidates by the bandwidth they would actually see next to the current
tenants.  Single-host candidates never touch a NIC and pass through
unchanged, as do all candidates under an empty ledger.

The cap evaluates the *same* shared term (``bandwidth_sim.
contended_inter_term``) as the contended ground truth — including the
deterministic per-(hosts, counts) fabric variation, which stands in for
calibration a production dispatcher would measure offline — fed from the
dispatcher's own state: the static topology (rail bandwidths) and its
ledger.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import features as feat_lib
from repro.core import telemetry
from repro.core.bandwidth_sim import (
    INTER_EFF,
    _jitter,
    contended_inter_term,
)
from repro.core.cluster import Cluster
from repro.core.predict_cache import PredictorStats
from repro.core.tenancy import Allocation, JobLedger

Subset = Sequence[int]


@dataclasses.dataclass(frozen=True)
class MergeView:
    """The virtual merge of a candidate subset with its co-tenants."""

    subset: Tuple[int, ...]
    contenders: Tuple[Allocation, ...]   # GPU-disjoint cross-host co-tenants
    merged_gpus: Tuple[int, ...]         # subset U all contender GPUs
    rail_shares: Dict[int, int]          # host id -> c_h (competing collectives)

    @property
    def contended(self) -> bool:
        return bool(self.contenders)


def virtual_merge(cluster: Cluster, ledger: JobLedger, subset: Subset) -> MergeView:
    """Merge ``subset`` with every live cross-host job sharing one of its
    hosts' NIC rails.  Single-host subsets merge with nothing."""
    by_host = cluster.partition_by_host(subset)
    sub = tuple(sorted(subset))
    if len(by_host) <= 1:
        return MergeView(sub, (), sub, {hid: 1 for hid in by_host})
    contenders: Dict[str, Allocation] = {}
    shares: Dict[int, int] = {}
    for hid in by_host:
        jobs = ledger.cross_host_jobs_on(hid, against=sub)
        shares[hid] = 1 + len(jobs)
        for alloc in jobs:
            contenders[alloc.job_id] = alloc
    ordered = tuple(contenders[j] for j in sorted(contenders))
    merged = set(sub)
    for alloc in ordered:
        merged.update(alloc.gpus)
    return MergeView(sub, ordered, tuple(sorted(merged)), shares)


CrossJobsByHost = Dict[int, List[Allocation]]


def _cap_from_snapshot(
    cluster: Cluster, cross_by_host: CrossJobsByHost, subset: Subset,
    eta: float = INTER_EFF, degrade=None,
) -> float:
    by_host = cluster.partition_by_host(subset)
    if len(by_host) <= 1:
        return float("inf")
    sset = set(subset)
    shares = {
        hid: 1 + sum(
            1 for a in cross_by_host.get(hid, ())
            if JobLedger.contends(a, sset)
        )
        for hid in by_host
    }
    # A degraded rail caps the inter term even with zero contenders — the
    # analytic branch's view of nic_flap / link_degrade faults (see
    # repro.core.faults); ``degrade=None`` is the healthy fast path.
    degraded = degrade is not None and any(
        degrade(hid) != 1.0 for hid in by_host
    )
    if all(c == 1 for c in shares.values()) and not degraded:
        return float("inf")
    # Same shared term (and deterministic fabric jitter) the contended
    # ground truth evaluates: the fabric's per-(hosts,counts) variation is
    # measurable offline and independent of tenancy, so folding it in keeps
    # near-symmetric candidates ranked consistently with the truth.
    return contended_inter_term(
        cluster, by_host, lambda hid: shares[hid], eta=eta,
        rail_factor=degrade if degraded else None,
    )


def contended_inter_cap(
    cluster: Cluster, ledger: JobLedger, subset: Subset, eta: float = INTER_EFF
) -> float:
    """Fair-share inter-host rail cap for ``subset`` given the live ledger.

    ``inf`` when no NIC is involved (single-host) or nothing contends — the
    wrapped predictor is then left untouched.
    """
    degrade = (
        ledger.host_degrade
        if getattr(ledger, "health_active", False) else None
    )
    return _cap_from_snapshot(
        cluster, ledger.cross_jobs_by_host(), subset, eta, degrade=degrade
    )


class _SnapshotArrays:
    """Dense per-snapshot arrays for the vectorized cap: contender GPU
    membership masks, per-host touch flags, and static host data.  Built
    once per (ledger uid, version) and reused across every predict call of
    an admission — the hybrid search degrades ~20 candidate batches against
    one unchanged ledger state."""

    def __init__(
        self, cluster: Cluster, cross_by_host: CrossJobsByHost, degrade=None
    ):
        self.gpu_host = np.asarray(cluster.gpu_host, np.int64)
        self.rail_bw = np.asarray(
            [h.host_type.nic_rail_bw for h in cluster.hosts], np.float64
        )
        # Health degrade folded into the rail vector (nic * f, the same
        # float order as the scalar path) + the activation mask that makes
        # a degraded-but-uncontended host still cap the inter term.
        if degrade is None:
            self.degraded = np.zeros(cluster.n_hosts, bool)
        else:
            f = np.asarray(
                [degrade(h.host_id) for h in cluster.hosts], np.float64
            )
            self.degraded = f != 1.0
            self.rail_bw = self.rail_bw * f
        allocs = sorted(
            {a.job_id: a
             for jobs in cross_by_host.values() for a in jobs}.values(),
            key=lambda a: a.job_id,
        )
        nJ = len(allocs)
        self.occ = np.zeros((nJ, cluster.n_gpus), np.int64)
        self.touch = np.zeros((nJ, cluster.n_hosts), np.int64)
        for j, a in enumerate(allocs):
            gs = np.asarray(a.gpus, np.int64)
            self.occ[j, gs] = 1
            self.touch[j, self.gpu_host[gs]] = 1


def _subset_grid(
    snap: _SnapshotArrays, subsets: Sequence[Subset], n_hosts: int, n_gpus: int
):
    """Membership/count grids + contends matrix for a candidate batch."""
    B = len(subsets)
    lens = np.asarray([len(s) for s in subsets], np.int64)
    flat = (
        np.concatenate([np.asarray(s, np.int64) for s in subsets])
        if B and lens.sum() else np.zeros((0,), np.int64)
    )
    rows = np.repeat(np.arange(B, dtype=np.int64), lens)
    counts = np.zeros((B, n_hosts), np.int64)
    np.add.at(counts, (rows, snap.gpu_host[flat]), 1)
    M = np.zeros((B, n_gpus), np.int64)
    M[rows, flat] = 1
    disjoint = ((M @ snap.occ.T) == 0).astype(np.int64)
    return lens, counts, disjoint


def _caps_from_snapshot_batched(
    cluster: Cluster,
    cross_by_host: CrossJobsByHost,
    subsets: Sequence[Subset],
    eta: float = INTER_EFF,
    jitter_cache: Optional[Dict] = None,
    snap: Optional[_SnapshotArrays] = None,
) -> np.ndarray:
    """Vectorized :func:`_cap_from_snapshot` over a candidate batch.

    One numpy program replaces the per-candidate partition + per-host
    contender scan: candidate membership masks matmul against the
    snapshot's contender GPU masks for the disjointness predicate, and the
    per-host contender counts fall out of a second matmul.  The final
    deterministic fabric jitter is the same per-(hosts, counts) hash the
    scalar path evaluates, memoized in ``jitter_cache`` — outputs are
    bit-identical to the loop (regression-pinned in tests/test_fast_path).
    """
    if snap is None:
        snap = _SnapshotArrays(cluster, cross_by_host)
    B = len(subsets)
    lens, counts, disjoint = _subset_grid(
        snap, subsets, cluster.n_hosts, cluster.n_gpus
    )
    part = counts > 0
    n_part = part.sum(axis=1)
    c = 1 + disjoint @ snap.touch                      # [B, n_hosts]

    caps = np.full((B,), np.inf, np.float64)
    # same float program as the scalar path: min over participating hosts
    # of rail_bw / c_h, then rail * min(counts) * (2(k-1)/k) * eta * jitter
    per_host = np.where(part, snap.rail_bw[None, :] / c, np.inf)
    rail = per_host.min(axis=1)
    min_counts = np.where(part, counts, np.iinfo(np.int64).max).min(axis=1)
    active = (n_part > 1) & (((c > 1) | snap.degraded[None, :]) & part).any(
        axis=1
    )
    idx = np.nonzero(active)[0]
    if not len(idx):
        return caps
    ks = lens[idx]
    inter = (
        rail[idx] * min_counts[idx] * (2.0 * (ks - 1) / ks) * eta
    )
    if jitter_cache is None:
        jitter_cache = {}
    for i, b in enumerate(idx):
        key = tuple(
            (int(h), int(counts[b, h])) for h in np.nonzero(part[b])[0]
        )
        j = jitter_cache.get(key)
        if j is None:
            j = _jitter(cluster.name, "inter", key)
            jitter_cache[key] = j
        caps[b] = inter[i] * j
    return caps


PREDICTOR_MODES = ("analytic", "learned")


class ContentionAwarePredictor:
    """Wrap a predictor so ``predict`` returns contention-degraded bandwidth.

    Exposes the same ``predict(list_of_subsets) -> np.ndarray`` protocol the
    hybrid search consumes, so it threads through ``search.hybrid_search``
    unchanged.  The ledger is read live at predict time: one wrapper built at
    service start stays correct across every admit/release.

    Two modes:

    * ``mode="analytic"`` (default) — the virtual-merge fair-share cap:
      ``min(B_iso(S), cap(S, L))``.
    * ``mode="learned"`` — candidates with at least one rail contender are
      scored by a trained :class:`~repro.core.surrogate.
      ContendedSurrogatePredictor` (``contended=...``), clamped by the
      isolated estimate (a co-tenant can never *raise* bandwidth).

    Both modes are exact pass-throughs for single-host candidates,
    uncontended candidates, and the empty ledger — the learned mode returns
    the isolated predictor's output *bit-identically* there
    (regression-pinned in ``tests/test_learned_contention.py``).
    """

    def __init__(
        self,
        cluster: Cluster,
        base,
        ledger: JobLedger,
        mode: str = "analytic",
        contended=None,
        vectorized: bool = True,
    ):
        if mode not in PREDICTOR_MODES:
            raise ValueError(
                f"unknown mode {mode!r}; expected one of {PREDICTOR_MODES}"
            )
        if mode == "learned" and contended is None:
            raise ValueError(
                "mode='learned' needs a contended predictor (contended=...)"
            )
        self.cluster = cluster
        self.base = base
        self.ledger = ledger
        self.mode = mode
        self.contended = contended
        self.vectorized = vectorized
        # Degraded-mode fallback switch: when True (set by faults.
        # install_degraded_fallback on a DriftMonitor alert), the learned
        # branch is bypassed and every candidate is scored by the analytic
        # cap — the surrogate never trained on degraded fabric, so its
        # errors there are structural.
        self.force_analytic = False
        self.stats = PredictorStats()
        self._jitter_cache: Dict = {}
        self._snap_version: Optional[int] = None
        self._snap: Optional[_SnapshotArrays] = None
        self._cap_tab: Optional[np.ndarray] = None
        self._cap_dev: Optional[jax.Array] = None
        self._cap_tab_version: Optional[Tuple[int, int]] = None

    # legacy instrumentation names
    @property
    def n_capped(self) -> int:
        return self.stats.n_capped

    @n_capped.setter
    def n_capped(self, v: int) -> None:
        self.stats.n_capped = v

    @property
    def predict_seconds(self) -> float:
        """Wrapper overhead (excl. base/contended predictor time)."""
        return self.stats.wrapper_seconds

    @predict_seconds.setter
    def predict_seconds(self, v: float) -> None:
        self.stats.wrapper_seconds = v

    def predict(self, subsets: Sequence[Subset]) -> np.ndarray:
        iso = np.asarray(self.base.predict(subsets), dtype=np.float64)
        return self._degrade(subsets, iso)

    def predict_children(self, parent: Sequence[int]) -> np.ndarray:
        """One fused PTS elimination round: the base predictor's incremental
        child path (when it has one) plus one batched cap evaluation."""
        parent = list(parent)
        if hasattr(self.base, "predict_children"):
            iso = np.asarray(self.base.predict_children(parent), np.float64)
        else:
            iso = np.asarray(
                self.base.predict(
                    [parent[:i] + parent[i + 1:] for i in range(len(parent))]
                ),
                np.float64,
            )
        children = [parent[:i] + parent[i + 1:] for i in range(len(parent))]
        return self._degrade(children, iso)

    def _snapshot(self) -> _SnapshotArrays:
        """Per-(ledger version) dense snapshot: the ledger cannot change
        within one predict call, and the hybrid search issues ~20 predict
        batches per admission against one unchanged state — build the
        membership arrays once per version, not once per batch."""
        v = (self.ledger.uid, self.ledger.version)
        if self._snap_version != v:
            degrade = (
                self.ledger.host_degrade
                if getattr(self.ledger, "health_active", False) else None
            )
            self._snap = _SnapshotArrays(
                self.cluster, self.ledger.cross_jobs_by_host(),
                degrade=degrade,
            )
            self._snap_version = v
        return self._snap

    # fused on-device descent ------------------------------------------------

    def eliminate_to(self, parent: Sequence[int], k: int):
        """Run a whole PTS descent on-device *through* the contention cap.

        For a PTS parent of free GPUs, every child is GPU-disjoint from
        every live job, so the analytic cap collapses to a pure function of
        the child's per-host count vector — one float32 table over the
        count lattice (built per ledger version, microseconds of numpy)
        that the scan body gathers alongside the isolated score.  Returns
        the base predictor's :class:`~repro.core.surrogate.ScanResult` or
        None (caller falls back to the host loop): learned mode under a
        contended ledger, non-vectorized wrappers, cap-incompatible bases,
        parents overlapping live jobs and single-host parents all
        decline."""
        base_elim = getattr(self.base, "eliminate_to", None)
        if base_elim is None:
            return None
        health = getattr(self.ledger, "health_active", False)
        if len(self.ledger) == 0 and not health:
            return base_elim(parent, k)  # exact pass-through, like _degrade
        if not self.ledger.busy().isdisjoint(parent):
            return None  # cap depends on disjointness: not table-gatherable
        if len(self.cluster.partition_by_host(parent)) < 2:
            return None  # the base declines it: build and upload no table
        mode = "analytic" if self.force_analytic else self.mode
        tables = getattr(self.base, "tables", None)
        caps = None
        with telemetry.span("cap.table") as sp:
            snap = self._snapshot()
            # no cross-host tenants: both modes leave candidates untouched
            uncapped = snap.touch.shape[0] == 0 and not health
            if (not uncapped and mode == "analytic" and self.vectorized
                    and tables is not None):
                if sp:
                    sp["rebuilt"] = self._cap_tab_version != (
                        self.ledger.uid, self.ledger.version)
                dt = feat_lib.device_tables(self.cluster, tables)
                caps = self._cap_table(dt, snap)
        if uncapped:
            return base_elim(parent, k)
        if caps is None:
            return None
        res = base_elim(parent, k, caps=caps)
        if res is not None:
            self.stats.n_capped += res.n_capped
        return res

    def _cap_table(
        self, dt: "feat_lib.DeviceTables", snap: _SnapshotArrays
    ) -> jax.Array:
        """The analytic cap tabulated over the per-host count lattice, for
        GPU-disjoint candidates against this ledger version.  The same
        float64 program as :func:`_caps_from_snapshot_batched` with
        ``disjoint == 1`` (so ``c_h = 1 + cross-jobs touching h``),
        evaluated per lattice point and cast to float32 once — a device
        gather lands on exactly ``np.float32(host-path cap)``.  Returns
        the table's device copy, uploaded once per ledger version."""
        v = (self.ledger.uid, self.ledger.version)
        if self._cap_tab_version != v or self._cap_tab is None:
            lat = dt.cap_lattice()
            c = 1 + snap.touch.sum(axis=0)                  # [n_hosts]
            per_host = np.where(
                lat.part, snap.rail_bw[None, :] / c[None, :], np.inf
            )
            rail = per_host.min(axis=1)
            min_counts = np.where(
                lat.part, lat.counts, np.iinfo(np.int64).max
            ).min(axis=1)
            active = (lat.n_part > 1) & (
                ((c[None, :] > 1) | snap.degraded[None, :]) & lat.part
            ).any(axis=1)
            caps = np.full((lat.counts.shape[0],), np.inf, np.float64)
            idx = np.nonzero(active)[0]
            if len(idx):
                ks = lat.ks[idx]
                inter = (
                    rail[idx] * min_counts[idx]
                    * (2.0 * (ks - 1) / ks) * INTER_EFF
                )
                caps[idx] = inter * lat.jitter[idx]
            self._cap_tab = caps.astype(np.float32)
            self._cap_dev = jnp.asarray(self._cap_tab)
            self.stats.n_descent_uploads += 1
            self._cap_tab_version = v
        return self._cap_dev

    def _degrade(
        self, subsets: Sequence[Subset], iso: np.ndarray
    ) -> np.ndarray:
        health = getattr(self.ledger, "health_active", False)
        if len(self.ledger) == 0 and not health:
            return iso
        with telemetry.span("cap.degrade"):
            t0 = time.time()
            out = iso.copy()
            inner = 0.0  # time inside the contended model, not the wrapper
            mode = "analytic" if self.force_analytic else self.mode
            if mode == "learned" and self.vectorized:
                snap = self._snapshot()
                _, counts, disjoint = _subset_grid(
                    snap, subsets, self.cluster.n_hosts, self.cluster.n_gpus
                )
                part = counts > 0
                contended = (part.sum(axis=1) > 1) & (
                    ((disjoint @ snap.touch) * part) > 0
                ).any(axis=1)
                learned_mask = contended
                if health:
                    # Degraded fabric: every candidate takes the analytic cap
                    # (the snapshot's rail vector carries the degrade factors),
                    # and the learned head is consulted only for contended
                    # candidates that touch no health-perturbed host — the
                    # surrogate never saw degraded rails in training.
                    caps = _caps_from_snapshot_batched(
                        self.cluster, {}, subsets,
                        jitter_cache=self._jitter_cache, snap=snap,
                    )
                    capped = caps < out
                    out[capped] = caps[capped]
                    self.stats.n_capped += int(capped.sum())
                    learned_mask = contended & ~(
                        part & snap.degraded[None, :]
                    ).any(axis=1)
                idx = np.nonzero(learned_mask)[0].tolist()
                if idx:
                    before = self.contended.predict_seconds
                    learned = self.contended.predict(
                        [subsets[i] for i in idx], self.ledger
                    )
                    inner = self.contended.predict_seconds - before
                    for i, p in zip(idx, learned):
                        if p < out[i]:
                            out[i] = p
                            self.stats.n_capped += 1
                self.stats.wrapper_seconds += time.time() - t0 - inner
                return out
            if self.vectorized:  # analytic: batched caps over the snapshot
                caps = _caps_from_snapshot_batched(
                    self.cluster, {}, subsets,
                    jitter_cache=self._jitter_cache, snap=self._snapshot(),
                )
                capped = caps < out
                out[capped] = caps[capped]
                self.stats.n_capped += int(capped.sum())
                self.stats.wrapper_seconds += time.time() - t0
                return out
            # Legacy scalar paths (the throughput bench's before-side):
            # snapshot the cross-host jobs per host once per call, not per
            # candidate.
            cross_by_host = self.ledger.cross_jobs_by_host()
            degrade = self.ledger.host_degrade if health else None
            if mode == "learned" and health:
                mode = "analytic"  # scalar learned path has no degraded view
            if mode == "learned":
                idx = [
                    i for i, s in enumerate(subsets)
                    if self._contended_by(cross_by_host, s)
                ]
                if idx:
                    # model inference is accounted by the contended predictor's
                    # own predict_seconds; keep this counter wrapper-only
                    before = self.contended.predict_seconds
                    learned = self.contended.predict(
                        [subsets[i] for i in idx], self.ledger
                    )
                    inner = self.contended.predict_seconds - before
                    for i, p in zip(idx, learned):
                        if p < out[i]:
                            out[i] = p
                            self.stats.n_capped += 1
            else:
                for i, s in enumerate(subsets):
                    cap = _cap_from_snapshot(
                        self.cluster, cross_by_host, s, degrade=degrade
                    )
                    if cap < out[i]:
                        out[i] = cap
                        self.stats.n_capped += 1
            self.stats.wrapper_seconds += time.time() - t0 - inner
            return out

    def _contended_by(
        self, cross_by_host: CrossJobsByHost, subset: Subset
    ) -> bool:
        """True iff >=1 live cross-host job contends with ``subset`` — the
        learned head only ever sees inputs with a non-zero ledger context."""
        by_host = self.cluster.partition_by_host(subset)
        if len(by_host) <= 1:
            return False
        sset = set(subset)
        return any(
            JobLedger.contends(a, sset)
            for hid in by_host
            for a in cross_by_host.get(hid, ())
        )

    def predict_one(self, subset: Subset) -> float:
        return float(self.predict([subset])[0])

    def tenant_bandwidths(self) -> Dict[str, float]:
        """Contention-degraded estimate for every *live* tenant, keyed by
        job id.  Each job's own ledger entry self-excludes through the
        ``contends`` predicate, so no bookkeeping is needed to grade a job
        that is already admitted.  This is the predictor-side view the
        defrag planner's gain accounting mirrors (the scheduler's triggers
        evaluate the same sum with the grading simulator — see
        :mod:`repro.core.defrag`)."""
        allocs = list(self.ledger.jobs())
        preds = self.predict([list(a.gpus) for a in allocs])
        return {a.job_id: float(p) for a, p in zip(allocs, preds)}

    def merged_bandwidth(self, subset: Subset) -> float:
        """Isolated-model bandwidth of the merged virtual collective — the
        shared-bottleneck capacity probe from the paper's Sec. 4.4 framing.
        Diagnostic: the fair-share cap, not this probe, drives ``predict``."""
        view = virtual_merge(self.cluster, self.ledger, subset)
        return float(np.asarray(self.base.predict([view.merged_gpus]))[0])
