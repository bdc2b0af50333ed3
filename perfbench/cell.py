"""One cell of the benchmark: set-up, the measured window, grading.

A cell is ``<config>.<mix>``.  Everything that belongs to one configuration,
one traffic mix or one metric is a file of its own, found by name:

* ``configs/<config>.json``: the cluster as its host-type list, and the
  surrogate's training recipe;
* ``traffic/<mix>.json``: the traffic parameters, naming a generator
  ``traffic/<generator>.py``;
* ``metrics/<metric>.py``: a reader ``read(window) -> float | None``;
* ``limits/<cell>.json``: the limits of the numbers ``correct`` compares.

The program is reached only through public ``repro.core`` names and
``repro.core.baselines.oracle_dispatch``.
"""

from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import pathlib
import time
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent


class WindowClosed(Exception):
    """Raised at the first admission after the window's deadline."""


def load_json(path: pathlib.Path) -> Dict:
    with open(path) as f:
        return json.load(f)


def _module(path: pathlib.Path):
    spec = importlib.util.spec_from_file_location(
        f"perfbench_{path.parent.name}_{path.stem}".replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def workload(bench: Dict, name: str) -> Dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise SystemExit(f"unknown workload {name!r}")


def config(name: str, base: Optional[pathlib.Path] = None) -> Dict:
    return load_json((base or BENCH) / "configs" / f"{name}.json")


def mix(name: str, base: Optional[pathlib.Path] = None) -> Dict:
    return load_json((base or BENCH) / "traffic" / f"{name}.json")


def generator(m: Dict, base: Optional[pathlib.Path] = None):
    return _module((base or BENCH) / "traffic" / f"{m['generator']}.py")


def reader(metric: str, base: Optional[pathlib.Path] = None):
    return _module((base or BENCH) / "metrics" / f"{metric}.py")


def limits(cell: str, base: Optional[pathlib.Path] = None) -> Dict[str, float]:
    return load_json((base or BENCH) / "limits" / f"{cell}.json")["limits"]


# -- set-up -------------------------------------------------------------------

@dataclasses.dataclass
class Deployment:
    """What set-up builds once per run: the cluster, the fabric that stands
    in for it, and the trained model(s) the dispatcher serves."""

    cfg: Dict
    mix: Dict
    cluster: Any
    sim: Any                 # grading fabric (fair or saturating)
    tables: Any
    params: Any
    contended_params: Any = None
    train_s: float = 0.0


def deploy(core, cfg: Dict, m: Dict, steps: Optional[int] = None) -> Deployment:
    """Train the configuration's surrogate(s) with the recipe of its file.
    ``steps`` overrides the step count (CPU tests only)."""
    cluster = core.Cluster([tuple(h) for h in cfg["hosts"]],
                           name=cfg["cluster_name"])
    fair = core.BandwidthSimulator(cluster)
    tables = core.IntraHostTables(cluster, fair)
    s = cfg["surrogate"]
    train, _ = core.make_train_test_split(fair, s["train_samples"],
                                          seed=s["split_seed"])
    params, info = core.train_surrogate(
        cluster, tables, train,
        core.TrainConfig(steps=steps or s["steps"], seed=s["train_seed"]))
    dep = Deployment(cfg, m, cluster, fair, tables, params,
                     train_s=info["train_seconds"])
    if m["simulator_contention"] != "fair":
        dep.sim = core.BandwidthSimulator(
            cluster, contention=m["simulator_contention"])
    if m["contention_mode"] == "learned":
        c = cfg["contended_surrogate"]
        ctrain, _ = core.make_contended_split(
            dep.sim, c["samples"], test_mult=1, seed=c["split_seed"],
            max_cotenants=c["max_cotenants"])
        dep.contended_params, cinfo = core.train_contended_surrogate(
            cluster, tables, core.to_triples(cluster, ctrain),
            core.TrainConfig(steps=steps or c["steps"], seed=c["train_seed"]),
            base_params=params)
        dep.train_s += cinfo["train_seconds"]
    return dep


def dispatcher(core, dep: Deployment):
    """A fresh service with its AOT warm-up, as a deployment starts one."""
    pred = core.SurrogatePredictor(dep.cluster, dep.tables, dep.params)
    if dep.mix["contention_mode"] == "learned":
        cpred = core.ContendedSurrogatePredictor(
            dep.cluster, dep.tables, dep.contended_params)
        return core.BandPilotDispatcher(
            dep.cluster, dep.tables, pred, contention_mode="learned",
            contended_predictor=cpred)
    return core.BandPilotDispatcher(dep.cluster, dep.tables, pred)


def trace_jobs(core, dep: Deployment, seed_words, n_jobs: int, prefix: str):
    gen = generator(dep.mix)
    return [core.TraceJob(*j)
            for j in gen.generate(dep.mix, list(seed_words), n_jobs, prefix)]


def scheduler(core, dep: Deployment, disp):
    return core.AdmissionScheduler(
        dep.cluster, dep.sim, dep.tables, disp,
        config=core.SchedulerConfig(policy=dep.mix["policy"]), grade=False)


def warm_shapes(core, dep: Deployment, disp) -> None:
    """Apply each model once at every batch a search can send it, assuming
    nothing of the program's padding: every row count up to twice the
    cluster's GPUs, on 2..hosts candidate hosts and, for the contended
    model, under one and two cross-host jobs contending on each of them."""
    cl = dep.cluster
    rows = range(1, 2 * cl.n_gpus + 1)
    for h in range(2, cl.n_hosts + 1):
        subset = [cl.hosts[i].gpu_ids[0] for i in range(h)]
        for r in rows:
            disp.raw_predictor.predict([subset] * r)
    cpred = disp.contended_predictor
    if cpred is None:
        return
    for h in range(2, cl.n_hosts + 1):
        subset = [cl.hosts[x].gpu_ids[0] for x in range(h)]
        for j in (1, 2):
            led = core.JobLedger(cl)
            for i in range(j):
                led.admit(f"warm-{i}", [cl.hosts[x].gpu_ids[1 + i]
                                        for x in range(h)])
            for r in rows:
                cpred.predict([subset] * r, led)


def warm_up(core, dep: Deployment, disp) -> None:
    """Compile (or read from the persistent cache) in set-up every program
    shape the window meets: each model-apply batch, then a fixed warm-up
    replay through the whole admission path."""
    warm_shapes(core, dep, disp)
    jobs = trace_jobs(core, dep, [dep.cfg["warmup_seed"], 1, 0],
                      dep.mix["warmup_jobs"], "warm")
    scheduler(core, dep, disp).run(jobs)


# -- the measured window ------------------------------------------------------

class Recorder:
    """Pass-through view of the dispatcher's predictor that keeps what each
    search scored: candidate batches, elimination rounds, fused descents."""

    def __init__(self, inner, stats):
        self.base = inner
        self.stats = stats          # empty: the inner chain keeps the counts
        self.log: Optional[list] = None

    def __getattr__(self, name):
        return getattr(self.base, name)

    def predict(self, subsets):
        out = self.base.predict(subsets)
        if self.log is not None:
            self.log.append(("predict", subsets, out))
        return out

    def predict_children(self, parent):
        out = self.base.predict_children(parent)
        if self.log is not None:
            self.log.append(("children", list(parent), out))
        return out

    def eliminate_to(self, parent, k):
        res = self.base.eliminate_to(parent, k)
        if res is not None and self.log is not None:
            self.log.append(("scan", sorted(parent), res))
        return res


@dataclasses.dataclass
class Search:
    """What one search scored and chose, kept for the sampled admissions."""

    predicted: float           # the search's score of its result
    eha: Tuple[Tuple[int, ...], float]
    pts: Tuple[Tuple[int, ...], float]
    winner: str
    decisions: list            # the Recorder's log


@dataclasses.dataclass
class Admission:
    index: int
    job_id: str
    k: int
    busy: int                  # bitmask of the GPUs live jobs held before it
    gpus: Tuple[int, ...]      # committed placement
    result_subset: Tuple[int, ...]
    admit_s: float
    # live (job id, gpus) before the admission: graded prefix and sample only
    snapshot: Optional[Tuple] = None
    search: Optional[Search] = None     # sample only


def n_scored(log) -> int:
    """Candidates a search's log scored."""
    return sum(int(np.count_nonzero(y.sels)) if kind == "scan" else len(x)
               for kind, x, y in log)


class Sample:
    """The admissions the check compares, decided while the window runs so
    that only they keep their snapshot and search record: a reservoir of
    ``n`` drawn from the seed, plus the one whose search scored the most
    candidates so far.  Admissions below ``graded`` keep their snapshot."""

    def __init__(self, seed: int, n: int, graded: int):
        self.rng = np.random.default_rng([seed, 2])
        self.n = n
        self.graded = graded
        self.slots: List[Admission] = []
        self.widest: Optional[Admission] = None
        self.widest_n = -1

    def offer(self, a: Admission, scored: int) -> bool:
        """-> whether ``a`` is kept; drops what an admission let go keeps."""
        out = []
        if a.index < self.n:
            self.slots.append(a)
            keep = True
        else:
            j = int(self.rng.integers(a.index + 1))
            keep = j < self.n
            if keep:
                out.append(self.slots[j])
                self.slots[j] = a
        if scored > self.widest_n:
            if self.widest is not None:
                out.append(self.widest)
            self.widest, self.widest_n = a, scored
            keep = True
        for o in out:
            if o is not self.widest and all(o is not x for x in self.slots):
                o.search = None
                if o.index >= self.graded:
                    o.snapshot = None
        return keep

    def admissions(self) -> List[Admission]:
        kept = {a.index: a for a in self.slots}
        if self.widest is not None:
            kept[self.widest.index] = self.widest
        return [kept[i] for i in sorted(kept)]


@dataclasses.dataclass
class Window:
    """Everything the metric readers and the check read."""

    dep: Deployment
    admissions: List[Admission] = dataclasses.field(default_factory=list)
    sample: Optional[Sample] = None
    scans: List[Tuple[int, int, int]] = dataclasses.field(
        default_factory=list)  # each fused descent: (rounds, slots, scores)
    t_open: float = 0.0
    t_close: float = 0.0
    dispatch_s: float = 0.0
    stats: Any = None          # PredictorStats delta over the window
    compiles: int = 0
    setup_s: float = 0.0
    aot_warm_s: float = 0.0
    gbe: Optional[List[float]] = None
    trace: Optional[Dict] = None
    extra: Dict = dataclasses.field(default_factory=dict)

    @property
    def window_s(self) -> float:
        return self.t_close - self.t_open

    @property
    def admit_s(self) -> float:
        return sum(a.admit_s for a in self.admissions)


def _delta(core, after, before):
    out = core.PredictorStats()
    for f in dataclasses.fields(out):
        setattr(out, f.name, getattr(after, f.name) - getattr(before, f.name))
    return out


def run_window(core, dep: Deployment, disp, seed: int, seconds: float,
               compile_count=lambda: 0, on_open=None) -> Window:
    """Replay the graded prefix, then the seed's trace chunk after chunk,
    through ``disp`` until ``seconds`` have passed; time every admission."""
    import jax

    graded = dep.mix["graded_prefix"]
    w = Window(dep, sample=Sample(seed, dep.mix["check_sample"], graded))
    rec = Recorder(disp.predictor, core.PredictorStats())
    disp.predictor = rec
    inner_admit = disp.admit
    inner_dispatch = type(disp).dispatch
    deadline = [float("inf")]

    def dispatch(avail, k, rng=None):
        t = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.dispatch"):
            out = inner_dispatch(disp, avail, k, rng=rng)
        w.dispatch_s += time.perf_counter() - t
        return out

    def admit(job_id, k, rng=None, tenant=""):
        if time.perf_counter() >= deadline[0]:
            raise WindowClosed
        snap = tuple((a.job_id, tuple(a.gpus)) for a in disp.ledger.jobs())
        rec.log = []
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.admit"):
            alloc = inner_admit(job_id, k, rng=rng, tenant=tenant)
        dt = time.perf_counter() - t0
        log, rec.log = rec.log, None
        res = disp.last_result
        busy = 0
        for _, gpus in snap:
            for g in gpus:
                busy |= 1 << g
        a = Admission(len(w.admissions), job_id, k, busy, tuple(alloc.gpus),
                      tuple(res.subset), dt)
        w.admissions.append(a)
        w.scans.extend((y.n_rounds, y.scores.shape[1], y.scores.size)
                       for kind, _, y in log if kind == "scan")
        if w.sample.offer(a, n_scored(log)):
            a.search = Search(
                float(res.predicted_bw),
                (tuple(res.eha.subset), res.eha.predicted_bw),
                (tuple(res.pts.subset), res.pts.predicted_bw),
                res.winner, log)
        if a.search is not None or a.index < graded:
            a.snapshot = snap
        return alloc

    disp.dispatch = dispatch
    disp.admit = admit
    before = disp.predictor_stats()
    if on_open is not None:
        on_open()

    def chunk_of(i):
        if i == 0:   # the graded prefix: the same jobs in one order for every seed
            return trace_jobs(core, dep, [dep.mix["base_seed"], 0, 0],
                              graded, "g")
        return trace_jobs(core, dep, [seed, 0, i], dep.mix["chunk_jobs"],
                          f"c{i}")

    chunk, jobs = 0, chunk_of(0)
    # set-up's objects stay alive all run: keep the collector off them
    gc.collect()
    gc.freeze()
    n0 = compile_count()
    w.t_open = time.perf_counter()
    deadline[0] = w.t_open + seconds
    try:
        with jax.profiler.TraceAnnotation("bench.window"):
            while True:
                scheduler(core, dep, disp).run(jobs)
                chunk += 1
                jobs = chunk_of(chunk)
    except WindowClosed:
        w.t_close = time.perf_counter()
    w.compiles = compile_count() - n0
    w.stats = _delta(core, disp.predictor_stats(), before)
    w.extra["chunks"] = chunk + 1
    return w


# -- grading ------------------------------------------------------------------

def grade(core, w: Window) -> List[float]:
    """Contention-degraded GBE of the graded prefix (the window's first
    chunk, the same jobs for every seed) against the ledger-aware exact
    oracle, each under its own pre-admission ledger."""
    from repro.core.baselines import oracle_dispatch

    dep = w.dep
    n = dep.mix["graded_prefix"]
    if len(w.admissions) <= n:
        raise RuntimeError(
            f"window committed {len(w.admissions)} admissions, not more "
            f"than the graded prefix of {n}")
    out = []
    for a in w.admissions[:n]:
        led = core.JobLedger(dep.cluster)
        for job_id, gpus in a.snapshot:
            led.admit(job_id, list(gpus))
        _, opt = oracle_dispatch(dep.cluster, dep.sim, dep.tables,
                                 led.available(), a.k, ledger=led)
        out.append(dep.sim.true_bandwidth(list(a.gpus), ledger=led) / opt)
    return out
