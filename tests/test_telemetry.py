"""Observability layer (ISSUE 8): tracer, metrics registry, drift recorder.

Covers the ISSUE 8 acceptance criteria:
  * **bit-identity** — a tracing-enabled replay commits byte-identical
    placements to a disabled one across fifo/batched x analytic/learned
    and with ``concurrent_workers > 1`` (the tracer only records; it
    never touches the rng, the predictor, or the ledger);
  * **ring buffer** — bounded under a multi-thread hammer, drops counted;
  * **Prometheus exposition** — grammar (HELP/TYPE ordering, label
    escaping, histogram bucket monotonicity + ``+Inf``) and the JSONL
    round-trip;
  * **drift recorder** — fires a structured alert (with dumped decision
    records) on an injected mispredicting predictor, stays silent on
    golden ground-truth traces, and triggers the fine-tune hook;
  * **unified stats semantics** — ``to_dict``/``reset``/``merged`` across
    every stats surface, and the control-plane partition invariant
    asserted at absorb time;
  * **profiler sink** — under ``jax.profiler.trace`` the program's spans
    land on the host plane, nested as the admission path nests them, one
    ``descent`` per fused descent, placements unchanged; with no tracer a
    span is a falsy annotation that records in no ring.
"""

import gc
import glob
import json
import math
import os
import re
import threading
from collections import defaultdict

import numpy as np
import pytest

import repro.core as core
from repro.core import telemetry
from repro.core.telemetry import (
    AdmissionTracer,
    DriftAlert,
    DriftMonitor,
    MetricsRegistry,
)


@pytest.fixture(scope="module")
def h100():
    cl = core.h100_cluster()
    sim = core.BandwidthSimulator(cl)
    tables = core.IntraHostTables(cl, sim)
    return cl, sim, tables


def _trace20(cl):
    return core.poisson_trace(
        cl, 20, np.random.default_rng(7),
        mean_interarrival=1.0, mean_duration=8.0, k_choices=range(4, 17),
    )


def _bp(cl, tables, sim, **kw):
    return core.BandPilotDispatcher(
        cl, tables, core.GroundTruthPredictor(sim), **kw
    )


# ---------------------------------------------------------------------------
# Tracer unit behaviour
# ---------------------------------------------------------------------------

def test_span_nesting_parents_and_trace_ids():
    tr = AdmissionTracer()
    with telemetry.trace(tr):
        with telemetry.span("outer", k=8) as outer:
            with telemetry.span("inner") as inner:
                inner["hit"] = True
            outer["done"] = 1
        with telemetry.span("second"):
            pass
    spans = {s.name: s for s in tr.spans()}
    assert spans["inner"].parent_id == spans["outer"].span_id
    assert spans["inner"].trace_id == spans["outer"].trace_id
    # a fresh root starts a fresh trace
    assert spans["second"].trace_id != spans["outer"].trace_id
    assert spans["second"].parent_id == -1  # root sentinel
    assert spans["outer"].attrs["k"] == 8 and spans["inner"].attrs["hit"]
    assert spans["outer"].duration >= spans["inner"].duration >= 0.0
    assert len(tr.traces()) == 2


def test_disabled_spans_are_free_and_falsy():
    assert telemetry.active_tracer() is None
    sp = telemetry.span("anything", k=4)
    assert not sp  # the untraced span is falsy: `if sp:` guards skip
    with sp as inner:
        inner["ignored"] = 1  # swallowed, no error
    telemetry.event("nobody.listening")  # no-op
    assert telemetry.active_tracer() is None


def test_span_records_error_and_reraises():
    tr = AdmissionTracer()
    with telemetry.trace(tr):
        with pytest.raises(ValueError):
            with telemetry.span("boom"):
                raise ValueError("no")
    (sp,) = tr.spans("boom")
    assert "ValueError" in sp.attrs["error"]
    assert telemetry.active_tracer() is None  # trace() restored on error


def test_ring_buffer_bounds_and_drop_count():
    tr = AdmissionTracer(capacity=16)
    with telemetry.trace(tr):
        for i in range(50):
            telemetry.event("e", i=i)
    assert len(tr) == 16
    assert tr.n_spans == 50 and tr.n_dropped == 34
    # the ring keeps the newest
    assert [s.attrs["i"] for s in tr.spans()] == list(range(34, 50))
    tr.clear()
    assert len(tr) == 0 and tr.n_spans == 50  # lifetime counters survive


def test_ring_buffer_hammer_many_threads():
    """Racing recorders (the control-plane worker pool) never corrupt the
    ring: every span lands or is counted dropped, nesting stays
    per-thread."""
    tr = AdmissionTracer(capacity=256)
    n_threads, per_thread = 8, 200
    errors = []

    def work(tid):
        try:
            for i in range(per_thread):
                with telemetry.span("outer", tid=tid) as sp:
                    sp["i"] = i
                    with telemetry.span("inner", tid=tid):
                        pass
        except Exception as exc:  # pragma: no cover - the assertion target
            errors.append(exc)

    with telemetry.trace(tr):
        threads = [
            threading.Thread(target=work, args=(t,))
            for t in range(n_threads)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
    assert not errors
    assert tr.n_spans == n_threads * per_thread * 2
    assert len(tr) == 256
    assert tr.n_dropped == tr.n_spans - 256
    # parenting never crosses threads
    by_id = {s.span_id: s for s in tr.spans()}
    for s in tr.spans("inner"):
        parent = by_id.get(s.parent_id)
        if parent is not None:
            assert parent.attrs["tid"] == s.attrs["tid"]


def test_tracer_summary_and_jsonl(tmp_path):
    tr = AdmissionTracer()
    with telemetry.trace(tr):
        for _ in range(3):
            with telemetry.span("a"):
                pass
        telemetry.event("b")
    summ = tr.summary()
    assert summ["a"]["count"] == 3 and summ["b"]["count"] == 1
    assert summ["a"]["total_seconds"] >= summ["a"]["mean_seconds"] >= 0.0
    path = tmp_path / "spans.jsonl"
    assert tr.write_jsonl(path) == 4
    rows = [json.loads(line) for line in path.read_text().splitlines()]
    assert [r["name"] for r in rows] == ["a", "a", "a", "b"]
    assert all("trace_id" in r and "t0" in r for r in rows)


def test_untraced_span_is_a_falsy_profiler_annotation():
    """With no tracer installed a span is the profiler's annotation alone:
    falsy, deaf to attribute writes, and recorded in no ring."""
    import jax

    bystander = AdmissionTracer()  # constructed, never installed
    assert telemetry.active_tracer() is None
    sp = telemetry.span("descent", k=4)
    assert isinstance(sp, jax.profiler.TraceAnnotation)
    assert not sp
    with sp as inner:
        assert inner is sp and not inner
        inner["steps"] = 3
        with telemetry.span("descent.sync") as child:
            child["ignored"] = True
            assert not child
    assert len(bystander) == 0 and bystander.n_spans == 0
    assert telemetry.current_trace_id() == -1


# ---------------------------------------------------------------------------
# Profiler sink: the program's spans on the device trace's clock
# ---------------------------------------------------------------------------

# the spans of the fused-descent admission path, below the search
PROGRAM_SPANS = (
    "descent", "descent.prep", "descent.upload", "descent.launch",
    "descent.sync", "cap.table", "cap.degrade", "featurize", "apply",
    "ledger.admit", "ledger.release", "py.gc",
)


def _host_spans(log_dir):
    """name -> [(thread line, start ns, end ns)] from a profile's host
    planes."""
    from jax.profiler import ProfileData

    (path,) = glob.glob(os.path.join(
        str(log_dir), "plugins", "profile", "*", "*.xplane.pb"))
    out = defaultdict(list)
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for i, line in enumerate(plane.lines):
            for e in line.events:
                s = int(e.start_ns)
                out[e.name].append(
                    ((plane.name, i), s, s + int(e.duration_ns)))
    return out


def _inside(inner, outers):
    line, s, e = inner
    return any(ol == line and os_ <= s and e <= oe for ol, os_, oe in outers)


def _fused_replay(cl, sim, tables, params, log_dir=None):
    """A short fifo replay with the fused descent on the device path, under
    the profiler when ``log_dir`` is given -> (placements, fused descents)."""
    import jax

    disp = core.BandPilotDispatcher(
        cl, tables, core.SurrogatePredictor(cl, tables, params))
    placed, n_fused = [], [0]
    inner_admit = disp.admit
    inner_elim = disp.predictor.eliminate_to

    def admit(job_id, k, rng=None, tenant=""):
        alloc = inner_admit(job_id, k, rng=rng, tenant=tenant)
        placed.append((job_id, tuple(alloc.gpus)))
        return alloc

    def eliminate_to(parent, k):
        res = inner_elim(parent, k)
        n_fused[0] += res is not None
        return res

    disp.admit = admit
    disp.predictor.eliminate_to = eliminate_to
    trace = core.poisson_trace(
        cl, 8, np.random.default_rng(5), mean_interarrival=1.0,
        mean_duration=6.0, k_choices=range(6, 13))
    sched = core.AdmissionScheduler(cl, sim, tables, disp)
    if log_dir is None:
        sched.run(trace)
    else:
        with jax.profiler.trace(str(log_dir)):
            sched.run(trace)
            gc.collect()
    return placed, n_fused[0]


def test_program_spans_on_profiler_host_plane(h100, tmp_path):
    """A few fused-descent admissions under the JAX profiler: every
    program span is on the host plane, the descent's phases nest inside
    ``descent`` inside ``search.pts`` inside ``dispatcher.dispatch``, one
    ``descent`` per fused descent, and the placements equal an unprofiled
    run's byte for byte."""
    import jax

    cl, sim, tables = h100
    params = core.surrogate.init_hierarchical_params(jax.random.PRNGKey(0))
    base, _ = _fused_replay(cl, sim, tables, params)
    placed, n_fused = _fused_replay(cl, sim, tables, params, tmp_path)
    assert placed == base and len(placed) == 8
    spans = _host_spans(tmp_path)
    missing = [n for n in PROGRAM_SPANS if not spans.get(n)]
    assert not missing, f"not on the host plane: {missing}"
    assert n_fused > 0 and len(spans["descent"]) == n_fused
    for phase in ("descent.prep", "descent.upload", "descent.launch",
                  "descent.sync"):
        assert len(spans[phase]) == n_fused
        assert all(_inside(ev, spans["descent"]) for ev in spans[phase])
    assert all(_inside(ev, spans["search.pts"]) for ev in spans["descent"])
    assert all(_inside(ev, spans["dispatcher.dispatch"])
               for ev in spans["search.pts"])
    assert len(spans["dispatcher.dispatch"]) == len(placed)


def test_traced_descent_span_carries_steps(h100):
    """Under a tracer the ring holds the same descent spans, with the
    phases as children and the step count as an attribute."""
    import jax

    cl, sim, tables = h100
    params = core.surrogate.init_hierarchical_params(jax.random.PRNGKey(0))
    pred = core.SurrogatePredictor(cl, tables, params)
    parent = list(range(4, 20))
    tr = AdmissionTracer()
    with telemetry.trace(tr):
        res = pred.eliminate_to(parent, 8)
    (d,) = tr.spans("descent")
    assert d.attrs["steps"] == res.n_rounds == 8
    phases = [s.name for s in tr.spans() if s.parent_id == d.span_id]
    assert phases == ["descent.prep", "descent.upload", "descent.launch",
                      "descent.sync"]


# ---------------------------------------------------------------------------
# Metrics registry + Prometheus exposition
# ---------------------------------------------------------------------------

# one exposition line: name{labels} value  (labels optional)
_SAMPLE_RE = re.compile(
    r"^[a-zA-Z_:][a-zA-Z0-9_:]*"
    r"(\{[a-zA-Z_][a-zA-Z0-9_]*=\"(?:[^\"\\\n]|\\\\|\\\"|\\n)*\""
    r"(,[a-zA-Z_][a-zA-Z0-9_]*=\"(?:[^\"\\\n]|\\\\|\\\"|\\n)*\")*\})?"
    r" \S+$"
)


def test_prometheus_exposition_grammar():
    reg = MetricsRegistry()
    c = reg.counter("ops_total", "ops", labels=("tenant",))
    c.inc(3, tenant='we"ird\\ten\nant')
    reg.gauge("level", "current level").set(-2.5)
    h = reg.histogram("lat_seconds", "latency", buckets=(0.1, 1.0))
    for v in (0.05, 0.5, 5.0):
        h.observe(v)
    text = reg.to_prometheus()
    lines = text.splitlines()
    for name in ("bandpilot_ops_total", "bandpilot_level",
                 "bandpilot_lat_seconds"):
        assert f"# HELP {name} " in text and f"# TYPE {name} " in text
        # HELP precedes TYPE precedes the samples
        idx_help = next(i for i, ln in enumerate(lines)
                        if ln.startswith(f"# HELP {name} "))
        idx_type = next(i for i, ln in enumerate(lines)
                        if ln.startswith(f"# TYPE {name} "))
        assert idx_help < idx_type
    for ln in lines:
        if ln.startswith("#") or not ln:
            continue
        assert _SAMPLE_RE.match(ln), f"bad exposition line: {ln!r}"
    # label escaping: backslash, quote, newline
    assert r'tenant="we\"ird\\ten\nant"' in text
    # histogram: cumulative buckets, +Inf == _count, sum of observations
    assert 'le="0.1"} 1' in text
    assert 'le="1"} 2' in text or 'le="1.0"} 2' in text
    assert 'le="+Inf"} 3' in text
    assert "bandpilot_lat_seconds_count 3" in text
    assert "bandpilot_lat_seconds_sum 5.55" in text


def test_histogram_bucket_counts_monotone():
    reg = MetricsRegistry()
    h = reg.histogram("x_seconds", "x")
    rng = np.random.default_rng(3)
    for v in rng.exponential(0.5, size=200):
        h.observe(float(v))
    snap = h.snapshot()["samples"][0]
    counts = snap["counts"]
    assert all(a <= b for a, b in zip(counts, counts[1:]))
    assert counts[-1] == snap["count"] == 200


def test_registry_conflicts_and_validation():
    reg = MetricsRegistry()
    reg.counter("a_total", "a", labels=("x",))
    reg.counter("a_total", "a", labels=("x",))  # get-or-create: same object
    assert len(reg) == 1
    with pytest.raises(ValueError):
        reg.gauge("a_total", "a")  # type conflict
    with pytest.raises(ValueError):
        reg.counter("a_total", "a", labels=("y",))  # labelset conflict
    with pytest.raises(ValueError):
        reg.counter("bad-name", "nope")
    with pytest.raises(ValueError):
        reg.counter("a_total", "a").inc(-1, x="t")  # counters only go up
    with pytest.raises(ValueError):
        reg.counter("a_total", "a").inc(1)  # missing label


def test_metrics_jsonl_roundtrip(tmp_path):
    reg = MetricsRegistry()
    reg.counter("jobs_total", "jobs", labels=("policy",)).inc(7, policy="fifo")
    reg.gauge("frag_score", "frag").set(0.25)
    reg.histogram("wait_seconds", "wait").observe(1.5)
    path = tmp_path / "metrics.jsonl"
    assert reg.write_jsonl(path) == 3
    assert telemetry.read_metrics_jsonl(path) == reg.snapshot()


def test_histogram_custom_buckets_roundtrip(tmp_path):
    """ISSUE 9 satellite: per-metric bucket boundaries (regret and
    whatif-delta distributions span negative GB/s where the default
    latency buckets are useless) survive the JSONL round-trip, and the
    boundaries are part of the metric's registered schema."""
    reg = MetricsRegistry()
    h = reg.histogram("regret_gbs", "regret", labels=("tenant",),
                      buckets=(-10.0, 0.0, 10.0, 50.0))
    h.observe(-5.0, tenant="a")
    h.observe(25.0, tenant="a")
    # re-registration with the SAME boundaries (any order) is get-or-create
    assert reg.histogram("regret_gbs", "regret", labels=("tenant",),
                         buckets=(50.0, 10.0, 0.0, -10.0)) is h
    # ... but different boundaries under one name are a schema conflict
    with pytest.raises(ValueError, match="buckets"):
        reg.histogram("regret_gbs", "regret", labels=("tenant",),
                      buckets=(0.0, 1.0))
    path = tmp_path / "metrics.jsonl"
    reg.write_jsonl(path)
    back = telemetry.read_metrics_jsonl(path)
    assert back == reg.snapshot()
    (snap,) = back.values()
    assert snap["buckets"] == [-10.0, 0.0, 10.0, 50.0]
    text = reg.to_prometheus()
    assert 'le="-10.0"' in text and 'le="+Inf"' in text


def test_absorb_is_idempotent_set_semantics():
    reg = MetricsRegistry()
    st = core.PredictorStats(n_model_calls=5, cache_hits=3, cache_misses=1)
    telemetry.absorb_predictor_stats(reg, st, predictor="bp")
    telemetry.absorb_predictor_stats(reg, st, predictor="bp")  # re-scrape
    c = reg.get("bandpilot_predictor_n_model_calls_total")
    assert c.value(predictor="bp") == 5  # set, not +=: no double count
    hr = reg.get("bandpilot_predictor_cache_hit_rate")
    assert hr.value(predictor="bp") == 0.75


def test_absorb_controlplane_asserts_partition():
    reg = MetricsRegistry()
    good = core.ControlPlaneStats(
        n_admitted=5, n_cas_commits=3, n_validated=1, n_serialized=1
    )
    telemetry.absorb_controlplane_stats(reg, good)
    c = reg.get("bandpilot_cplane_commits_total")
    assert c.value(commit="cas") == 3 and c.value(commit="validated") == 1
    bad = core.ControlPlaneStats(n_admitted=5, n_cas_commits=3)
    with pytest.raises(ValueError):
        telemetry.absorb_controlplane_stats(reg, bad)


# ---------------------------------------------------------------------------
# Unified stats semantics (reset / merge / to_dict)
# ---------------------------------------------------------------------------

def test_stats_to_dict_reset_merged_everywhere(h100):
    cl, sim, tables = h100
    ps = core.PredictorStats(n_model_calls=2, cache_hits=1)
    assert ps.to_dict()["n_model_calls"] == 2 and ps.as_dict() == ps.to_dict()
    ps.reset()
    assert ps.to_dict() == core.PredictorStats().to_dict()

    a = core.ControlPlaneStats(n_admitted=2, n_cas_commits=2,
                               search_seconds=0.5)
    b = core.ControlPlaneStats(n_admitted=1, n_validated=1, n_parked=3)
    m = core.ControlPlaneStats.merged(a, b)
    assert m.n_admitted == 3 and m.n_cas_commits == 2 and m.n_parked == 3
    assert m.search_seconds == 0.5
    a.reset()
    assert a.to_dict() == core.ControlPlaneStats().to_dict()

    ledger = core.JobLedger(cl)
    frag = core.fragmentation_metrics(cl, ledger)
    d = frag.to_dict()
    assert set(d) and all(isinstance(v, (int, float)) for v in d.values())


def test_record_to_dicts(h100):
    cl, sim, tables = h100
    sched = core.AdmissionScheduler(cl, sim, tables, _bp(cl, tables, sim))
    recs = sched.run(_trace20(cl)[:5])
    d = recs[0].to_dict()
    assert d["job_id"] == recs[0].job_id and "predicted_bw" in d
    out = core.AdmissionOutcome(
        job_id="j", tenant="t", status="rejected", reason="capacity"
    )
    od = out.to_dict()
    assert od["alloc"] is None and od["reason"] == "capacity"
    got = core.AdmissionOutcome(
        job_id="j", tenant="t", status="admitted",
        alloc=core.Allocation("j", (0, 1), (0,)),
    ).to_dict()
    assert got["alloc"] == [0, 1]


# ---------------------------------------------------------------------------
# Bit-identity: tracing never changes placements
# ---------------------------------------------------------------------------

def _replay_ids(cl, sim, tables, disp, tracer=None, **cfg_kw):
    sched = core.AdmissionScheduler(
        cl, sim, tables, disp, core.SchedulerConfig(**cfg_kw)
    )
    if tracer is None:
        recs = sched.run(_trace20(cl))
    else:
        with telemetry.trace(tracer):
            recs = sched.run(_trace20(cl))
    return [(r.job_id, r.bw) for r in recs]


@pytest.mark.parametrize("cfg", [
    dict(),                                       # fifo serial
    dict(policy="batched", batch_window=2.0),     # joint batch path
    dict(concurrent_workers=1),                   # control-plane path
], ids=["fifo", "batched", "concurrent1"])
def test_traced_replay_bit_identical_analytic(h100, cfg):
    cl, sim, tables = h100
    base = _replay_ids(cl, sim, tables, _bp(cl, tables, sim), **cfg)
    tr = AdmissionTracer()
    traced = _replay_ids(cl, sim, tables, _bp(cl, tables, sim), tr, **cfg)
    assert traced == base
    names = {s.name for s in tr.spans()}
    assert "sched.admit" in names and "sched.oracle" in names
    if cfg.get("concurrent_workers"):
        assert "cplane.stage" in names and "cplane.commit" in names
    else:
        assert "dispatcher.dispatch" in names and "search.eha" in names
    # grading stamped a real B-hat on every record
    for sp in tr.spans("dispatcher.dispatch"):
        assert not math.isnan(sp.attrs.get("predicted_bw", 0.0))


def test_traced_replay_multi_worker_neutral(h100):
    """With ``concurrent_workers > 1`` the admission schedule itself races
    (CAS commit order is a property of thread timing, traced or not — the
    repo's own multi-worker tests assert drain/counts, not goldens), so
    run-to-run byte equality is not a meaningful oracle here.  What
    tracing must preserve: every job still admits exactly once, the
    ledger drains, the commit-kind partition holds, and the worker
    threads' spans all land in the ring with intact parenting."""
    cl, sim, tables = h100
    tr = AdmissionTracer()
    sched = core.AdmissionScheduler(
        cl, sim, tables, _bp(cl, tables, sim),
        core.SchedulerConfig(concurrent_workers=4),
    )
    with telemetry.trace(tr):
        recs = sched.run(_trace20(cl))
    assert sorted(r.job_id for r in recs) == sorted(
        f"job-{i:04d}" for i in range(20)
    )
    assert len(sched.dispatcher.ledger) == 0  # drained
    st = sched._cplane.stats
    assert st.n_admitted == 20
    assert st.n_cas_commits + st.n_validated + st.n_serialized == 20
    names = {s.name for s in tr.spans()}
    assert {"cplane.stage", "cplane.commit", "sched.admit"} <= names
    commits = tr.spans("cplane.commit")
    assert len(commits) >= 20  # one per admission (+ conflict re-tries)
    by_id = {s.span_id: s for s in tr.spans()}
    for s in commits:
        parent = by_id.get(s.parent_id)
        if parent is not None:  # parent may have rotated out of the ring
            assert parent.thread == s.thread


@pytest.mark.slow
def test_traced_replay_bit_identical_learned(h100):
    """Learned-contention configuration (contended featurizer on the hot
    path): tracing still changes nothing."""
    import jax

    from repro.core import surrogate as surr

    cl, sim, tables = h100
    params = surr.init_hierarchical_params(jax.random.PRNGKey(0))
    cparams = surr.init_contended_params(params)

    def disp():
        return core.BandPilotDispatcher(
            cl, tables, core.SurrogatePredictor(cl, tables, params),
            cache=True, contention_mode="learned",
            contended_predictor=core.ContendedSurrogatePredictor(
                cl, tables, cparams
            ),
        )

    base = _replay_ids(cl, sim, tables, disp())
    tr = AdmissionTracer()
    traced = _replay_ids(cl, sim, tables, disp(), tr)
    assert traced == base
    assert any(s.name == "search.pts" for s in tr.spans())


# ---------------------------------------------------------------------------
# Drift recorder
# ---------------------------------------------------------------------------

def test_drift_alert_fires_on_mispredicting_predictor():
    mon = DriftMonitor(window=8, min_samples=4, mape_threshold=0.25,
                       dump_last=4)
    alerts = []
    mon.on_alert = alerts.append
    alert = None
    for i in range(6):
        # injected regression: predictor is 50% optimistic
        got = mon.observe(100.0, job_id=f"j{i}", subset=(i,),
                          predicted=150.0, t=float(i))
        alert = got or alert
    assert alert is not None and mon.alerts and alerts
    assert alert.mape == pytest.approx(0.5)
    assert alert.bias == pytest.approx(0.5)
    assert alert.kind == "bias"
    assert len(alert.records) <= 4
    assert all(r.predicted == 150.0 and r.realized == 100.0
               for r in alert.records)
    d = alert.to_dict()
    assert d["kind"] == "bias" and len(d["records"]) == len(alert.records)
    # throttle: min_samples fresh pairs between alerts
    n = len(mon.alerts)
    mon.observe(100.0, job_id="x", predicted=150.0)
    assert len(mon.alerts) == n
    for i in range(4):
        mon.observe(100.0, job_id=f"y{i}", predicted=150.0)
    assert len(mon.alerts) == n + 1


def test_drift_pairs_report_path_through_pending_map():
    mon = DriftMonitor(window=4, min_samples=2)
    mon.note_prediction("job-a", (0, 1), 200.0, digest="abcd1234",
                        tenant="t0")
    mon.observe(180.0, job_id="job-a", source="report")
    (rec,) = mon.records()
    assert rec.predicted == 200.0 and rec.realized == 180.0
    assert rec.subset == (0, 1) and rec.tenant == "t0"
    assert rec.digest == "abcd1234" and rec.source == "report"
    # no stamped prediction -> counted unmatched, not an error
    mon.observe(99.0, job_id="stranger")
    assert mon.n_unmatched == 1 and mon.n_observed == 1
    # NaN / non-positive realized carry no signal
    mon.note_prediction("job-b", (2,), 100.0)
    mon.observe(float("nan"), job_id="job-b")
    mon.observe(0.0, job_id="job-b")
    assert mon.n_observed == 1
    mon.release("job-b")
    mon.observe(50.0, job_id="job-b")
    assert mon.n_unmatched == 2


def test_drift_silent_on_golden_trace(h100):
    """A ground-truth predictor graded against the same simulator has zero
    drift: a full replay must not raise a single alert."""
    cl, sim, tables = h100
    mon = DriftMonitor(window=8, min_samples=4, mape_threshold=0.05,
                       bias_threshold=0.05)
    harv = core.TelemetryHarvester(cl, drift=mon)
    sched = core.AdmissionScheduler(
        cl, sim, tables, _bp(cl, tables, sim), harvester=harv
    )
    sched.run(_trace20(cl))
    assert mon.n_observed >= 20
    assert not mon.alerts
    assert mon.mape() == pytest.approx(0.0, abs=1e-9)
    # every record carries the decision-time contention digest
    assert all(r.digest for r in mon.records())


def test_drift_flight_recorder_dump(h100, tmp_path):
    cl, sim, tables = h100
    mon = DriftMonitor()
    harv = core.TelemetryHarvester(cl, drift=mon)
    sched = core.AdmissionScheduler(
        cl, sim, tables, _bp(cl, tables, sim), harvester=harv
    )
    sched.run(_trace20(cl))
    path = tmp_path / "decisions.jsonl"
    rows = mon.dump(last=8, path=path)
    assert 0 < len(rows) <= 8
    reread = [json.loads(l) for l in path.read_text().splitlines()]
    assert reread == json.loads(json.dumps(rows))  # tuples -> lists
    assert {"job_id", "predicted", "realized", "ape", "digest"} <= set(rows[0])


def test_finetune_on_drift_hook(h100):
    cl, sim, tables = h100
    ledger = core.JobLedger(cl)
    ledger.admit("a", (0, 1, 2, 3))
    ledger.admit("b", (8, 9))
    harv = core.TelemetryHarvester(cl)
    for _ in range(10):
        harv.observe(ledger, (16, 17), 55.0)

    calls = []

    class _Pred:
        params = "old"
        tables = None

    pred = _Pred()

    def trainer(cluster, tbl, params, samples):
        calls.append((len(samples), params))
        return "new"

    hook = telemetry.finetune_on_drift(
        harv, pred, tables=tables, min_contended=8, trainer=trainer
    )
    alert = DriftAlert(0.0, 8, 0.5, 0.5, 0.25, 0.2, tenant="")
    hook(alert)
    assert calls and calls[0][0] == 10 and calls[0][1] == "old"
    assert pred.params == "new"
    # below the floor: a no-op (never destabilize on thin data)
    thin = core.TelemetryHarvester(cl)
    thin.observe(ledger, (16, 17), 55.0)
    telemetry.finetune_on_drift(
        thin, pred, tables=tables, min_contended=8, trainer=trainer
    )(alert)
    assert len(calls) == 1


def test_drift_monitor_wired_as_on_alert_fires_during_replay(h100):
    """End-to-end injected regression: a predictor that over-promises by
    3x trips the monitor inside a real scheduler replay."""
    cl, sim, tables = h100

    class Optimist(core.GroundTruthPredictor):
        def predict(self, subset):
            return 3.0 * super().predict(subset)

    mon = DriftMonitor(window=8, min_samples=4)
    harv = core.TelemetryHarvester(cl, drift=mon)
    disp = core.BandPilotDispatcher(cl, tables, Optimist(sim))
    sched = core.AdmissionScheduler(cl, sim, tables, disp, harvester=harv)
    sched.run(_trace20(cl))
    assert mon.alerts, "3x-optimistic predictor must trip the drift monitor"
    assert mon.alerts[0].records  # the flight recorder dumped context
    # systematically optimistic (the analytic cap tempers the 3x on
    # contended placements, so the magnitude varies — the sign must not)
    assert mon.bias() > 0.0
    assert mon.alerts[0].bias > 0.0


# ---------------------------------------------------------------------------
# snapshot digest + collector
# ---------------------------------------------------------------------------

def test_snapshot_digest_tracks_cotenancy(h100):
    cl, _, _ = h100
    ledger = core.JobLedger(cl)
    d0 = telemetry.snapshot_digest(ledger, (0, 1))
    ledger.admit("a", (8, 9))
    d1 = telemetry.snapshot_digest(ledger, (0, 1))
    assert d0 != d1 and re.fullmatch(r"[0-9a-f]{8}", d1)
    # overlap self-excludes: the subset's own job is not a co-tenant
    assert telemetry.snapshot_digest(ledger, (8, 9)) == d0
    ledger.release("a")
    assert telemetry.snapshot_digest(ledger, (0, 1)) == d0


def test_collect_scheduler_metrics_end_to_end(h100):
    cl, sim, tables = h100
    mon = DriftMonitor()
    harv = core.TelemetryHarvester(cl, drift=mon)
    sched = core.AdmissionScheduler(
        cl, sim, tables, _bp(cl, tables, sim, cache=True),
        core.SchedulerConfig(concurrent_workers=2), harvester=harv,
    )
    sched.run(_trace20(cl))
    reg = core.collect_scheduler_metrics(sched)
    snap = reg.snapshot()
    for name in (
        "bandpilot_admissions_total",
        "bandpilot_admission_gbe",
        "bandpilot_predictor_n_model_calls_total",
        "bandpilot_cplane_commits_total",
        "bandpilot_frag_total_free",
        "bandpilot_drift_mape",
        "bandpilot_drift_samples_total",
    ):
        assert name in snap, f"missing {name}"
    text = reg.to_prometheus()
    assert "bandpilot_admissions_total" in text
    # scrape twice: absorb is set-idempotent, values stable
    assert core.collect_scheduler_metrics(sched).snapshot() == snap
