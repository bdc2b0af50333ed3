"""On-device elimination scan (ISSUE 6): audit, goldens, batcher, LRU.

The load-bearing guarantees:

* every round of a fused ``lax.scan`` descent scores children within
  ``SCORE_RTOL`` of the host path and eliminates the slot the host
  loop would, except at a near-tie (``surrogate.audit_descent``, audited
  round by round, bare-isolated AND through the analytic contention cap
  table);
* pinned scheduler-trace replays select **byte-identical subsets** with the
  scan enabled (the new default) vs disabled (``use_scan=False``), in both
  analytic and learned contention modes;
* the cross-search inference batcher is value-neutral: whichever requests
  happen to fuse into one padded apply, every caller receives outputs
  within ``SCORE_RTOL`` of a solo apply (property-based, concurrent threads
  included);
* the LRU-capped lifetime memo can only forget values, never change them;
* a descent moves one packed vector each way and returns bit for bit what
  the unpacked layout returns; the cluster's tables stay on the device;
* ``PredictorStats`` accounts the scan path in its own bucket — no
  double-counting through the ``collect_stats`` chain merge.
"""

import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st

    HAVE_HYPOTHESIS = True
except ModuleNotFoundError:
    from _hypothesis_fallback import given, settings, st

    HAVE_HYPOTHESIS = False

import repro.core as core
from repro.core import defrag as defrag_mod
from repro.core import features as feat
from repro.core import search
from repro.core import surrogate as surr
from repro.core.predict_cache import (
    InferenceBatcher,
    LruDict,
    PredictionCache,
    PredictorStats,
)
from repro.core.tenancy import JobLedger


@pytest.fixture(scope="module", params=["H100", "Het-4Mix"])
def stack(request):
    cl = core.PAPER_CLUSTERS[request.param]()
    sim = core.BandwidthSimulator(cl)
    tables = core.IntraHostTables(cl, sim)
    params = surr.init_hierarchical_params(jax.random.PRNGKey(0))
    return cl, sim, tables, params


def _tenanted_ledger(cl):
    led = JobLedger(cl)
    led.admit("a", [0, 1, cl.hosts[1].gpu_ids[0]])
    led.admit("b", [cl.hosts[1].gpu_ids[1], cl.hosts[-1].gpu_ids[0]])
    led.admit("s", [cl.hosts[0].gpu_ids[5]])  # single-host: occupancy only
    return led


def _multi_host_parent(cl, rng, n0, exclude=()):
    """A sorted n0-GPU parent spanning >= 2 hosts, avoiding ``exclude``."""
    pool = [g for g in range(cl.n_gpus) if g not in set(exclude)]
    while True:
        parent = sorted(rng.choice(pool, size=n0, replace=False).tolist())
        if len(cl.partition_by_host(parent)) > 1:
            return parent


def _audit_descent(cl, predictor, res, parent, k):
    """Hold a ScanResult to the agreement contract against the host loop:
    per-child scores within SCORE_RTOL, host-argmax eliminations
    except at near-ties, survivors of the replayed descent."""
    audit = surr.audit_descent(predictor, res, parent, k)
    assert not audit.violations, audit
    assert res.subset == sorted(res.subset) and len(res.subset) == k


def _same_or_near_tie(a_subset, a_bw, b_subset, b_bw):
    """Two searches over the same state agree: the same placement with
    scores within SCORE_RTOL, or different placements only where
    their scores are a near-tie (within twice that)."""
    rtol = surr.SCORE_RTOL
    if a_subset == b_subset:
        np.testing.assert_allclose(a_bw, b_bw, rtol=rtol)
    else:
        np.testing.assert_allclose(a_bw, b_bw, rtol=2 * rtol)


# ---------------------------------------------------------------------------
# Round-by-round audit vs the host loop
# ---------------------------------------------------------------------------

def test_scan_descent_audit_isolated(stack):
    cl, sim, tables, params = stack
    pred = core.SurrogatePredictor(cl, tables, params)
    rng = np.random.default_rng(10)
    for n0, k in ((12, 6), (20, 10), (9, 2)):
        parent = _multi_host_parent(cl, rng, n0)
        res = pred.eliminate_to(parent, k)
        assert res is not None
        assert res.n_capped == 0  # no caps table: isolated scoring
        _audit_descent(cl, pred, res, parent, k)


def test_scan_descent_audit_contended(stack):
    """Through the analytic contention wrapper: device scores gather the
    per-ledger cap table and still match np.float32(host min(iso, cap))."""
    cl, sim, tables, params = stack
    led = _tenanted_ledger(cl)
    pred = core.SurrogatePredictor(cl, tables, params)
    wrapped = core.ContentionAwarePredictor(cl, pred, led)
    rng = np.random.default_rng(11)
    free = sorted(set(range(cl.n_gpus)) - led.busy())
    for n0, k in ((14, 7), (10, 4)):
        parent = _multi_host_parent(cl, rng, n0, exclude=led.busy())
        assert set(parent) <= set(free)
        before = wrapped.stats.n_capped
        res = wrapped.eliminate_to(parent, k)
        assert res is not None
        assert wrapped.stats.n_capped == before + res.n_capped
        _audit_descent(cl, wrapped, res, parent, k)  # host predicts also
        #                           bump n_capped, so assert before auditing


def test_scan_declines_out_of_envelope(stack):
    cl, sim, tables, params = stack
    pred = core.SurrogatePredictor(cl, tables, params)
    h0 = list(cl.hosts[0].gpu_ids[:6])
    assert pred.eliminate_to(h0, 3) is None          # single-host parent
    assert pred.eliminate_to([0, cl.hosts[1].gpu_ids[0]], 2) is None  # n0<=k
    off = core.SurrogatePredictor(cl, tables, params, use_scan=False)
    parent = _multi_host_parent(cl, np.random.default_rng(0), 12)
    assert off.eliminate_to(parent, 6) is None       # scan disabled
    slow = core.SurrogatePredictor(cl, tables, params, vectorized=False)
    assert slow.eliminate_to(parent, 6) is None      # loop featurizer
    # parents overlapping live jobs decline at the wrapper
    led = _tenanted_ledger(cl)
    wrapped = core.ContentionAwarePredictor(cl, pred, led)
    overlap = sorted(set(parent) | {0})  # GPU 0 is held by job "a"
    assert wrapped.eliminate_to(overlap, 6) is None


# ---------------------------------------------------------------------------
# Search- and trace-level goldens: scan on vs off, byte-identical
# ---------------------------------------------------------------------------

def test_pts_search_scan_vs_host(stack):
    cl, sim, tables, params = stack
    on = core.SurrogatePredictor(cl, tables, params)
    off = core.SurrogatePredictor(cl, tables, params, use_scan=False)
    rng = np.random.default_rng(12)
    for k in (4, 9, 12):
        avail = sorted(
            rng.choice(cl.n_gpus, size=min(cl.n_gpus, 22),
                       replace=False).tolist()
        )
        a = search.pts_search(cl, tables, on, avail, k)
        b = search.pts_search(cl, tables, off, avail, k)
        assert a.subset == b.subset
        assert a.predicted_bw == b.predicted_bw
        assert a.n_candidates == b.n_candidates  # same descent accounting


def test_hybrid_search_scan_vs_host_contended(stack):
    cl, sim, tables, params = stack
    led = _tenanted_ledger(cl)
    free = sorted(set(range(cl.n_gpus)) - led.busy())
    rng = np.random.default_rng(13)
    avail = sorted(rng.choice(free, size=min(len(free), 18),
                              replace=False).tolist())
    results = {}
    for use_scan in (True, False):
        pred = core.SurrogatePredictor(cl, tables, params,
                                       use_scan=use_scan)
        wrapped = core.cached_contention_predictor(cl, pred, led)
        results[use_scan] = core.hybrid_search(cl, tables, wrapped, avail, 9)
    _same_or_near_tie(results[True].subset, results[True].predicted_bw,
                      results[False].subset, results[False].predicted_bw)


# ---------------------------------------------------------------------------
# PTS's closing score: the descent's own, cache first, apply near a tie
# ---------------------------------------------------------------------------

class _Spy:
    """Forwards to a predictor chain and logs what a search asks of it.
    With ``eha_value`` every batch of more than one subset (EHA's
    candidates) scores that value instead, which places EHA's score."""

    def __init__(self, inner, eha_value=None):
        self.base = inner
        self.stats = PredictorStats()
        self.eha_value = eha_value
        self.log = []

    def __getattr__(self, name):
        return getattr(self.base, name)

    def predict(self, subsets):
        self.log.append(("predict", [list(s) for s in subsets]))
        if self.eha_value is not None and len(subsets) > 1:
            return np.full((len(subsets),), self.eha_value)
        return self.base.predict(subsets)

    def eliminate_to(self, parent, k):
        res = self.base.eliminate_to(parent, k)
        self.log.append(("descent", res))
        return res


def _closing_case(cl, tenanted):
    """(ledger, avail, k) for a k > 8 hybrid search: EHA scores a batch of
    several candidates, PTS descends from a multi-host parent."""
    led = _tenanted_ledger(cl) if tenanted else JobLedger(cl)
    free = sorted(set(range(cl.n_gpus)) - led.busy())
    avail = sorted(np.random.default_rng(19).choice(
        free, size=20, replace=False).tolist())
    return led, avail, 10


def _standalone(cl, tables, params, led, subset):
    """The closing score the standalone apply gives, on a fresh chain."""
    fresh = core.cached_contention_predictor(
        cl, core.SurrogatePredictor(cl, tables, params), led)
    return float(fresh.predict([subset])[0])


@pytest.mark.parametrize("tenanted", [False, True])
def test_hybrid_closing_score_from_descent(stack, tenanted):
    """Cold cache, EHA's score far below PTS's: PTS keeps the descent's
    closing score bit for bit and no model row is applied for it."""
    cl, sim, tables, params = stack
    led, avail, k = _closing_case(cl, tenanted)
    pred = core.SurrogatePredictor(cl, tables, params)
    spy = _Spy(core.cached_contention_predictor(cl, pred, led),
               eha_value=1.0)
    res = search.hybrid_search(cl, tables, spy, avail, k)
    (scan,) = [r for kind, r in spy.log if kind == "descent"]
    assert scan is not None and res.pts.subset == scan.subset
    assert res.pts.closing == "descent"
    assert res.pts.predicted_bw == scan.bw
    assert res.winner == "PTS" and res.predicted_bw == scan.bw
    assert spy.log[-1][0] == "descent"      # nothing scored after it
    assert pred.stats.n_model_calls == 0    # EHA's batch was the stub's
    assert spy.stats.n_closing_from_descent == 1
    np.testing.assert_allclose(
        scan.bw, _standalone(cl, tables, params, led, scan.subset),
        rtol=surr.SCORE_RTOL)


def test_hybrid_closing_score_cache_first(stack):
    """With PTS's subset in the prediction cache the cached value is the
    closing score, and the descent's is not used."""
    cl, sim, tables, params = stack
    led, avail, k = _closing_case(cl, True)
    first = search.hybrid_search(
        cl, tables,
        _Spy(core.cached_contention_predictor(
            cl, core.SurrogatePredictor(cl, tables, params), led),
            eha_value=1.0),
        avail, k)
    chain = core.cached_contention_predictor(
        cl, core.SurrogatePredictor(cl, tables, params), led)
    assert chain.lookup(first.pts.subset) is None
    warm = float(chain.predict([first.pts.subset])[0])
    hits = chain.stats.cache_hits
    assert chain.lookup(first.pts.subset) == warm
    assert chain.stats.cache_hits == hits + 1
    spy = _Spy(chain, eha_value=1.0)
    res = search.hybrid_search(cl, tables, spy, avail, k)
    assert res.pts.subset == first.pts.subset
    assert res.pts.closing == "cache" and res.pts.predicted_bw == warm
    assert spy.stats.n_closing_from_descent == 0


@pytest.mark.parametrize("side", [-1, 1])
def test_hybrid_closing_near_tie_rescored(stack, side):
    """EHA's score within 2 * SCORE_RTOL of the descent's: PTS's subset
    is scored by the standalone apply, and the winner is the one that
    score gives against EHA's."""
    cl, sim, tables, params = stack
    led, avail, k = _closing_case(cl, True)
    probe = core.cached_contention_predictor(
        cl, core.SurrogatePredictor(cl, tables, params), led)
    scan = probe.eliminate_to(avail, k)
    assert scan is not None
    eha_bw = scan.bw * (1 + side * 0.5 * surr.SCORE_RTOL)
    spy = _Spy(core.cached_contention_predictor(
        cl, core.SurrogatePredictor(cl, tables, params), led),
        eha_value=eha_bw)
    res = search.hybrid_search(cl, tables, spy, avail, k)
    assert res.pts.subset == scan.subset
    assert res.pts.closing == "apply"
    assert spy.log[-1] == ("predict", [scan.subset])
    pts_bw = _standalone(cl, tables, params, led, scan.subset)
    assert res.pts.predicted_bw == pts_bw
    assert res.winner == ("EHA" if eha_bw >= pts_bw else "PTS")
    assert spy.stats.n_closing_from_descent == 0


def test_closing_apply_without_descent_or_rival(stack):
    """Public ``pts_search`` (no EHA score to compare), the host loop and a
    fragmentation-penalised search close with the standalone apply, as
    before: the closing score is a one-row ``predict`` of the subset."""
    cl, sim, tables, params = stack
    led, avail, k = _closing_case(cl, False)

    def standalone(subset):
        return float(core.SurrogatePredictor(cl, tables, params)
                     .predict([subset])[0])

    spy = _Spy(core.SurrogatePredictor(cl, tables, params))
    res = search.pts_search(cl, tables, spy, avail, k)
    assert any(kind == "descent" and r is not None for kind, r in spy.log)
    assert res.closing == "apply"
    assert res.predicted_bw == standalone(res.subset)
    for spy, penalty in (
        (_Spy(core.SurrogatePredictor(cl, tables, params, use_scan=False)),
         None),
        (_Spy(core.SurrogatePredictor(cl, tables, params)),
         defrag_mod.make_frag_penalty(cl, led, 0.1)),
    ):
        hyb = search.hybrid_search(cl, tables, spy, avail, k,
                                   frag_penalty=penalty)
        assert all(r is None for kind, r in spy.log if kind == "descent")
        assert hyb.pts.closing == "apply"
        assert hyb.pts.predicted_bw == standalone(hyb.pts.subset)
        assert spy.stats.n_closing_from_descent == 0


def _scan_dispatcher(cl, tables, params, use_scan, **kw):
    pred = core.SurrogatePredictor(cl, tables, params, use_scan=use_scan)
    return core.BandPilotDispatcher(cl, tables, pred, aot_warm=use_scan,
                                    **kw)


def _logged_replay(disp, cl, sim, tables, trace):
    log = []
    orig = core.BandPilotDispatcher.dispatch

    def wrapped(self, avail, k, rng=None, _log=log):
        s = orig(self, avail, k, rng=rng)
        _log.append(tuple(s))
        return s

    disp.dispatch = wrapped.__get__(disp)
    recs = core.AdmissionScheduler(cl, sim, tables, disp).run(trace)
    return log, recs


def test_trace_replay_golden_scan_on_off(stack):
    """THE acceptance golden: a pinned fifo scheduler trace selects
    byte-identical subsets with the on-device scan enabled (the new
    default) vs disabled (the host-loop configuration)."""
    cl, sim, tables, params = stack
    trace = core.poisson_trace(
        cl, 14, np.random.default_rng(14),
        mean_interarrival=1.0, mean_duration=6.0,
        k_choices=range(4, cl.n_gpus // 2 + 1),
    )
    logs, recs = {}, {}
    for use_scan in (True, False):
        disp = _scan_dispatcher(cl, tables, params, use_scan)
        logs[use_scan], recs[use_scan] = _logged_replay(
            disp, cl, sim, tables, trace
        )
    assert logs[True] == logs[False]
    for a, b in zip(recs[True], recs[False]):
        assert (a.job_id, a.t_admit, a.bw, a.gbe) == \
            (b.job_id, b.t_admit, b.bw, b.gbe)


@pytest.mark.slow
def test_trace_replay_golden_scan_learned_mode(stack):
    """Scan on/off byte identity in the learned-contention configuration:
    contended ledgers decline to the host loop, empty-ledger admissions
    still ride the scan — placements must not move either way."""
    cl, sim, tables, params = stack
    cparams = surr.init_contended_params(params)
    trace = core.poisson_trace(
        cl, 10, np.random.default_rng(15), mean_duration=6.0,
        k_choices=range(4, cl.n_gpus // 2 + 1),
    )
    logs = {}
    for use_scan in (True, False):
        cpred = core.ContendedSurrogatePredictor(cl, tables, cparams)
        disp = _scan_dispatcher(
            cl, tables, params, use_scan,
            contention_mode="learned", contended_predictor=cpred,
        )
        logs[use_scan], _ = _logged_replay(disp, cl, sim, tables, trace)
    assert logs[True] == logs[False]


# ---------------------------------------------------------------------------
# AOT warm-up
# ---------------------------------------------------------------------------

def test_warm_scan_idempotent(stack):
    cl, sim, tables, params = stack
    pred = core.SurrogatePredictor(cl, tables, params)
    pred.warm_scan()  # may or may not compile (executables are process-wide)
    dt = feat.device_tables(cl, tables)
    caps_l = dt.caps_inf().shape[0]
    b = surr.SCAN_MIN_SLOTS
    while b <= min(max(cl.n_gpus, surr.SCAN_MIN_SLOTS), surr.SCAN_MAX_SLOTS):
        assert (b, cl.n_hosts, dt.mask_size, caps_l) in surr._SCAN_COMPILED
        b *= 2
    assert pred.warm_scan() == 0.0  # everything already compiled
    off = core.SurrogatePredictor(cl, tables, params, use_scan=False)
    assert off.warm_scan() == 0.0  # outside the envelope: no-op
    # a warmed dispatcher records the spend; aot_warm=False records zero
    disp = core.BandPilotDispatcher(cl, tables, pred)
    assert disp.aot_warm_seconds == 0.0  # warmed above: nothing left to do
    cold = core.BandPilotDispatcher(cl, tables, pred, aot_warm=False)
    assert cold.aot_warm_seconds == 0.0


# ---------------------------------------------------------------------------
# Packed descent: one argument vector up, one result array down
# ---------------------------------------------------------------------------

_rounds_jit = jax.jit(surr._descent_rounds)


def _descent_inputs(cl, tables, parent):
    """A descent's per-slot and per-host argument arrays, as the unpacked
    layout took them -> (N0b, slot_host, slot_bit, sel0, bits0, counts0)."""
    arrays = feat.host_arrays(cl, tables)
    n0 = len(parent)
    n0b = max(surr._round_up_pow2(n0), surr.SCAN_MIN_SLOTS)
    slot_host = np.zeros((n0b,), np.int32)
    slot_bit = np.zeros((n0b,), np.int32)
    slot_host[:n0] = arrays.gpu_host[parent]
    slot_bit[:n0] = arrays.gpu_bit[parent]
    pbits, pcounts, _, _, _ = feat._batch_bits_counts(arrays, [parent])
    return (n0b, slot_host, slot_bit, np.arange(n0b) < n0,
            pbits[0].astype(np.int32), pcounts[0].astype(np.int32))


def _bytes(a):
    return np.ascontiguousarray(a).view(np.uint8)


_BUCKET_DESCENTS = {8: ((8, 3), (6, 2)), 16: ((12, 5), (16, 9)),
                    32: ((20, 4), (26, 13))}


@pytest.mark.parametrize("capped", [False, True], ids=["uncapped", "capped"])
@pytest.mark.parametrize("bucket", sorted(_BUCKET_DESCENTS))
def test_packed_descent_bit_identical(stack, bucket, capped):
    """The packed descent's unpacked rows, and the ScanResult built from
    them, equal the unpacked layout's five outputs bit for bit, in every
    slot bucket, with and without a cap table."""
    cl, sim, tables, params = stack
    pred = core.SurrogatePredictor(cl, tables, params)
    dt = feat.device_tables(cl, tables)
    led = _tenanted_ledger(cl)
    caps = handed = dt.caps_inf()      # eliminate_to takes numpy caps ...
    if capped:
        wrapped = core.ContentionAwarePredictor(cl, pred, led)
        handed = wrapped._cap_table(dt, wrapped._snapshot())  # ... or device
        caps = wrapped._cap_tab
        assert np.isfinite(caps).any()
        np.testing.assert_array_equal(np.asarray(handed), caps)
    rng = np.random.default_rng(30 + bucket)
    for n0, k in _BUCKET_DESCENTS[bucket]:
        parent = _multi_host_parent(cl, rng, n0, exclude=led.busy())
        n0b, *inputs = _descent_inputs(cl, tables, parent)
        assert n0b == bucket
        # the unpacked layout: every argument and result an array of its own
        want = [np.asarray(y) for y in _rounds_jit(
            params, dt.tok0, dt.tok4, dt.stage1, caps,
            dt.strides.astype(np.int32), *inputs, np.int32(k),
            np.float32(dt.n_gpus_f),
        )]
        packed = surr._pack_args(*inputs, k)
        out = np.asarray(surr._pts_scan_jit(
            *surr._scan_args(params, dt, caps, packed, True)))
        assert out.dtype == np.int32
        got = surr._unpack_result(out, n0b)
        for g, w in zip(got, want):
            assert g.dtype == w.dtype and g.shape == w.shape
            np.testing.assert_array_equal(_bytes(g), _bytes(w))
        res = pred.eliminate_to(parent, k, caps=handed)
        R = n0 - k
        assert res.n_rounds == R and res.n_capped == int(want[4][:R].sum())
        assert capped or res.n_capped == 0
        for name, w in (("scores", want[0]), ("sels", want[1]),
                        ("elims", want[2])):
            g = getattr(res, name)
            assert g.dtype == w.dtype and g.shape == w[:R].shape
            np.testing.assert_array_equal(_bytes(g), _bytes(w[:R]))
        sel = np.arange(n0b) < n0
        sel[want[2][:R]] = False
        assert res.subset == [parent[i] for i in np.nonzero(sel[:n0])[0]]


def test_descent_tables_stay_resident(stack):
    """The cluster's tables are uploaded once; a descent then uploads its
    packed arguments alone, plus the cap table when the ledger moved."""
    cl, sim, tables, params = stack
    res = feat.device_tables(cl, tables).resident()
    assert feat.device_tables(cl, tables).resident() is res
    assert all(a is b for a, b in zip(
        feat.device_tables(cl, tables).resident(), res))
    pred = core.SurrogatePredictor(cl, tables, params)
    led = _tenanted_ledger(cl)
    wrapped = core.ContentionAwarePredictor(cl, pred, led)
    later = [cl.hosts[2].gpu_ids[7], cl.hosts[3].gpu_ids[7]]
    rng = np.random.default_rng(40)
    parent = _multi_host_parent(cl, rng, 14,
                                exclude=led.busy() | set(later))

    def uploads(parent, k, declines=False):
        before = core.collect_stats(wrapped).n_descent_uploads
        assert (wrapped.eliminate_to(parent, k) is None) == declines
        return core.collect_stats(wrapped).n_descent_uploads - before

    single_host = list(cl.hosts[2].gpu_ids[:6])  # no descent, no cap table
    assert uploads(single_host, 3, declines=True) == 0
    assert uploads(parent, 7) == 2        # new ledger version: cap table
    assert uploads(parent, 7) == 1        # same version: packed args only
    assert uploads(parent, 5) == 1
    led.admit("c", later)                 # a commit moves the version
    assert uploads(parent, 7) == 2
    assert uploads(parent, 6) == 1
    before = pred.stats.n_descent_uploads  # isolated: packed args only
    assert pred.eliminate_to(parent, 7) is not None
    assert pred.stats.n_descent_uploads == before + 1


# ---------------------------------------------------------------------------
# Cross-search inference batcher: value neutrality (property-based)
# ---------------------------------------------------------------------------

_STACK_CACHE = {}


def _h100_stack():
    if "H100" not in _STACK_CACHE:
        cl = core.PAPER_CLUSTERS["H100"]()
        sim = core.BandwidthSimulator(cl)
        tables = core.IntraHostTables(cl, sim)
        params = surr.init_hierarchical_params(jax.random.PRNGKey(0))
        _STACK_CACHE["H100"] = (cl, sim, tables, params)
    return _STACK_CACHE["H100"]


def _solo_apply(params, feats, mask):
    """The un-batched apply path: pad B to a power of two with sentinel
    rows, one jitted call, slice the real rows back."""
    B = feats.shape[0]
    Bp = 1
    while Bp < B:
        Bp *= 2
    f = np.zeros((Bp,) + feats.shape[1:], feats.dtype)
    m = np.zeros((Bp, feats.shape[1]), mask.dtype)
    m[B:, 0] = 1.0
    f[:B] = feats
    m[:B] = mask
    out = np.asarray(
        surr._apply_hierarchical_bw(params, jnp.asarray(f), jnp.asarray(m))
    )
    return out[:B]


def _check_batcher_neutral(seed: int) -> None:
    cl, sim, tables, params = _h100_stack()
    rng = np.random.default_rng(seed)
    n_workers = int(rng.integers(1, 4))
    requests = []
    for _ in range(n_workers):
        B = int(rng.integers(1, 5))
        subs = [
            sorted(rng.choice(cl.n_gpus, size=int(rng.integers(2, 13)),
                              replace=False).tolist())
            for _ in range(B)
        ]
        requests.append(feat.featurize_batch(cl, tables, subs))
    want = [_solo_apply(params, f, m) for f, m in requests]
    batcher = InferenceBatcher()
    got = [None] * n_workers
    errs = []
    barrier = threading.Barrier(n_workers)

    def run(i):
        try:
            with batcher.worker():
                barrier.wait()
                f, m = requests[i]
                got[i] = batcher.apply(
                    surr._apply_hierarchical_bw, params, f, m
                )
        except BaseException as e:  # noqa: BLE001 - re-raised below
            errs.append(e)

    threads = [threading.Thread(target=run, args=(i,))
               for i in range(n_workers)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    if errs:
        raise errs[0]
    assert batcher.n_requests == n_workers
    # a fused request runs at a larger padded batch, which XLA-CPU's matmul
    # already rounds differently (up to ~3e-6 relative): the score contract
    for w, g in zip(want, got):
        np.testing.assert_allclose(g, w, rtol=surr.SCORE_RTOL)


@settings(max_examples=8, deadline=None)
@given(seed=st.integers(min_value=0, max_value=2**32 - 1))
def test_property_batcher_value_neutral(seed):
    _check_batcher_neutral(seed)


@pytest.mark.skipif(HAVE_HYPOTHESIS, reason="hypothesis drives this instead")
def test_seeded_batcher_value_neutral():
    for seed in (0, 1, 7, 1234):
        _check_batcher_neutral(seed)


def test_batcher_through_predictor(stack):
    """The surrogate's apply path routes through a thread-registered
    batcher and returns exactly what the direct path returns."""
    cl, sim, tables, params = stack
    pred = core.SurrogatePredictor(cl, tables, params)
    rng = np.random.default_rng(16)
    subs = [sorted(rng.choice(cl.n_gpus, size=10, replace=False).tolist())
            for _ in range(5)]
    want = pred.predict(subs)
    batcher = InferenceBatcher()
    with batcher.worker():
        got = pred.predict(subs)
    np.testing.assert_array_equal(want, got)
    assert batcher.n_requests > 0


def test_joint_search_batched_identical(stack):
    """joint_hybrid_search with the batcher (threaded orders) picks the
    same plan as the sequential path: the batcher's padded batches are other
    XLA shapes, so the scores agree to the contract, not to the bit."""
    cl, sim, tables, params = stack
    pred = core.SurrogatePredictor(cl, tables, params)
    led = JobLedger(cl)
    led.admit("t", [0, 1])
    reqs = [("j1", 12), ("j2", 4), ("j3", 8)]
    seq = search.joint_hybrid_search(cl, tables, pred, led, reqs)
    bat = search.joint_hybrid_search(cl, tables, pred, led, reqs,
                                     batcher=InferenceBatcher())
    _same_or_near_tie(
        (seq.order, [p.subset for p in seq.placements]),
        seq.total_predicted_bw,
        (bat.order, [p.subset for p in bat.placements]),
        bat.total_predicted_bw,
    )


def test_defrag_proposer_batcher_neutral(stack):
    cl, sim, tables, params = stack
    pred = core.SurrogatePredictor(cl, tables, params)
    led = _tenanted_ledger(cl)
    free = sorted(set(range(cl.n_gpus)) - led.busy())
    plain = defrag_mod.consolidation_proposer(cl, tables, pred)
    batched = defrag_mod.consolidation_proposer(
        cl, tables, pred, batcher=InferenceBatcher()
    )
    assert plain(led, free, 4) == batched(led, free, 4)


# ---------------------------------------------------------------------------
# LRU-capped lifetime memo
# ---------------------------------------------------------------------------

def _check_lru(seed: int) -> None:
    rng = np.random.default_rng(seed)
    cap = int(rng.integers(1, 9))
    lru = LruDict(cap)
    ref = {}
    for step in range(60):
        key = int(rng.integers(0, 12))
        if rng.random() < 0.5:
            ref[key] = (key, step) if rng.random() < 0.2 else key * 2
            lru[key] = ref[key]
        else:
            got = lru.get(key)
            # eviction may forget, but a served value is never wrong
            assert got is None or got == ref[key]
        assert len(lru) <= cap
    # recency: touch the oldest entry, insert a fresh key -> the touched
    # entry survives and the next-oldest is the one evicted
    lru = LruDict(2)
    lru["a"] = 1
    lru["b"] = 2
    assert lru["a"] == 1
    lru["c"] = 3
    assert "a" in lru and "b" not in lru and "c" in lru


@settings(max_examples=20, deadline=None)
@given(seed=st.integers(min_value=0, max_value=2**32 - 1))
def test_property_lru_dict(seed):
    _check_lru(seed)


@pytest.mark.skipif(HAVE_HYPOTHESIS, reason="hypothesis drives this instead")
def test_seeded_lru_dict():
    for seed in (0, 1, 7, 1234):
        _check_lru(seed)


def test_prediction_cache_lru_capped(stack):
    """A tightly-capped lifetime memo stays within its bound and keeps
    serving correct values (recompute-on-evict, never a wrong hit)."""
    cl, sim, tables, params = stack
    pred = core.SurrogatePredictor(cl, tables, params)
    cache = PredictionCache(max_entries=8)
    cached = cache.wrap(pred, mode="isolated", versioned=False)
    fresh = core.SurrogatePredictor(cl, tables, params)
    rng = np.random.default_rng(17)
    subs = [sorted(rng.choice(cl.n_gpus, size=6, replace=False).tolist())
            for _ in range(30)]
    for s in subs + subs[:10]:
        np.testing.assert_array_equal(
            cached.predict([s]), fresh.predict([s])
        )
        assert len(cache._static) <= 8


# ---------------------------------------------------------------------------
# Stats: the scan path gets its own bucket, merges cleanly
# ---------------------------------------------------------------------------

def test_scan_stats_accounting(stack):
    cl, sim, tables, params = stack
    pred = core.SurrogatePredictor(cl, tables, params)
    parent = _multi_host_parent(cl, np.random.default_rng(18), 16)
    res = pred.eliminate_to(parent, 8)
    assert res is not None
    assert pred.stats.n_scan_steps == 8
    assert pred.stats.scan_seconds > 0.0
    # the fused descent bumps ONLY the scan bucket: no phantom model calls
    assert pred.stats.n_model_calls == 0
    assert pred.stats.infer_seconds == 0.0
    merged = PredictorStats.merged(pred.stats, pred.stats)
    assert merged.n_scan_steps == 2 * pred.stats.n_scan_steps
    # reset() clears the new fields with everything else
    pred.stats.reset()
    assert pred.stats.n_scan_steps == 0 and pred.stats.scan_seconds == 0.0


def test_dispatcher_stats_include_scan(stack):
    cl, sim, tables, params = stack
    pred = core.SurrogatePredictor(cl, tables, params)
    disp = core.BandPilotDispatcher(cl, tables, pred)
    disp.admit("a", 12)
    disp.admit("b", 10)
    st_ = disp.predictor_stats()
    assert st_.n_scan_steps == pred.stats.n_scan_steps > 0
    assert st_.scan_seconds == pred.stats.scan_seconds > 0.0
    # the host-loop fields still behave (final re-score runs on the host)
    assert st_.n_model_calls > 0 and st_.infer_seconds > 0.0


# ---------------------------------------------------------------------------
# Scheduler: batch_applies on/off golden
# ---------------------------------------------------------------------------

def test_scheduler_batch_applies_golden(stack, monkeypatch):
    """A batched-policy burst placed as one joint plan is byte-identical
    with the cross-search batcher on vs off."""
    cl, sim, tables, params = stack
    trace = (
        [core.TraceJob("filler", 0.0, 5.0, cl.n_gpus)]
        + [core.TraceJob(f"b{i}", 1.0 + 0.1 * i, 50.0, [4, 8, 12][i % 3])
           for i in range(3)]
    )
    plans = {}
    orig = search.joint_hybrid_search

    def run(batch_applies):
        log = []

        def spy(*a, **kw):
            plan = orig(*a, **kw)
            log.append([tuple(p.subset) for p in plan.placements])
            return plan

        monkeypatch.setattr(search, "joint_hybrid_search", spy)
        pred = core.SurrogatePredictor(cl, tables, params)
        disp = core.BandPilotDispatcher(cl, tables, pred)
        cfg = core.SchedulerConfig(
            policy="batched", batch_window=1.0, batch_applies=batch_applies
        )
        sch = core.AdmissionScheduler(cl, sim, tables, disp, config=cfg)
        recs = sch.run(trace)
        plans[batch_applies] = log
        return [(r.job_id, r.t_admit, r.batch_size, r.bw, r.gbe)
                for r in recs], sch

    recs_off, _ = run(False)
    recs_on, sch_on = run(True)
    assert recs_off == recs_on
    assert plans[True] == plans[False]
    assert any(len(p) > 1 for p in plans[True])  # a real joint batch ran
    assert sch_on._batcher is not None
    assert sch_on._batcher.n_requests > 0  # applies actually fused
