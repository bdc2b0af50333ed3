"""Smoke run of the dispatcher's admission path on one TPU chip.

Drives the system once through its public API (``repro.core``) at the
paper's own deployment size, the 4x8 H100 and 4x8 Het-4Mix clusters:

1. device      - what JAX found; anything but a TPU ends the run non-zero
2. surrogate   - train each cluster's surrogate (250 samples, 2000 steps)
3. warm-up     - build the dispatcher (AOT compile of the fused descent);
                 the Fig. 1 scenario must pick a 4+4 split
4. scan        - fused on-device descents against the host loop, isolated
                 and through an analytic cap table, held to the agreement
                 contract of ``repro.core.surrogate.audit_descent``
5. admission   - a 200-job Poisson trace per cluster (fifo, analytic
                 contention) graded against the ledger-aware exact oracle
6. learned     - the ContendedSurrogate and a 100-job learned-contention
                 trace on H100 (the per-round apply path)

Every phase prints its lines; a failed check is printed as ``FAIL`` and
makes the run exit 1 after the last phase.  Only on a TPU with every check
holding is the last line ``{"ok": true, "device": {...}}``.

  python chip_smoke.py                                # one TPU chip
  JAX_PLATFORMS=cpu python chip_smoke.py --cpu-rehearsal

``--cpu-rehearsal`` runs every phase on whatever device JAX has and still
exits non-zero: it checks the control flow, not the chip.
"""

from __future__ import annotations

import argparse
import importlib.metadata
import json
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402

import repro.core as core  # noqa: E402
from repro.core import telemetry  # noqa: E402

CLUSTERS = ("H100", "Het-4Mix")
TRACE_JOBS = 200
LEARNED_JOBS = 100
# (n0, k) parents of the scan-vs-host audit: slot buckets 16 and 32
DESCENTS = ((9, 2), (12, 6), (16, 8), (20, 10), (24, 4))

# This script's own XLA-CPU run (``--cpu-rehearsal``, jax 0.9.0): the chip
# run must land within MAPE_TOL / GBE_TOL points of these.
CPU_REFERENCE = {
    "H100": {"mape": 1.352, "gbe": 99.931},
    "Het-4Mix": {"mape": 2.923, "gbe": 95.359},
}
MAPE_TOL = 0.5   # points of MAPE (H100)
GBE_TOL = 1.0    # points of mean GBE (each cluster, fifo/analytic)


class Checks:
    def __init__(self):
        self.failed = []

    def __call__(self, ok: bool, what: str) -> None:
        print(f"  check {'ok  ' if ok else 'FAIL'} {what}", flush=True)
        if not ok:
            self.failed.append(what)


def _tenanted_ledger(cl):
    led = core.JobLedger(cl)
    led.admit("a", [0, 1, cl.hosts[1].gpu_ids[0]])
    led.admit("b", [cl.hosts[1].gpu_ids[1], cl.hosts[-1].gpu_ids[0]])
    led.admit("s", [cl.hosts[0].gpu_ids[5]])  # single-host: occupancy only
    return led


def _multi_host_parent(cl, rng, n0, exclude):
    pool = [g for g in range(cl.n_gpus) if g not in exclude]
    while True:
        parent = sorted(rng.choice(pool, size=n0, replace=False).tolist())
        if len(cl.partition_by_host(parent)) > 1:
            return parent


def phase_device(rehearsal: bool):
    dev = jax.devices()[0]
    print(f"[device] platform={dev.platform} kind={dev.device_kind!r} "
          f"count={len(jax.devices())} jax={jax.__version__} "
          f"libtpu={importlib.metadata.version('libtpu')}", flush=True)
    if dev.platform != "tpu" and not rehearsal:
        print(f"[device] no TPU (found {dev.platform}): nothing to smoke",
              flush=True)
        sys.exit(1)
    return dev


def phase_surrogate(name, check):
    cl = core.PAPER_CLUSTERS[name]()
    sim = core.BandwidthSimulator(cl)
    tables = core.IntraHostTables(cl, sim)
    train, test = core.make_train_test_split(sim, 250, seed=0)
    params, info = core.train_surrogate(
        cl, tables, train, core.TrainConfig(steps=2000)
    )
    pred = core.SurrogatePredictor(cl, tables, params)
    acc = core.evaluate_surrogate(pred, test)
    print(f"[surrogate] {name} R2={acc['r2']:.4f} MAPE={acc['mape']:.3f}% "
          f"train_seconds={info['train_seconds']:.2f} "
          f"final_loss={info['final_loss']:.6f}", flush=True)
    ref = CPU_REFERENCE[name]["mape"]
    if name == "H100":
        check(abs(acc["mape"] - ref) <= MAPE_TOL,
              f"{name} MAPE {acc['mape']:.3f}% within {MAPE_TOL} of the "
              f"CPU run's {ref}%")
    check(np.isfinite(acc["r2"]) and acc["r2"] > 0.9, f"{name} R2 > 0.9")
    return cl, sim, tables, params


def phase_warmup(name, cl, tables, params, cache_state, check):
    pred = core.SurrogatePredictor(cl, tables, params)
    disp = core.BandPilotDispatcher(cl, tables, pred)
    print(f"[warm-up] {name} aot_warm_seconds={disp.aot_warm_seconds:.3f} "
          f"cache={cache_state}", flush=True)
    if name == "H100":
        avail = list(range(0, 6)) + list(range(8, 14))
        s = disp.dispatch(avail, 8)
        split = sorted(len(v) for v in cl.partition_by_host(s).values())
        print(f"[warm-up] Fig.1 k=8 over 6+6 idle -> {s} split={split}",
              flush=True)
        check(split == [4, 4], "Fig.1 scenario picks a 4+4 split")


def phase_scan(name, cl, tables, params, check):
    on = core.SurrogatePredictor(cl, tables, params)
    off = core.SurrogatePredictor(cl, tables, params, use_scan=False)
    led = _tenanted_ledger(cl)
    rng = np.random.default_rng(10)
    gap, ties, n = 0.0, 0, 0
    for mode in ("isolated", "capped"):
        if mode == "isolated":
            p_on, p_off, busy = on, off, set()
        else:
            p_on = core.ContentionAwarePredictor(cl, on, led)
            p_off = core.ContentionAwarePredictor(cl, off, led)
            busy = led.busy()
        for n0, k in DESCENTS:
            parent = _multi_host_parent(cl, rng, n0, busy)
            res = p_on.eliminate_to(parent, k)
            check(res is not None, f"{name} {mode} {n0}->{k} ran on-device")
            if res is None:
                continue
            # the audit replays every round on the host loop: with no
            # violations, every round took the host argmax or a near-tie
            audit = core.audit_descent(p_off, res, parent, k)
            check(not audit.violations,
                  f"{name} {mode} {n0}->{k} agrees with the host loop "
                  f"(near-ties={audit.near_ties}, "
                  f"gap={audit.max_rel_gap:.3g}) {audit.violations}")
            gap = max(gap, audit.max_rel_gap)
            ties += audit.near_ties
            n += 1
    print(f"[scan] {name} descents={n} max_rel_score_gap={gap:.3g} "
          f"(contract {core.SCORE_RTOL:g}) near_ties={ties} "
          f"declines={on.stats.n_scan_declines}", flush=True)
    check(on.stats.n_scan_declines == 0, f"{name} no in-envelope declines")


def _replay(cl, sim, tables, disp, trace):
    with telemetry.trace(core.AdmissionTracer(capacity=1 << 16)) as tr:
        recs = core.AdmissionScheduler(cl, sim, tables, disp,
                                       grade=True).run(trace)
    ms = 1e3 * np.asarray([s.duration for s in tr.spans("dispatcher.dispatch")])
    return recs, ms


def _trace(cl, n_jobs, seed):
    return core.poisson_trace(
        cl, n_jobs, np.random.default_rng(seed),
        mean_interarrival=1.0, mean_duration=8.0,
        k_choices=range(4, cl.n_gpus // 2 + 1),
    )


def phase_admission(name, cl, sim, tables, params, check):
    pred = core.SurrogatePredictor(cl, tables, params)
    disp = core.BandPilotDispatcher(cl, tables, pred)
    trace = _trace(cl, TRACE_JOBS, seed=0)
    t0 = time.perf_counter()
    recs, ms = _replay(cl, sim, tables, disp, trace)
    wall = time.perf_counter() - t0
    gbe = 100 * float(np.mean([r.gbe for r in recs]))
    st = disp.predictor_stats()
    admits = np.sort([r.t_admit for r in recs])
    arrivals = np.sort([r.t_admit - r.wait for r in recs])
    backlog = int(max(
        np.searchsorted(arrivals, a, "right") - np.searchsorted(admits, a,
                                                               "right")
        for a in arrivals
    ))
    print(f"[admission] {name} jobs={len(recs)} mean_gbe={gbe:.3f}% "
          f"max_backlog={backlog} "
          f"n_scan_steps={st.n_scan_steps} n_model_calls={st.n_model_calls} "
          f"scan_declines={st.n_scan_declines} "
          f"dispatch_ms_p50={np.percentile(ms, 50):.2f} "
          f"p99={np.percentile(ms, 99):.2f} replay_seconds={wall:.1f} "
          f"(host clock, information only)", flush=True)
    check(len(recs) == TRACE_JOBS, f"{name} every job admitted")
    check(st.n_scan_steps > 0, f"{name} the fused descent ran")
    check(st.n_scan_declines == 0, f"{name} no in-envelope scan declines")
    ref = CPU_REFERENCE[name]["gbe"]
    check(abs(gbe - ref) <= GBE_TOL,
          f"{name} mean GBE {gbe:.3f}% within {GBE_TOL} of the CPU run's "
          f"{ref}%")


def phase_learned(cl, tables, params, check):
    sat = core.BandwidthSimulator(cl, contention="saturating")
    train, _ = core.make_contended_split(sat, 600, test_mult=1, seed=3,
                                         max_cotenants=6)
    cparams, info = core.train_contended_surrogate(
        cl, tables, core.to_triples(cl, train),
        core.TrainConfig(steps=2000), base_params=params,
    )
    cpred = core.ContendedSurrogatePredictor(cl, tables, cparams)
    disp = core.BandPilotDispatcher(
        cl, tables, core.SurrogatePredictor(cl, tables, params),
        contention_mode="learned", contended_predictor=cpred,
    )
    recs, ms = _replay(cl, sat, tables, disp, _trace(cl, LEARNED_JOBS, 1))
    gbe = 100 * float(np.mean([r.gbe for r in recs]))
    print(f"[learned] H100 train_seconds={info['train_seconds']:.2f} "
          f"jobs={len(recs)} mean_gbe={gbe:.3f}% "
          f"contended_model_calls={cpred.stats.n_model_calls} "
          f"dispatch_ms_p50={np.percentile(ms, 50):.2f} "
          f"p99={np.percentile(ms, 99):.2f}", flush=True)
    check(len(recs) == LEARNED_JOBS, "learned: every job admitted")
    check(cpred.stats.n_model_calls > 0, "learned: contended applies ran")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--cpu-rehearsal", action="store_true",
                    help="run every phase without a TPU; always exits 1")
    args = ap.parse_args()

    cache_dir = pathlib.Path(core.use_compile_cache())
    n_cached = len(list(cache_dir.iterdir())) if cache_dir.is_dir() else 0
    cache_state = f"{'warm' if n_cached else 'cold'}({n_cached} entries)"
    print(f"[cache] dir={cache_dir} entries_at_start={n_cached}", flush=True)
    dev = phase_device(args.cpu_rehearsal)
    check = Checks()
    t_start = time.perf_counter()

    stacks = {name: phase_surrogate(name, check) for name in CLUSTERS}
    for name, (cl, sim, tables, params) in stacks.items():
        phase_warmup(name, cl, tables, params, cache_state, check)
    for name, (cl, sim, tables, params) in stacks.items():
        phase_scan(name, cl, tables, params, check)
    for name, (cl, sim, tables, params) in stacks.items():
        phase_admission(name, cl, sim, tables, params, check)
    cl, _, tables, params = stacks["H100"]
    phase_learned(cl, tables, params, check)

    stats = dev.memory_stats() or {}
    print(f"[memory] peak_bytes_in_use={stats.get('peak_bytes_in_use')} "
          f"bytes_limit={stats.get('bytes_limit')}", flush=True)
    print(f"[done] seconds={time.perf_counter() - t_start:.1f} "
          f"failed_checks={len(check.failed)}", flush=True)
    if check.failed or dev.platform != "tpu":
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices()),
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
