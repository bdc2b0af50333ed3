"""Multi-tenant dispatching: the admit/release lifecycle + contention.

Walks the contention subsystem end to end on the H100 testbed (no surrogate
training — the ground-truth predictor keeps this snappy):

  1. admit a cross-host tenant, watch a candidate's bandwidth degrade under
     the fair-share rail model, release and watch it restore *exactly*;
  2. replay the same Poisson job trace through contention-aware BandPilot,
     the contention-oblivious variant, and the Topo/Default/Random
     baselines, grading every admission with contention-degraded GBE
     against the ledger-aware exact Oracle.

  PYTHONPATH=src python examples/multi_tenant.py
"""

import numpy as np

import repro.core as core


def main():
    core.use_compile_cache()
    cluster = core.h100_cluster()
    sim = core.BandwidthSimulator(cluster)
    tables = core.IntraHostTables(cluster, sim)
    print(cluster.describe())

    # -- 1. lifecycle: degrade under contention, restore on release --------
    bp = core.BandPilotDispatcher(
        cluster, tables, core.GroundTruthPredictor(sim)
    )
    candidate = list(range(0, 4)) + list(range(8, 12))  # 4+4 on hosts 0,1
    iso = sim.true_bandwidth(candidate)
    print(f"\ncandidate 4+4 on hosts (0,1): isolated B(S) = {iso:.1f} GB/s")

    tenant = bp.ledger.admit("tenant-a", list(range(4, 8)) + list(range(12, 16)))
    print(f"admitted {tenant.job_id}: k={tenant.k} on hosts {tenant.host_ids}")
    print(bp.ledger.describe())
    deg = sim.true_bandwidth(candidate, ledger=bp.ledger)
    view = core.virtual_merge(cluster, bp.ledger, candidate)
    print(f"virtual merge: rail shares {view.rail_shares} "
          f"({len(view.merged_gpus)} GPUs in merged collective)")
    print(f"contended B(S | ledger) = {deg:.1f} GB/s "
          f"({100 * (1 - deg / iso):.0f}% degradation)")

    # the aware search routes around the tenant; the oblivious one cannot tell
    s_aware = bp.dispatch(bp.ledger.available(), 8)
    hosts = sorted(cluster.partition_by_host(s_aware))
    print(f"aware dispatch(k=8) lands on hosts {hosts}: "
          f"B = {sim.true_bandwidth(s_aware, ledger=bp.ledger):.1f} GB/s")

    bp.release("tenant-a")
    restored = sim.true_bandwidth(candidate, ledger=bp.ledger)
    assert restored == iso
    print(f"released tenant-a: B(S | ledger) = {restored:.1f} GB/s "
          "(exactly isolated again)")

    # -- 2. trace replay: aware vs oblivious vs baselines -------------------
    seed = 3
    trace = core.poisson_trace(
        cluster, 40, np.random.default_rng(seed),
        mean_interarrival=1.0, mean_duration=8.0,
        k_choices=range(4, cluster.n_gpus // 2 + 1),
    )
    print(f"\nreplaying {len(trace)} Poisson jobs "
          f"(k in [4, {cluster.n_gpus // 2}], mean duration 8.0) ...")
    results = core.compare_contention_awareness(
        cluster, sim, tables,
        lambda: core.GroundTruthPredictor(sim), trace, seed=seed,
    )
    summaries = {
        name: core.summarize_trace(recs)[name]
        for name, recs in results.items()
    }
    print(f"{'dispatcher':<22} {'mean GBE':>9} {'degraded':>9} "
          f"{'contended':>10} {'mean wait':>10}")
    for name, s in sorted(
        summaries.items(), key=lambda kv: -kv[1]["mean_gbe"]
    ):
        print(f"{name:<22} {100 * s['mean_gbe']:>8.2f}% "
              f"{100 * s['mean_degradation']:>8.1f}% "
              f"{100 * s['frac_contended']:>9.0f}% {s['mean_wait']:>10.2f}")


if __name__ == "__main__":
    main()
