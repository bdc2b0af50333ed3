"""Quickstart: BandPilot end-to-end on a simulated H100 cluster.

Builds the paper's physical testbed (4 hosts x 8 H100), trains the
hierarchical Transformer surrogate on 250 sparse measurements, and compares
dispatchers on the Fig. 1 scenario + randomized requests.

  PYTHONPATH=src python examples/quickstart.py
"""

import numpy as np

import repro.core as core


def main():
    core.use_compile_cache()
    # 1. the cluster + its (black-box) bandwidth landscape
    cluster = core.h100_cluster()
    sim = core.BandwidthSimulator(cluster)
    print(cluster.describe())

    # 2. Stage-1: exhaustive intra-host measurement (one-time, offline)
    tables = core.IntraHostTables(cluster, sim)
    print(f"intra-host tables: {tables.n_measurements} measurements, "
          f"{tables.storage_bytes() / 1024:.0f} KB")

    # 3. Stage-2: train the surrogate on 250 sparse inter-host samples
    train_set, test_set = core.make_train_test_split(sim, 250, seed=0)
    params, info = core.train_surrogate(
        cluster, tables, train_set, core.TrainConfig(steps=2000)
    )
    predictor = core.SurrogatePredictor(cluster, tables, params)
    acc = core.evaluate_surrogate(predictor, test_set)
    print(f"surrogate: R2={acc['r2']:.4f} MAPE={acc['mape']:.2f}% "
          f"({info['param_bytes'] / 1024:.0f} KB model)")

    # 4. the Fig. 1 scenario: two hosts with 6 idle GPUs each, k=8
    avail = list(range(0, 6)) + list(range(8, 14))
    bp = core.BandPilotDispatcher(cluster, tables, predictor)
    topo = core.BaselineDispatcher(cluster, "topo")
    s_bp = bp.dispatch(avail, 8)
    s_topo = topo.dispatch(avail, 8)
    print(f"\nFig.1 scenario (k=8, 6+6 idle):")
    print(f"  Topo      -> {s_topo}  B={sim.true_bandwidth(s_topo):.1f} GB/s")
    print(f"  BandPilot -> {s_bp}  B={sim.true_bandwidth(s_bp):.1f} GB/s")

    # 5. randomized availability protocol (Sec. 5.3, abbreviated)
    ds = [bp, topo, core.BaselineDispatcher(cluster, "default"),
          core.BaselineDispatcher(cluster, "random")]
    recs = core.evaluate_dispatchers(
        cluster, sim, tables, ds, request_sizes=[4, 8, 12, 16, 20],
        n_scenarios=10, seed=1,
    )
    print("\nmean GBE over randomized scenarios:")
    for name, s in sorted(core.summarize(recs).items(),
                          key=lambda kv: -kv[1]["mean_gbe"]):
        print(f"  {name:10s} {100 * s['mean_gbe']:5.1f}%  "
              f"(bw loss {s['mean_bw_loss']:.1f} GB/s)")


if __name__ == "__main__":
    main()
