"""Seeded Poisson arrivals with exponential durations and uniform k.

The shape of the program's own ``poisson_trace``: for each job, an
exponential interarrival gap, an exponential duration (at least 1e-3) and
k drawn uniformly from ``k_min..k_max``.  Times are in trace units; the
replay is closed, so they order events and never wait on the wall clock.

The jobs are drawn once from the mix's ``base_seed`` (the same set of
sizes, gaps and durations for every seed); the run's seed orders them,
block by block of ``block_jobs`` jobs, so that every seed offers the same
work in each block and differs only in its order.
"""

from __future__ import annotations

from typing import Dict, List, Sequence, Tuple

import numpy as np

Job = Tuple[str, float, float, int]   # (job id, arrival, duration, k)


def generate(mix: Dict, seed_words: Sequence[int], n_jobs: int,
             prefix: str) -> List[Job]:
    """``seed_words`` = (run seed, stream, chunk); the chunk's jobs come
    from (base_seed, stream, chunk), their order from ``seed_words``."""
    *_, stream, chunk = seed_words
    base = np.random.default_rng([mix["base_seed"], stream, chunk])
    ks = list(range(int(mix["k_min"]), int(mix["k_max"]) + 1))
    draws = []
    for _ in range(n_jobs):
        gap = float(base.exponential(mix["mean_interarrival"]))
        dur = max(float(base.exponential(mix["mean_duration"])), 1e-3)
        draws.append((gap, dur, ks[int(base.integers(len(ks)))]))
    order = np.random.default_rng(list(seed_words))
    block = int(mix["block_jobs"])
    jobs: List[Job] = []
    t = 0.0
    for lo in range(0, n_jobs, block):
        part = draws[lo:lo + block]
        for j in order.permutation(len(part)):
            gap, dur, k = part[j]
            t += gap
            jobs.append((f"{prefix}-{len(jobs):05d}", t, dur, k))
    return jobs
