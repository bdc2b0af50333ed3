"""Benchmark harness: one bench per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows (stdout) per the repo contract.
Scenario counts honour BENCH_SCENARIOS (default 20; paper protocol = 50).

Alongside the CSV stream, every completed run writes a machine-readable
``BENCH_RESULTS.json`` (path override: ``BENCH_RESULTS_PATH``) so the perf
trajectory is trackable across commits — one entry per row with the bench
name, config row, metric value/units, the parsed derived fields, and the
git commit.  CI archives it as an artifact (see .github/workflows/ci.yml).

  PYTHONPATH=src python -m benchmarks.run            # all benches
  PYTHONPATH=src python -m benchmarks.run fig5 fig9  # subset by prefix
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time
import traceback

import repro.core as core
from benchmarks import (
    bench_surrogate_accuracy,
    bench_dispatch_gbe,
    bench_bandwidth_loss,
    bench_search_overhead,
    bench_hier_vs_naive,
    bench_search_ablation,
    bench_offline_cost,
    bench_llama70b_delta,
    bench_contention,
    bench_scheduler,
    bench_learned_contention,
    bench_defrag,
    bench_dispatch_throughput,
    bench_controlplane,
    bench_failure_recovery,
)

BENCHES = [
    ("fig5_surrogate_accuracy", bench_surrogate_accuracy.run),
    ("table2_fig6_dispatch_gbe", bench_dispatch_gbe.run),
    ("fig7_bandwidth_loss", bench_bandwidth_loss.run),
    ("fig8_search_overhead", bench_search_overhead.run),
    ("fig9_hier_vs_naive", bench_hier_vs_naive.run),
    ("fig10_search_ablation", bench_search_ablation.run),
    ("table3_offline_cost", bench_offline_cost.run),
    ("appendixA_llama70b_delta", bench_llama70b_delta.run),
    ("sec44_contention", bench_contention.run),
    ("issue2_scheduler_policies", bench_scheduler.run),
    ("issue3_learned_contention", bench_learned_contention.run),
    ("issue4_defrag", bench_defrag.run),
    ("issue6_dispatch_throughput", bench_dispatch_throughput.run),
    ("issue7_controlplane", bench_controlplane.run),
    ("issue10_failure_recovery", bench_failure_recovery.run),
]

RESULTS_SCHEMA = 1


def _git_commit() -> str:
    try:
        return subprocess.run(
            ["git", "rev-parse", "HEAD"],
            capture_output=True, text=True, timeout=10,
            cwd=os.path.dirname(os.path.abspath(__file__)),
        ).stdout.strip() or "unknown"
    except (OSError, subprocess.SubprocessError):
        return "unknown"


def parse_row(bench: str, row: str) -> dict:
    """One CSV row -> one BENCH_RESULTS.json entry.

    Rows follow the repo contract ``name,us_per_call,derived`` where
    ``derived`` is ";"-separated ``k=v`` pairs (kept verbatim *and* parsed
    into ``derived_fields``, with numeric strings coerced).
    """
    name, _, rest = row.partition(",")
    value_str, _, derived = rest.partition(",")
    try:
        value = float(value_str)
    except ValueError:
        value = float("nan")
    fields = {}
    for pair in derived.split(";"):
        k, sep, v = pair.partition("=")
        if not sep:
            continue
        try:
            fields[k.strip()] = float(v)
        except ValueError:
            fields[k.strip()] = v.strip()
    return {
        "bench": bench,
        "row": name,
        "metric": "us_per_call",
        "value": value,
        "units": "us",
        "derived": derived,
        "derived_fields": fields,
    }


def write_results(entries, path=None, commit=None) -> str:
    """Dump entries (plus schema/commit header) to BENCH_RESULTS.json."""
    path = path or os.environ.get("BENCH_RESULTS_PATH", "BENCH_RESULTS.json")
    doc = {
        "schema": RESULTS_SCHEMA,
        "commit": commit if commit is not None else _git_commit(),
        "generated_at": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        "results": entries,
    }
    with open(path, "w", encoding="utf-8") as fh:
        json.dump(doc, fh, indent=1, sort_keys=True)
        fh.write("\n")
    return path


def main() -> None:
    core.use_compile_cache()
    prefixes = [a for a in sys.argv[1:] if not a.startswith("-")]
    print("name,us_per_call,derived")
    failures = 0
    entries = []
    for name, fn in BENCHES:
        if prefixes and not any(name.startswith(p) or p in name
                                for p in prefixes):
            continue
        t0 = time.time()
        try:
            for row in fn():
                print(row, flush=True)
                entries.append(parse_row(name, row))
            print(f"# {name} done in {time.time() - t0:.0f}s", flush=True)
        except Exception:
            failures += 1
            traceback.print_exc()
            print(f"{name},0,FAILED", flush=True)
            entries.append({
                "bench": name, "row": name, "metric": "failed",
                "value": float("nan"), "units": "", "derived": "FAILED",
                "derived_fields": {},
            })
    path = write_results(entries)
    print(f"# wrote {len(entries)} entries to {path}", flush=True)
    if failures:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
