"""Run one benchmark cell once and print its result as one JSON line.

  python3 perfbench/run.py --workload h100-4x8.fifo-analytic --seed 1 \
      --seconds 10 --trace 0

The cell is read from ``BENCHMARK.json`` at the checkout's root; its
configuration, traffic mix, metric readers and limits are files under
``perfbench/`` found by name (see ``cell.py``).  A run trains the
configuration's surrogate, builds the dispatcher with its AOT warm-up and
replays a fixed warm-up trace (set-up), then replays the seed's trace for
``--seconds`` (the window), grades a prefix of the window's admissions
against the exact oracle and compares what the window produced with the plain
reference (``check.py``).  ``--trace 1`` runs a window of at most
``TRACE_SECONDS`` under the JAX profiler and reports the per-layer metrics.

Without a TPU the run exits non-zero and prints no result.  With
``--cpu-rehearsal`` it runs on whatever JAX has (``--train-steps`` can cut
training) and still exits 1: it checks the control flow, not the chip.
"""

from __future__ import annotations

import os
import sys

# One fixed string-hash seed for every run, so that no run's dict and set
# layout differs from another's (the interpreter reads it only at start:
# the process replaces itself once), and single-threaded BLAS for the
# host's small matrix products.
if __name__ == "__main__":
    if os.environ.get("PYTHONHASHSEED") != "0":
        os.environ["PYTHONHASHSEED"] = "0"
        os.execv(sys.executable, [sys.executable] + sys.argv)
    for _var in ("OMP_NUM_THREADS", "OPENBLAS_NUM_THREADS", "MKL_NUM_THREADS"):
        os.environ[_var] = "1"

import time  # noqa: E402

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parent
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(HERE))

import cell  # noqa: E402

TRACE_DIR = ROOT / ".perfbench_trace"
# A traced run's window: a few seconds, as every device op of every descent
# round is an event (about 17 MB of trace a second on one v5e chip).
TRACE_SECONDS = 2.0
COMPILE_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"


def applies(metric: dict, name: str) -> bool:
    return "workloads" not in metric or name in metric["workloads"]


def read_metrics(bench: dict, kind: str, name: str, w) -> dict:
    out = {}
    for m in bench[kind]:
        if not applies(m, name):
            continue
        v = cell.reader(m["name"]).read(w)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out


def compare(w, lim: dict, control: bool = False) -> dict:
    """Each number ``correct`` compares, beside its limit.  ``control`` puts
    the plain reference in three bfloat16 passes in the program's place
    (``control.py``; the benchmark's own runs never do)."""
    import check

    dep = w.dep
    r = check.readings(check.fabric(dep), w.sample.admissions(),
                       dep.mix["contention_mode"], dep.params,
                       dep.contended_params, control=control)
    numbers = {"invalid": check.invalid(w), "off_argmax": r["off_argmax"],
               "score_gap": r["score_gap"]}
    w.extra["n_scores_checked"] = r["n_scores"]
    return {k: {"value": v, "limit": lim[k]} for k, v in numbers.items()}


def is_correct(checked: dict) -> bool:
    return all(c["value"] <= c["limit"] for c in checked.values())


def parse(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--cpu-rehearsal", action="store_true",
                    help="run without a TPU; never reports a result")
    ap.add_argument("--train-steps", type=int, default=None,
                    help="cut training (rehearsal only)")
    ap.add_argument("--keep-trace", default=None,
                    help="copy the traced run's .xplane.pb into this directory")
    args = ap.parse_args(argv)
    if args.train_steps is not None and not args.cpu_rehearsal:
        ap.error("--train-steps is for --cpu-rehearsal only")
    return args


def execute(args: argparse.Namespace):
    """One run: -> (exit code, result or None)."""
    bench = cell.load_json(ROOT / "BENCHMARK.json")
    wl = cell.workload(bench, args.workload)
    lim = cell.limits(wl["name"])

    import jax
    import repro.core as core

    core.use_compile_cache()
    devs = jax.devices()
    dev = devs[0]
    if dev.platform != "tpu" and not args.cpu_rehearsal:
        print(f"no TPU: JAX found {dev.platform}", file=sys.stderr)
        return 2, None
    if len(devs) < wl["chips"]:
        print(f"{wl['name']} needs {wl['chips']} chips, found {len(devs)}",
              file=sys.stderr)
        return 2, None

    n_lowered = [0]

    def on_event(event, duration, **kwargs):
        if event == COMPILE_EVENT:
            n_lowered[0] += 1
    jax.monitoring.register_event_duration_secs_listener(on_event)

    dep = cell.deploy(core, cell.config(wl["config"]), cell.mix(wl["traffic"]),
                      steps=args.train_steps)
    disp = cell.dispatcher(core, dep)
    cell.warm_up(core, dep, disp)

    on_open = None
    if args.trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0

        def on_open():
            jax.profiler.start_trace(str(TRACE_DIR), profiler_options=opts)
    seconds = min(args.seconds, TRACE_SECONDS) if args.trace else args.seconds
    w = cell.run_window(core, dep, disp, args.seed, seconds,
                        compile_count=lambda: n_lowered[0], on_open=on_open)
    if args.trace:
        jax.profiler.stop_trace()
    w.setup_s = w.t_open - T_START
    w.aot_warm_s = disp.aot_warm_seconds
    w.extra["device_kind"] = dev.device_kind
    stats = dev.memory_stats() or {}
    peak = int(stats.get("peak_bytes_in_use", 0))

    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs), "memory_peak_bytes": peak}
    result = {}
    if args.trace:
        import xtrace

        path = xtrace.find(str(TRACE_DIR))
        if args.keep_trace:
            out = pathlib.Path(args.keep_trace)
            out.mkdir(parents=True, exist_ok=True)
            shutil.copy(path, out / f"{wl['name']}.{args.seed}.xplane.pb")
        w.trace = xtrace.reduce(path)
        print(f"trace: idle by host activity {xtrace.idle_by_label(w.trace)}, "
              f"programs {sorted(w.trace['modules'].items(), key=lambda kv: -kv[1])[:4]}",
              file=sys.stderr)
        device["busy_s"] = w.trace["busy_s"]
        device["window_s"] = w.trace["window_s"]
        result["breakdown"] = xtrace.breakdown(w.trace)
        metrics = read_metrics(bench, "per_layer", wl["name"], w)
    else:
        w.gbe = cell.grade(core, w)
        metrics = read_metrics(bench, "end_to_end", wl["name"], w)

    checked = compare(w, lim)
    correct = is_correct(checked)
    lat = sorted(a.admit_s for a in w.admissions)
    print(f"window: {len(w.admissions)} admissions in {w.window_s:.3f} s, "
          f"admit ms p50 {1e3 * statistics.median(lat):.3f} "
          f"max {1e3 * lat[-1]:.3f}, compiles {w.compiles}, "
          f"chunks {w.extra['chunks']}, scores checked "
          f"{w.extra['n_scores_checked']}, "
          f"setup_s {w.setup_s:.3f}, "
          f"train_s {dep.train_s:.3f}, aot_warm_s {w.aot_warm_s:.3f}",
          file=sys.stderr)
    for k, c in checked.items():
        print(f"check {k} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    result = {"correct": correct, "attempted": len(w.admissions),
              "failed": checked["invalid"]["value"], "metrics": metrics,
              "device": device, **result, "check": checked}
    if args.cpu_rehearsal:
        print("rehearsal (no result): " + json.dumps(result), file=sys.stderr)
        return 1, result
    return 0, result


def main(argv=None) -> int:
    rc, result = execute(parse(argv))
    if rc == 0:
        print(json.dumps(result))
    return rc


if __name__ == "__main__":
    sys.exit(main())
