"""Readings that set the limits of ``correct``: the program and its control.

  python3 perfbench/control.py --workload h100-4x8.fifo-analytic \
      --seeds 101,102,103 --seconds 10

Sets the cell up once, then for each seed builds a fresh dispatcher, runs
the window as ``run.py`` does (``--seconds`` at the cell's own load) and
compares, through ``run.compare``, on the same sampled admissions:

* the program against the plain reference: the lower readings of the
  limits, and ``correct`` as a benchmark run decides it;
* the control, the reference computed in three bfloat16 passes put in the
  program's place: the upper readings, and its ``correct``, which has to
  come out false.

One JSON line per seed on stdout.  The benchmark's own runs never run the
control.  ``--cpu-rehearsal`` and ``--train-steps`` as in ``run.py``.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parent
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(HERE))

import cell  # noqa: E402
import run  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds, one window each")
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--cpu-rehearsal", action="store_true")
    ap.add_argument("--train-steps", type=int, default=None)
    args = ap.parse_args(argv)

    import jax
    import repro.core as core

    core.use_compile_cache()
    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.cpu_rehearsal:
        print(f"no TPU: JAX found {dev.platform}", file=sys.stderr)
        return 2
    wl = cell.workload(cell.load_json(ROOT / "BENCHMARK.json"), args.workload)
    dep = cell.deploy(core, cell.config(wl["config"]), cell.mix(wl["traffic"]),
                      steps=args.train_steps)
    lim = cell.limits(wl["name"])
    for seed in (int(s) for s in args.seeds.split(",")):
        disp = cell.dispatcher(core, dep)
        cell.warm_up(core, dep, disp)
        w = cell.run_window(core, dep, disp, seed, args.seconds)
        prog = run.compare(w, lim)
        ctl = run.compare(w, lim, control=True)
        print(json.dumps({
            "workload": wl["name"], "seed": seed, "platform": dev.platform,
            "admissions": len(w.admissions),
            "n_scores": w.extra["n_scores_checked"],
            "correct": run.is_correct(prog),
            "control_correct": run.is_correct(ctl),
            "program": {k: c["value"] for k, c in prog.items()},
            "control": {k: c["value"] for k, c in ctl.items()}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
