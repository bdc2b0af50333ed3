"""The comparison that decides ``correct``, on the CPU at a small size.

* the program agrees with the plain reference well inside the limits;
* the control (the reference in three bfloat16 passes), put in the
  program's place through ``run.compare``, reads far above the program on
  the same admissions and comes out with ``correct`` false;
* the reference's fabric, built from the configuration file alone, agrees
  with the deployment's simulator on every intra-host subset;
* the window keeps search records for the sampled admissions only;
* a run whose timed path is broken underneath (an answer altered where the
  search produces it; scores altered where the model produces them) comes
  out with ``correct`` false, through the rest of ``run.py``.

  JAX_PLATFORMS=cpu python -m pytest -q perfbench/tests
"""

from __future__ import annotations

import dataclasses
import pathlib
import sys

import numpy as np
import pytest

HERE = pathlib.Path(__file__).resolve().parent
BENCH = HERE.parent
sys.path.insert(0, str(BENCH.parent / "src"))
sys.path.insert(0, str(BENCH))

import cell  # noqa: E402
import reference  # noqa: E402
import run  # noqa: E402

CELL = "h100-4x8.fifo-analytic"
ARGS = ["--workload", CELL, "--seed", "4294967311", "--seconds", "24",
        "--cpu-rehearsal", "--train-steps", "30"]


@pytest.fixture(scope="module")
def window():
    import repro.core as core

    wl = cell.workload(cell.load_json(BENCH.parent / "BENCHMARK.json"), CELL)
    dep = cell.deploy(core, cell.config(wl["config"]),
                      cell.mix(wl["traffic"]), steps=30)
    disp = cell.dispatcher(core, dep)
    cell.warm_up(core, dep, disp)
    return cell.run_window(core, dep, disp, seed=17, seconds=2.0)


def test_program_inside_limits_control_outside(window):
    lim = cell.limits(CELL)
    prog = run.compare(window, lim)
    ctl = run.compare(window, lim, control=True)
    assert run.is_correct(prog) and not run.is_correct(ctl)
    assert prog["invalid"]["value"] == 0 and prog["off_argmax"]["value"] == 0
    assert prog["score_gap"]["value"] < lim["score_gap"] / 3
    assert ctl["score_gap"]["value"] > lim["score_gap"]
    assert ctl["score_gap"]["value"] > 3 * prog["score_gap"]["value"]


def test_only_sampled_admissions_keep_records(window):
    kept = window.sample.admissions()
    n = window.dep.mix["check_sample"]
    assert n <= len(kept) <= n + 1
    assert all(a.search is not None and a.snapshot is not None for a in kept)
    ids = {a.index for a in kept}
    assert all(a.search is None for a in window.admissions
               if a.index not in ids)
    assert window.sample.widest.index in ids


@pytest.mark.parametrize("name", ["h100-4x8", "het4mix-4x8"])
def test_reference_fabric_matches_deployment(name):
    """The reference's intra-host bandwidths and rails come from the
    configuration file, not from the program; they agree with the
    simulator that stands in for the cluster on every subset of every
    host."""
    import itertools

    import repro.core as core

    cfg = cell.config(name)
    fab = reference.Fabric.from_config(cfg)
    cl = core.Cluster([tuple(h) for h in cfg["hosts"]],
                      name=cfg["cluster_name"])
    sim = core.BandwidthSimulator(cl)
    assert fab.gpu_host == list(cl.gpu_host)
    assert fab.rail_bw == [h.host_type.nic_rail_bw for h in cl.hosts]
    for h in cl.hosts:
        for n in range(1, len(h.gpu_ids) + 1):
            for local in itertools.combinations(range(len(h.gpu_ids)), n):
                gpus = [h.gpu_ids[i] for i in local]
                assert fab.intra(h.host_id, gpus) == pytest.approx(
                    sim.intra_bandwidth(h.host_id, local), rel=1e-12)


def _rehearse(capsys):
    rc, result = run.execute(run.parse(ARGS))
    assert rc == 1   # a rehearsal never reports a result
    return result


def test_sound_run_is_correct(capsys):
    assert _rehearse(capsys)["correct"] is True


def test_altered_answer_is_caught(monkeypatch, capsys):
    from repro.core import search

    inner = search.hybrid_search

    def altered(cluster, tables, predictor, avail, k, **kw):
        res = inner(cluster, tables, predictor, avail, k, **kw)
        spare = sorted(set(avail) - set(res.subset))
        if spare:   # one GPU of the answer swapped for another free one
            res = dataclasses.replace(
                res, subset=sorted(res.subset[1:] + [spare[0]]))
        return res

    monkeypatch.setattr(search, "hybrid_search", altered)
    result = _rehearse(capsys)
    assert result["correct"] is False
    assert result["check"]["score_gap"]["value"] > \
        result["check"]["score_gap"]["limit"]


def test_altered_scores_are_caught(monkeypatch, capsys):
    import repro.core as core

    inner = core.SurrogatePredictor.predict

    def scaled(self, subsets):
        return np.asarray(inner(self, subsets)) * 1.001

    monkeypatch.setattr(core.SurrogatePredictor, "predict", scaled)
    result = _rehearse(capsys)
    assert result["correct"] is False
