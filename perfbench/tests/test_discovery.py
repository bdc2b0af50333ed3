"""A configuration, a traffic mix and a per-layer metric are picked up from
added files by name, with no edit to a file that is there.

  JAX_PLATFORMS=cpu python -m pytest -q perfbench/tests
"""

from __future__ import annotations

import json
import pathlib
import shutil
import sys

HERE = pathlib.Path(__file__).resolve().parent
BENCH = HERE.parent
sys.path.insert(0, str(BENCH.parent / "src"))
sys.path.insert(0, str(BENCH))

import cell  # noqa: E402


def _added(tmp: pathlib.Path) -> pathlib.Path:
    """A throwaway benchmark tree: copies of the existing generator, plus a
    new configuration, a new mix and a new metric reader."""
    for sub in ("configs", "traffic", "metrics"):
        (tmp / sub).mkdir()
    shutil.copy(BENCH / "traffic" / "poisson.py", tmp / "traffic")
    cfg = cell.config("h100-4x8")
    cfg.update(name="h100-2x8", hosts=[["H100", 2]])
    (tmp / "configs" / "h100-2x8.json").write_text(json.dumps(cfg))
    mix = cell.mix("fifo-analytic")
    mix.update(name="fifo-small", k_min=2, k_max=6, warmup_jobs=20,
               graded_prefix=5, check_sample=4)
    (tmp / "traffic" / "fifo-small.json").write_text(json.dumps(mix))
    (tmp / "metrics" / "mean_k.py").write_text(
        "def read(w):\n"
        "    return sum(a.k for a in w.admissions) / len(w.admissions)\n")
    return tmp


def test_added_files_are_found_by_name(tmp_path):
    base = _added(tmp_path)
    cfg = cell.config("h100-2x8", base)
    mix = cell.mix("fifo-small", base)
    assert cfg["hosts"] == [["H100", 2]] and mix["k_max"] == 6
    assert cell.generator(mix, base).generate.__module__.endswith("poisson")
    assert hasattr(cell.reader("mean_k", base), "read")
    # the shipped files are untouched and still found
    assert cell.config("h100-4x8")["hosts"] == [["H100", 4]]


def test_added_cell_runs(tmp_path, monkeypatch):
    import repro.core as core

    base = _added(tmp_path)
    monkeypatch.setattr(cell, "BENCH", base)
    dep = cell.deploy(core, cell.config("h100-2x8"), cell.mix("fifo-small"),
                      steps=20)
    assert dep.cluster.n_gpus == 16
    disp = cell.dispatcher(core, dep)
    cell.warm_up(core, dep, disp)
    w = cell.run_window(core, dep, disp, seed=2**33 + 5, seconds=1.0)
    assert len(w.admissions) >= 5
    assert all(2 <= a.k <= 6 for a in w.admissions)
    v = cell.reader("mean_k").read(w)
    assert 2 <= v <= 6
    w.gbe = cell.grade(core, w)
    assert len(w.gbe) == 5 and all(0 < g <= 1 + 1e-9 for g in w.gbe)
