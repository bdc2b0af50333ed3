"""The program's spans in a profile: collection, coverage and gap labels.

* a traced window of the analytic cell, recorded on the CPU at a small
  size, holds every program span of the fused descent; their totals nest
  (the descent's phases inside ``descent``), the new metric readers read
  them, and nothing in an untraced run;
* the TPU trace in ``data/`` (recorded before the program opened spans of
  its own) yields no program span, the readers report nothing, and its
  idle gaps keep the harness's labels;
* the gap label names the innermost program span over the gap.

  JAX_PLATFORMS=cpu python -m pytest -q perfbench/tests
"""

from __future__ import annotations

import gzip
import pathlib
import sys
import types

import pytest

HERE = pathlib.Path(__file__).resolve().parent
BENCH = HERE.parent
sys.path.insert(0, str(BENCH.parent / "src"))
sys.path.insert(0, str(BENCH))

import cell  # noqa: E402
import spans  # noqa: E402
import xtrace  # noqa: E402

CELL = "h100-4x8.fifo-analytic"
NEW = ("descent_upload_ms", "descent_launch_ms", "descent_sync_ms",
       "cap_table_ms", "untraced_ms")
PHASES = ("descent.prep", "descent.upload", "descent.launch", "descent.sync")


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """(window, trace directory) of a 1.5 s window under the profiler."""
    import jax
    import repro.core as core

    log_dir = tmp_path_factory.mktemp("trace")
    wl = cell.workload(cell.load_json(BENCH.parent / "BENCHMARK.json"), CELL)
    dep = cell.deploy(core, cell.config(wl["config"]),
                      cell.mix(wl["traffic"]), steps=30)
    disp = cell.dispatcher(core, dep)
    cell.warm_up(core, dep, disp)
    w = cell.run_window(core, dep, disp, seed=2**33 + 7, seconds=1.5,
                        on_open=lambda: jax.profiler.start_trace(str(log_dir)))
    jax.profiler.stop_trace()
    return w, log_dir


def test_program_spans_collected_in_the_window(traced):
    from jax.profiler import ProfileData

    w, log_dir = traced
    got = spans.collect(ProfileData.from_file(xtrace.find(str(log_dir))))
    tot, n = got["totals"], got["counts"]
    for name in ("sched.admit", "dispatcher.dispatch", "search.pts",
                 "descent", *PHASES, "cap.table", "cap.degrade",
                 "featurize", "apply", "ledger.admit", "ledger.release"):
        assert tot.get(name, 0) > 0, name
    assert n["dispatcher.dispatch"] == len(w.admissions)
    assert n["descent"] == len(w.scans)
    assert all(n[p] == n["descent"] for p in PHASES)
    assert sum(tot[p] for p in PHASES) <= tot["descent"]
    assert tot["descent"] < tot["search.pts"] < tot["dispatcher.dispatch"]
    assert 0 <= got["untraced_s"] < got["admit_s"]
    # the profiler's descent covers what the program's own counter times
    assert tot["descent"] == pytest.approx(w.stats.scan_seconds, rel=0.25)
    assert got["gaps"] == []    # the CPU profile has no device plane


def test_new_readers_traced_and_untraced(traced, monkeypatch):
    w, log_dir = traced
    monkeypatch.setattr(spans, "TRACE_DIR", log_dir)
    untraced = types.SimpleNamespace(trace=None, admissions=w.admissions)
    w.trace = {}            # what run.py's reduction leaves in a traced run
    try:
        vals = {m: cell.reader(m).read(w) for m in NEW}
        assert all(cell.reader(m).read(untraced) is None for m in NEW)
    finally:
        w.trace = None
    assert all(v is not None and v >= 0 for v in vals.values()), vals
    assert vals["descent_sync_ms"] > 0 and vals["cap_table_ms"] > 0
    per_adm = 1e3 * w.admit_s / len(w.admissions)
    assert vals["untraced_ms"] < per_adm


@pytest.fixture(scope="module")
def recorded():
    from jax.profiler import ProfileData

    raw = gzip.decompress(
        (HERE / "data" / "h100-4x8.fifo-analytic.xplane.pb.gz").read_bytes())
    return ProfileData.from_serialized_xspace(raw)


def test_trace_without_program_spans(recorded, tmp_path, monkeypatch):
    got = spans.collect(recorded)
    assert got["totals"] == {} and got["counts"] == {}
    red = xtrace.reduce_profile(recorded)
    labels = xtrace.idle_by_label(got)
    assert labels == pytest.approx(xtrace.idle_by_label(red))
    assert set(labels) <= {"loop", "dispatch", "commit"}
    # the readers then report nothing
    path = tmp_path / "plugins" / "profile" / "run"
    path.mkdir(parents=True)
    (path / "h.xplane.pb").write_bytes(gzip.decompress(
        (HERE / "data" / "h100-4x8.fifo-analytic.xplane.pb.gz").read_bytes()))
    monkeypatch.setattr(spans, "TRACE_DIR", tmp_path)
    w = types.SimpleNamespace(trace={}, admissions=[None] * 9)
    assert all(cell.reader(m).read(w) is None for m in NEW)


def test_gap_label_names_innermost_span():
    harness = {"bench.admit": [(0, 100)], "bench.dispatch": [(10, 90)]}
    program = {"dispatcher.dispatch": [(12, 88)], "descent": [(20, 60)],
               "descent.sync": [(40, 59)], "py.gc": [(45, 50)],
               "ledger.admit": [(91, 99)]}
    assert spans.label(harness, program, 30) == "dispatch/descent"
    assert spans.label(harness, program, 41) == "dispatch/descent.sync"
    assert spans.label(harness, program, 47) == "dispatch/py.gc"
    assert spans.label(harness, program, 89) == "dispatch"
    assert spans.label(harness, program, 95) == "commit/ledger.admit"
    assert spans.label(harness, program, 150) == "loop"
    assert spans._intersect([(0, 10), (20, 30)], [(5, 25)]) == [(5, 10),
                                                                (20, 25)]
