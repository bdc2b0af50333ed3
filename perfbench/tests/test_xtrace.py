"""The trace reduction on a small trace recorded on one TPU v5e chip.

``data/h100-4x8.fifo-analytic.xplane.pb.gz``: a traced window of 0.1 s of
``run.py --workload h100-4x8.fifo-analytic --trace 1`` (9 admissions).

  JAX_PLATFORMS=cpu python -m pytest -q perfbench/tests
"""

from __future__ import annotations

import gzip
import pathlib
import sys

import pytest

HERE = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent))

import ops  # noqa: E402
import xtrace  # noqa: E402


@pytest.fixture(scope="module")
def red():
    from jax.profiler import ProfileData

    raw = gzip.decompress(
        (HERE / "data" / "h100-4x8.fifo-analytic.xplane.pb.gz").read_bytes())
    return xtrace.reduce_profile(ProfileData.from_serialized_xspace(raw))


def test_busy_and_gaps_tile_the_window(red):
    assert red["n_devices"] == 1
    assert 0.09 < red["window_s"] < 0.12
    assert 0 < red["busy_s"] < red["window_s"]
    idle = sum(s for _, s in red["gaps"])
    assert idle + red["busy_s"] == pytest.approx(red["window_s"], abs=1e-8)
    assert set(xtrace.idle_by_label(red)) <= {"loop", "dispatch", "commit"}
    assert xtrace.idle_by_label(red)["dispatch"] > 0


def test_programs_and_ops_by_name(red):
    scan = sum(v for k, v in red["modules"].items() if "pts_scan" in k)
    assert 0 < scan <= red["busy_s"]
    assert all(" = " not in k and not k.startswith("%") for k in red["ops"])
    b = xtrace.breakdown(red, top=3)
    assert len(b["device_ops"]) == 3 and len(b["idle_gaps"]) == 3
    assert b["idle_gaps"][0][1] >= b["idle_gaps"][-1][1]


def test_union_and_roofline_arithmetic():
    assert xtrace._union([(5, 9), (0, 3), (2, 4), (9, 10)]) == [(0, 4), (5, 10)]
    with pytest.raises(KeyError):
        ops.peaks("TPU v0")
    # 197e9 operations in one second on a 197 TFLOP/s chip: 0.1 %
    assert ops.roofline_pct(197e9, 0, 1.0, "TPU v5 lite") == pytest.approx(0.1)


@pytest.mark.parametrize("tokens", [4, 12])
def test_counted_operations_against_xla(tokens):
    """The matmul count is a lower bound of XLA's own count of the
    reference forward pass (which adds the elementwise work), and close."""
    import jax
    import jax.numpy as jnp

    sys.path.insert(0, str(HERE.parent.parent / "src"))
    from repro.core import surrogate

    import reference

    params = surrogate.init_hierarchical_params(jax.random.PRNGKey(0))
    lowered = jax.jit(reference.forward, static_argnames=("precision",)).lower(
        params, jnp.zeros((1, tokens, 5)), jnp.ones((1, tokens)),
        precision="highest")
    cost = lowered.compile().cost_analysis()
    xla = (cost[0] if isinstance(cost, list) else cost)["flops"]
    counted = ops.forward_flops(params, tokens)
    assert 0.85 * xla < counted <= xla
