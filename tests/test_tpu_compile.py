"""The dispatcher's device programs compile for one TPU v5e chip.

The chip's compiler runs here against a described ``v5e:2x2`` topology,
with no chip attached: each test lowers one program of the admission path
at the paper's H100 shapes for one chip and compiles it, so a program the
chip's compiler refuses fails here and not on the chip.  Nothing runs, so
these tests say nothing about results or times.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker imports
this file.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

import repro.core as core
from repro.core import features as feat
from repro.core import surrogate as surr
from repro.core import training


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 - any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def h100():
    cl = core.h100_cluster()
    tables = core.IntraHostTables(cl, core.BandwidthSimulator(cl))
    params = surr.init_hierarchical_params(jax.random.PRNGKey(0))
    return cl, tables, params


def _on(sharding, tree):
    """Shapes of ``tree``'s leaves, placed on ``sharding``."""
    return jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=sharding,
            weak_type=getattr(a, "weak_type", False),
        ),
        tree,
    )


@pytest.mark.parametrize("n0b", [8, 16, 32])
def test_pts_scan_compiles(one_chip, h100, n0b):
    """The fused descent, per slot bucket, as ``warm_scan`` builds it."""
    cl, tables, params = h100
    dt = feat.device_tables(cl, tables)
    H = cl.n_hosts
    packed = surr._pack_args(
        np.zeros((n0b,), np.int32), np.ones((n0b,), np.int32),
        np.ones((n0b,), bool), np.zeros((H,), np.int32),
        np.zeros((H,), np.int32), 1,
    )
    assert packed.shape == (3 * n0b + 2 * H + 1,)
    args = surr._scan_args(params, dt, dt.caps_inf(), packed, True)
    exe = surr._pts_scan_jit.lower(*_on(one_chip, args)).compile()
    out = exe.out_info  # one packed result: [score bits | sel | elim,
    #                     active, n_capped] per round
    assert out.shape == (n0b - 1, 2 * n0b + 3) and out.dtype == jnp.int32
    assert exe.memory_analysis().temp_size_in_bytes < 64 << 20


@pytest.mark.parametrize("apply_fn,n_feat", [
    (surr._apply_hierarchical_bw, feat.N_FEATURES),
    (surr._apply_contended_bw, feat.N_FEATURES + feat.N_LEDGER_FEATURES),
], ids=["hierarchical", "contended"])
def test_round_apply_compiles(one_chip, h100, apply_fn, n_feat):
    """A per-round apply at the largest batch bucket of a 32-GPU parent."""
    cl, tables, params = h100
    if apply_fn is surr._apply_contended_bw:
        params = surr.init_contended_params(params)
    B, T = 64, cl.n_hosts
    feats = jax.ShapeDtypeStruct((B, T, n_feat), jnp.float32,
                                 sharding=one_chip)
    mask = jax.ShapeDtypeStruct((B, T), jnp.float32, sharding=one_chip)
    exe = apply_fn.lower(_on(one_chip, params), feats, mask).compile()
    assert exe.out_info.shape == (B,)


def test_train_step_compiles(one_chip, h100):
    """The surrogate's AdamW step over a 250-sample dataset."""
    cl, tables, params = h100
    opt_init, step = training.train_step(
        surr.apply_hierarchical, core.TrainConfig(steps=2000)
    )
    n, T = 250, cl.n_hosts
    args = (
        _on(one_chip, params),
        _on(one_chip, jax.eval_shape(opt_init, params)),
        jax.ShapeDtypeStruct((n, T, feat.N_FEATURES), jnp.float32,
                             sharding=one_chip),
        jax.ShapeDtypeStruct((n, T), jnp.float32, sharding=one_chip),
        jax.ShapeDtypeStruct((n,), jnp.float32, sharding=one_chip),
        jax.ShapeDtypeStruct((64,), jnp.int32, sharding=one_chip),
    )
    exe = step.lower(*args).compile()
    new_params, _, loss = exe.out_info
    assert loss.shape == ()
    assert jax.tree_util.tree_structure(new_params) == \
        jax.tree_util.tree_structure(params)
