"""Sharding-rule unit tests: spec resolution, divisibility fallbacks,
parameter/caches logical mapping.  Uses a fake mesh built over 1 device
repeated via jax.sharding.Mesh abstract construction — resolve_spec only
consults mesh.shape, so a small real mesh suffices."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from repro.parallel import sharding as shd


def _mesh(shape, axes):
    # resolve_spec only needs mesh.shape; an abstract mesh is enough.
    return jax.sharding.AbstractMesh(shape, axes)


M = _mesh((2, 4, 4), ("pod", "data", "model"))
RULES = shd.STRATEGIES["fsdp_tp"]()


def test_resolve_simple():
    spec = shd.resolve_spec(M, RULES, ("embed", "ff"), (64, 128))
    assert spec == P("data", "model")


def test_resolve_divisibility_fallback():
    # kv_heads=1 (MQA) cannot shard 4 ways -> replicated
    spec = shd.resolve_spec(M, RULES, ("embed", "kv_heads", "head_dim"),
                            (64, 1, 128))
    assert spec == P("data", None, None)


def test_resolve_multi_axis_batch():
    spec = shd.resolve_spec(M, RULES, ("batch", "seq"), (16, 128))
    assert spec == P(("pod", "data"), "model")
    # batch=2 can only take the pod axis
    spec2 = shd.resolve_spec(M, RULES, ("batch", "seq"), (2, 128))
    assert spec2 == P("pod", "model")


def test_resolve_no_axis_reuse():
    # two dims mapping to "model": only the first gets it
    spec = shd.resolve_spec(M, RULES, ("heads", "ff"), (8, 128))
    assert spec == P("model", None)


def test_param_logical_stacked_detection():
    # stacked scan leaf gets a leading "layers"=None axis
    log = shd.logical_for_leaf("wq", 4)
    assert log == ("layers", "embed", "heads", "head_dim")
    log2 = shd.logical_for_leaf("wq", 3)
    assert log2 == ("embed", "heads", "head_dim")


def test_moe_leaf_logical():
    assert shd.logical_for_leaf("w_up", 3) == ("experts", "embed", "ff")
    assert shd.logical_for_leaf("w_up", 4) == ("layers", "experts", "embed", "ff")
    assert shd.logical_for_leaf("w_up", 2) == ("embed", "ff")


def test_unknown_leaf_replicates():
    assert shd.logical_for_leaf("mystery", 3) == (None, None, None)


def test_param_specs_tree():
    params = {
        "embed": jax.ShapeDtypeStruct((1024, 64), jnp.float32),
        "blocks": [{
            "wq": jax.ShapeDtypeStruct((6, 64, 8, 16), jnp.float32),
            "norm1": jax.ShapeDtypeStruct((64,), jnp.float32),
        }],
    }
    specs = shd.param_specs(M, RULES, params)
    assert specs["embed"] == P("model", "data")
    assert specs["blocks"][0]["wq"] == P(None, "data", "model", None)
    assert specs["blocks"][0]["norm1"] == P(None)


def test_serve_2d_rules_keep_batch_off_data():
    rules = shd.STRATEGIES["serve_2d"]()
    spec = shd.resolve_spec(M, rules, ("batch", None), (128, 1))
    assert spec == P("pod", None)
    cache_spec = shd.resolve_spec(
        M, rules, ("batch", "seq_cache", "kv_heads", "head_dim"),
        (128, 32768, 8, 128),
    )
    assert cache_spec == P("pod", ("data", "model"), None, None)


def test_activation_constraint_noop_without_context():
    x = jnp.ones((4, 8))
    y = shd.shard_activation(x, "batch", "seq")
    assert y is x
