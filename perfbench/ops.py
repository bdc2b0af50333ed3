"""Operations and bytes of the surrogate's device programs, from shapes.

Counts the matrix products of one forward pass of the surrogate Transformer
(embedding, per layer: qkv, scores, weighted values, output projection, the
two feed-forward layers; the pooled head), two operations per multiply-add.
Elementwise work (layer norms, softmax, GELU) is left out, so a roofline
share computed from these counts is a lower bound.
"""

from __future__ import annotations

import json
import math
import pathlib
from typing import Dict, Iterable

import numpy as np


def _w(p) -> tuple:
    return tuple(np.shape(p["w"]))


def forward_flops(params, n_tokens: int) -> int:
    """Operations of one row (one candidate) of ``n_tokens`` tokens."""
    T = n_tokens
    f_in, d = _w(params["embed"])
    ops = 2 * T * f_in * d
    if "ctx_embed" in params:
        ops += 2 * T * np.shape(params["ctx_embed"]["w"])[0] * d
    trunk = params["trunk"]
    for layer in trunk["layers"]:
        ops += 2 * T * math.prod(_w(layer["qkv"]))
        ops += 2 * 2 * T * T * d                       # scores, weighted sum
        ops += 2 * T * math.prod(_w(layer["o"]))
        ops += 2 * T * math.prod(_w(layer["ff1"]))
        ops += 2 * T * math.prod(_w(layer["ff2"]))
    ops += sum(2 * math.prod(_w(h)) for h in trunk["head"])
    return int(ops)


def param_bytes(params) -> int:
    import jax
    return int(sum(np.asarray(x).nbytes for x in jax.tree_util.tree_leaves(params)))


def descent(params, scans: Iterable, n_hosts: int, table_bytes: int) -> Dict:
    """Operations and least bytes of a set of fused descents, each given as
    (active rounds, slots ``N0b``, scores returned).

    Each active round applies the model to all ``N0b`` children, each of
    ``n_hosts`` tokens.  The least traffic is the weights, the lookup
    tables and the returned scores, once."""
    row = forward_flops(params, n_hosts)
    pb = param_bytes(params)
    flops = byts = 0
    for n_rounds, n0b, n_scores in scans:
        flops += n_rounds * n0b * row
        byts += pb + table_bytes + n_scores * 4
    return {"flops": flops, "bytes": byts}


def peaks(device_kind: str) -> Dict[str, float]:
    table = json.loads(
        (pathlib.Path(__file__).resolve().parent / "peaks.json").read_text())
    if device_kind not in table["devices"]:
        raise KeyError(f"no peaks for device {device_kind!r} in peaks.json")
    return table["devices"][device_kind]


def roofline_pct(flops: float, byts: float, seconds: float,
                 device_kind: str) -> float:
    p = peaks(device_kind)
    least = max(flops / p["flops_per_s"], byts / p["bytes_per_s"])
    return 100.0 * least / seconds
