"""Bandwidth surrogate models in pure JAX (Sec. 4.2).

Three models share one Transformer-encoder trunk:

* **HierarchicalSurrogate** (the paper's design): tokens are per-host feature
  tuples (Stage-1 intra-host bandwidth lookup, GPU count); a 6-layer,
  d_model=32 encoder with a 3-layer MLP head predicts normalized end-to-end
  bandwidth.  ~89k params ~= 356 KB fp32, matching the paper's "354 KB".
* **NaiveSurrogate** (ablation baseline, Sec. 5.5.1): tokens are raw GPU
  identifiers passed through a learned embedding; the model must infer the
  physical hierarchy from scratch.
* **ContendedSurrogate** (the learned-contention head): the same encoder
  trunk, warm-started from the isolated surrogate, plus a zero-initialized
  *context embedding* over the ledger channels of
  :func:`repro.core.features.featurize_contended_batch`.  At init it is
  exactly the isolated model on any zero-context input; training on a
  curriculum of (subset, ledger, contended-bw) triples teaches it the rail
  split the analytic estimator only approximates.

Everything is written against plain parameter pytrees (dicts) so the model
is trivially checkpointable and shardable with the rest of the framework.
"""

from __future__ import annotations

import dataclasses
import functools
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.core import features as feat_lib
from repro.core import telemetry
from repro.core.bandwidth_sim import BW_SCALE
from repro.core.cluster import Cluster
from repro.core.intra_host import IntraHostTables
from repro.core.predict_cache import PredictorStats, active_batcher

PyTree = Any

D_MODEL = 32
N_LAYERS = 6
N_HEADS = 4
D_FF = 128
HEAD_HIDDEN = 64

# The model regresses log-bandwidth: collective bandwidths span ~2.5 orders
# of magnitude across heterogeneous clusters, and the paper's accuracy
# metric (MAPE) is a *relative* error — log-space MSE optimizes it directly.
LOG_SCALE = 5.0


def encode_bw(bw_gbps):
    """GB/s -> normalized log-space target."""
    return jnp.log1p(jnp.asarray(bw_gbps)) / LOG_SCALE


def decode_bw(y):
    """normalized log-space prediction -> GB/s."""
    return jnp.expm1(jnp.clip(y, 0.0, 2.0) * LOG_SCALE)


# ---------------------------------------------------------------------------
# Parameter init
# ---------------------------------------------------------------------------

def _dense_init(key, d_in, d_out, scale=None):
    if scale is None:
        scale = 1.0 / np.sqrt(d_in)
    w = jax.random.normal(key, (d_in, d_out), jnp.float32) * scale
    return {"w": w, "b": jnp.zeros((d_out,), jnp.float32)}


def _layer_init(key, d=D_MODEL, d_ff=D_FF):
    ks = jax.random.split(key, 6)
    return {
        "ln1": {"g": jnp.ones((d,)), "b": jnp.zeros((d,))},
        "qkv": _dense_init(ks[0], d, 3 * d),
        "o": _dense_init(ks[1], d, d),
        "ln2": {"g": jnp.ones((d,)), "b": jnp.zeros((d,))},
        "ff1": _dense_init(ks[2], d, d_ff),
        "ff2": _dense_init(ks[3], d_ff, d),
    }


def _trunk_init(key, d=D_MODEL, n_layers=N_LAYERS):
    ks = jax.random.split(key, n_layers + 2)
    head_keys = jax.random.split(ks[-1], 3)
    return {
        "layers": [_layer_init(ks[i], d) for i in range(n_layers)],
        "ln_f": {"g": jnp.ones((d,)), "b": jnp.zeros((d,))},
        "head": [
            _dense_init(head_keys[0], d, HEAD_HIDDEN),
            _dense_init(head_keys[1], HEAD_HIDDEN, HEAD_HIDDEN),
            _dense_init(head_keys[2], HEAD_HIDDEN, 1),
        ],
    }


def init_hierarchical_params(key) -> PyTree:
    k_embed, k_trunk = jax.random.split(key)
    embed = _dense_init(k_embed, feat_lib.N_FEATURES, D_MODEL, scale=1.0)
    # The per-host-type normalized channel (features.py channel 4) starts
    # inert: a zero embed row means an un-trained (or legacy-trained) model
    # is bit-for-bit unaffected by it; training opts in where it helps.
    embed["w"] = embed["w"].at[feat_lib.N_FEATURES - 1].set(0.0)
    return {
        "embed": embed,
        "trunk": _trunk_init(k_trunk),
    }


def init_naive_params(key, n_gpus: int) -> PyTree:
    k_embed, k_trunk = jax.random.split(key)
    return {
        "id_embed": jax.random.normal(k_embed, (n_gpus, D_MODEL)) * 0.1,
        "trunk": _trunk_init(k_trunk),
    }


def init_contended_params(base_params: PyTree) -> PyTree:
    """ContendedSurrogate init: the isolated trunk + embed (warm start) plus
    a ZERO context embedding — so at init the contended model computes
    exactly the isolated prediction wherever the ledger channels are zero.
    Deterministic (no rng): all the randomness came from the base params."""
    copied = jax.tree_util.tree_map(jnp.array, base_params)
    return {
        "embed": copied["embed"],
        "ctx_embed": {
            "w": jnp.zeros((feat_lib.N_LEDGER_FEATURES, D_MODEL), jnp.float32)
        },
        "trunk": copied["trunk"],
    }


def param_count(params: PyTree) -> int:
    return sum(int(np.prod(p.shape)) for p in jax.tree_util.tree_leaves(params))


def param_bytes(params: PyTree) -> int:
    return sum(
        int(np.prod(p.shape)) * p.dtype.itemsize
        for p in jax.tree_util.tree_leaves(params)
    )


# ---------------------------------------------------------------------------
# Forward pass
# ---------------------------------------------------------------------------

# Every matmul of the surrogate runs at full float32 precision.  A TPU at
# default precision rounds matmul inputs to bfloat16; measured on a v5e,
# that moved the fused descent 6.6e-3 away from the host loop (the
# agreement contract below allows 1e-4), and cost 0.07 points of H100 MAPE
# and 0.8 points of Het-4Mix GBE.  On XLA-CPU the setting changes nothing.
_PRECISION = lax.Precision.HIGHEST


def _dense(p, x):
    return jnp.matmul(x, p["w"], precision=_PRECISION) + p["b"]


def _layernorm(p, x, eps=1e-6):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.var(x, axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * p["g"] + p["b"]


def _mha(p, x, mask):
    """Masked multi-head self-attention.  x: [B,H,D], mask: [B,H]."""
    B, H, D = x.shape
    dh = D // N_HEADS
    qkv = _dense(p["qkv"], x)  # [B,H,3D]
    q, k, v = jnp.split(qkv, 3, axis=-1)
    q = q.reshape(B, H, N_HEADS, dh).transpose(0, 2, 1, 3)
    k = k.reshape(B, H, N_HEADS, dh).transpose(0, 2, 1, 3)
    v = v.reshape(B, H, N_HEADS, dh).transpose(0, 2, 1, 3)
    scores = jnp.einsum("bnid,bnjd->bnij", q, k,
                        precision=_PRECISION) / np.sqrt(dh)
    neg = jnp.finfo(scores.dtype).min
    scores = jnp.where(mask[:, None, None, :] > 0, scores, neg)
    att = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bnij,bnjd->bnid", att, v, precision=_PRECISION)
    out = out.transpose(0, 2, 1, 3).reshape(B, H, D)
    return _dense(p["o"], out)


def _encoder(trunk: PyTree, x: jnp.ndarray, mask: jnp.ndarray) -> jnp.ndarray:
    """Pre-LN Transformer encoder + masked mean-pool + MLP head -> [B]."""
    for layer in trunk["layers"]:
        x = x + _mha(layer, _layernorm(layer["ln1"], x), mask)
        h = _layernorm(layer["ln2"], x)
        h = _dense(layer["ff2"], jax.nn.gelu(_dense(layer["ff1"], h)))
        x = x + h
    x = _layernorm(trunk["ln_f"], x)
    denom = jnp.maximum(jnp.sum(mask, axis=-1, keepdims=True), 1.0)
    pooled = jnp.sum(x * mask[..., None], axis=1) / denom  # [B, D]
    h = jax.nn.gelu(_dense(trunk["head"][0], pooled))
    h = jax.nn.gelu(_dense(trunk["head"][1], h))
    return _dense(trunk["head"][2], h)[..., 0]


def apply_hierarchical(params: PyTree, feats: jnp.ndarray, mask: jnp.ndarray):
    """feats: [B, H, F], mask: [B, H] -> normalized bandwidth [B]."""
    x = _dense(params["embed"], feats)
    return _encoder(params["trunk"], x, mask)


def apply_naive(params: PyTree, ids: jnp.ndarray, mask: jnp.ndarray):
    """ids: [B, K] int32 GPU identifiers, mask: [B, K] -> normalized bw [B]."""
    x = params["id_embed"][ids]
    return _encoder(params["trunk"], x, mask)


# Module-level jitted apply+decode functions, SHARED by every predictor
# instance: jax's compilation cache is keyed on the function object, so a
# per-predictor ``jax.jit(...)`` closure would re-trace and re-compile every
# (B, H) shape bucket for every fresh predictor — benchmarks and scratch
# searches build many.  decode_bw is fused in (elementwise, bit-identical)
# so each call costs exactly one dispatch + one sync.

@jax.jit
def _apply_hierarchical_bw(params, feats, mask):
    return decode_bw(apply_hierarchical(params, feats, mask))


@jax.jit
def _apply_naive_bw(params, ids, mask):
    return decode_bw(apply_naive(params, ids, mask))


@jax.jit
def _apply_contended_bw(params, feats, mask):
    return decode_bw(apply_contended(params, feats, mask))


def apply_contended(params: PyTree, feats: jnp.ndarray, mask: jnp.ndarray):
    """feats: [B, T, N_CONTENDED_FEATURES], mask: [B, T] -> normalized bw [B].

    The ledger channels enter through a bias-free context embedding added to
    the base-token embedding; with an all-zero context the forward pass is
    the isolated :func:`apply_hierarchical` of the embedded trunk."""
    base = feats[..., : feat_lib.N_FEATURES]
    ctx = feats[..., feat_lib.N_FEATURES:]
    x = _dense(params["embed"], base) + jnp.matmul(
        ctx, params["ctx_embed"]["w"], precision=_PRECISION
    )
    return _encoder(params["trunk"], x, mask)


# ---------------------------------------------------------------------------
# Predictor: the deployable surrogate B̂(S)
# ---------------------------------------------------------------------------

def _round_up_pow2(n: int) -> int:
    p = 1
    while p < n:
        p *= 2
    return p


# ---------------------------------------------------------------------------
# Fused on-device elimination scan: the whole PTS descent as ONE device call
# ---------------------------------------------------------------------------
#
# The host PTS loop pays one featurize + one jitted apply + one host<->device
# round-trip per elimination round.  The scan below moves the entire descent
# |S0| -> k into a single XLA program: a ``lax.scan`` whose body re-expresses
# the per-round child patching of ``features.featurize_children`` as pure
# gathers over precomputed per-(host, bitmask) tables
# (:class:`features.DeviceTables`), dispatches Stage-1 single-host children
# to an exact table lookup, runs the Stage-2 Transformer apply on the rest,
# applies the (tabulated) analytic contention cap, and takes the per-round
# argmax — so one device call replaces |S0|-k applies.  This is the
# ``predict_children_scan`` of the ISSUE, surfaced as
# ``SurrogatePredictor.eliminate_to`` (a whole descent, not one round).
#
# Agreement contract with the host loop.  The feature channels agree
# exactly: the tables are the host's float64 programs cast to float32 once,
# the small-integer ratio channels are exactly representable, and min is
# monotone under the cast.  The model apply does not: the scan body and the
# standalone jitted apply are different XLA programs over different batch
# shapes, so their float32 scores differ in the low bits (about 2e-6
# relative, on XLA-CPU and on a TPU v5e at the surrogate's full float32
# matmul precision).  So the contract, checked round by round by
# :func:`audit_descent` in the tests and in ``chip_smoke.py``, is:
#
# * every live child's scan score is within ``SCORE_RTOL`` (relative)
#   of the host loop's score for the same child;
# * every round eliminates the host loop's argmax, except at a near-tie: a
#   round where the host's best score and the score of the child the scan
#   took are within ``2 * SCORE_RTOL`` of each other.  From such a
#   round on, the two descents may keep different survivors.
#
# The same bound holds wherever one batch is scored by two programs of
# different shapes: the cross-search batcher pads other searches' rows in.
# ``pts_search`` keeps the host loop for every configuration the scan
# declines (outside the envelope below).

SCORE_RTOL = 1e-4

SCAN_MIN_SLOTS = 8    # slot-bucket floor: descent buckets are {8, 16, 32, 64}
SCAN_MAX_SLOTS = 64   # largest parent the scan path accepts
_SCAN_MAX_HOST_GPUS = 16   # gather tables are [H, 2**max_g]: bound them
_SCAN_MAX_LATTICE = 1 << 16  # cap-table bound (paper clusters: 9**4 = 6561)


@dataclasses.dataclass
class ScanResult:
    """One whole on-device elimination descent ``|S0| -> k``.

    ``scores``/``sels``/``elims`` expose every round's internal state so the
    audit tests can compare each round against the host loop; ``sels[r]``
    marks the slots still live *entering* round ``r`` (slot i = the i-th
    element of the sorted parent), and ``scores[r]`` holds the f32 child
    scores at those slots (padding / eliminated slots carry mirror-parent
    garbage and are never selected).  ``bw`` is the score the last round
    gave ``subset`` (its chosen child): capped by the same table, in GB/s,
    and held to ``SCORE_RTOL`` of the standalone apply like every round."""

    subset: List[int]          # the surviving k GPUs, ascending
    bw: float                  # its score: the last round's chosen child
    n_rounds: int              # active elimination rounds (= |S0| - k)
    n_capped: int              # live children whose cap bound (f32 compare)
    scores: np.ndarray         # [R, N0b] float32 per-round child scores
    sels: np.ndarray           # [R, N0b] bool pre-round live slots
    elims: np.ndarray          # [R] int32 slot eliminated per round


@dataclasses.dataclass
class DescentAudit:
    """A fused descent held to the agreement contract (see above)."""

    max_rel_gap: float         # largest |scan - host| / |host| of any round
    near_ties: int             # rounds that took a near-tie over the argmax
    violations: List[str]      # empty when the contract holds


def audit_descent(predictor, res: ScanResult, parent: Sequence[int],
                  k: int) -> DescentAudit:
    """Replay a fused descent round by round on the host path.

    Each round scores every remove-one child of the scan's own current
    parent with ``predictor.predict`` (one jitted apply per round) and
    compares: live slots, per-child score gap, and the eliminated child
    against the host argmax.  The replay follows the scan's eliminations,
    so a near-tie does not end the audit."""
    parent = sorted(parent)
    s = list(parent)
    bad: List[str] = []
    gap, ties = 0.0, 0
    if res.n_rounds != len(parent) - k:
        bad.append(f"{res.n_rounds} rounds for {len(parent)} -> {k}")
    for r in range(res.n_rounds):
        live = np.nonzero(res.sels[r])[0]
        if [parent[i] for i in live] != s:
            bad.append(f"round {r}: live slots left the descent")
            break
        host = np.asarray(
            predictor.predict([s[:i] + s[i + 1:] for i in range(len(s))]),
            np.float64,
        )
        dev = res.scores[r][live].astype(np.float64)
        rel = np.abs(dev - host) / np.abs(host)
        gap = max(gap, float(rel.max()))
        j = int(np.flatnonzero(live == res.elims[r])[0])
        best = float(host.max())
        if host[j] < best:
            if best - host[j] > 2 * SCORE_RTOL * abs(best):
                bad.append(f"round {r}: took {host[j]!r}, host best {best!r}")
            ties += 1
        s.pop(j)
    if gap > SCORE_RTOL:
        bad.append(f"score gap {gap:.3g} > {SCORE_RTOL:g}")
    if not bad and res.subset != s:
        bad.append(f"survivors {res.subset} != replay {s}")
    return DescentAudit(max_rel_gap=gap, near_ties=ties, violations=bad)


def _descent_rounds(params, tok0, tok4, stage1, cap_tab, strides, slot_host,
                    slot_bit, sel0, bits0, counts0, k, n_gpus_f):
    """The descent's rounds -> per-round (scores, sels, elims, actives,
    n_capped), each stacked over ``N0b - 1`` rounds.

    Fixed trip count ``N0b - 1`` with a ``lax.cond`` gate: rounds after the
    descent reaches ``k`` are no-ops (carry passes through unchanged).
    """
    N0b = slot_host.shape[0]
    H = bits0.shape[0]
    harange = jnp.arange(H, dtype=jnp.int32)
    # per-slot one-hot host row / local bit, for child patching + elimination
    host_oh = (slot_host[:, None] == harange[None, :]).astype(jnp.int32)
    sub_bits = host_oh * slot_bit[:, None]
    slot_idx = jnp.arange(N0b)

    def do_round(carry):
        sel, bits, counts, n = carry
        # child i = parent minus slot i.  Eliminated/padded slots mirror the
        # parent itself (valid tokens, no NaN enters the model) and are
        # excluded from the argmax below.
        bits_c = jnp.where(sel[:, None], bits[None, :] - sub_bits,
                           bits[None, :])
        counts_c = jnp.where(sel[:, None], counts[None, :] - host_oh,
                             counts[None, :])
        part = counts_c > 0
        kc = (n - 1).astype(jnp.float32)
        cf = counts_c.astype(jnp.float32)
        # the five isolated channels of features._isolated_channels, as
        # where-gated gathers (never multiply-by-mask: NaN-safe)
        ch0 = jnp.where(part, tok0[harange[None, :], bits_c], 0.0)
        ch1 = jnp.where(part, cf / 8.0, 0.0)
        ch2 = jnp.where(part, cf / kc, 0.0)
        ch3 = jnp.where(part, kc / n_gpus_f, 0.0)
        ch4 = jnp.where(part, tok4[harange[None, :], bits_c], 0.0)
        feats = jnp.stack([ch0, ch1, ch2, ch3, ch4], axis=-1)
        # pack participating tokens into the leading slots, hosts ascending
        # (the order features._pack_tokens scatters into)
        order = jnp.argsort(
            jnp.logical_not(part).astype(jnp.int32), axis=1, stable=True
        )
        feats_p = jnp.take_along_axis(feats, order[..., None], axis=1)
        mask = jnp.take_along_axis(part, order, axis=1).astype(jnp.float32)
        bw = decode_bw(apply_hierarchical(params, feats_p, mask))
        # Stage-1 dispatch: single-host children read the exact lookup
        h_star = jnp.argmax(part, axis=1)
        s1 = stage1[h_star, bits_c[slot_idx, h_star]]
        n_part = part.sum(axis=1)
        iso = jnp.where(n_part == 1, s1, bw)
        # analytic contention cap: one gather on the count-vector lattice
        cap = cap_tab[(counts_c * strides[None, :]).sum(axis=1)]
        score = jnp.minimum(iso, cap)
        n_capped = ((cap < iso) & sel).sum().astype(jnp.int32)
        elim = jnp.argmax(jnp.where(sel, score, -jnp.inf))
        oh = (harange == slot_host[elim]).astype(jnp.int32)
        new_carry = (
            sel.at[elim].set(False),
            bits - oh * slot_bit[elim],
            counts - oh,
            n - 1,
        )
        return new_carry, (score, sel, elim.astype(jnp.int32),
                           jnp.bool_(True), n_capped)

    def skip_round(carry):
        ys = (
            jnp.zeros((N0b,), jnp.float32),
            jnp.zeros((N0b,), bool),
            jnp.int32(0),
            jnp.bool_(False),
            jnp.int32(0),
        )
        return carry, ys

    def body(carry, _):
        return lax.cond(carry[3] > k, do_round, skip_round, carry)

    carry0 = (sel0, bits0, counts0, sel0.sum().astype(jnp.int32))
    _, ys = lax.scan(body, carry0, None, length=N0b - 1)
    return ys


# One descent moves one packed int32 vector each way.  The argument vector
# is [slot_host | slot_bit | sel0 | bits0 | counts0 | k], of length
# 3*N0b + 2*H + 1; the result is one [N0b - 1, 2*N0b + 3] row per round:
# [score bits | sel | elim | active | n_capped].  Scores travel as their
# float32 bit patterns, so the packing is exact.

def _pack_args(slot_host, slot_bit, sel0, bits0, counts0, k) -> np.ndarray:
    """The per-descent arguments as the one int32 vector ``_pts_scan``
    takes."""
    return np.concatenate(
        [slot_host, slot_bit, sel0, bits0, counts0, [k]]
    ).astype(np.int32)


def _pts_scan(params, tok0, tok4, stage1, cap_tab, strides, n_gpus_f,
              packed):
    """The fused descent: traced once per (N0b, H, W, L) shape bucket.

    All tables and scalars are runtime arguments, so one compiled
    executable serves every cluster/ledger/k sharing the bucket shapes.
    The offsets into ``packed`` follow from its length and ``H``.
    """
    H = tok0.shape[0]
    N0b = (packed.shape[0] - 2 * H - 1) // 3
    o = 3 * N0b
    scores, sels, elims, actives, capped = _descent_rounds(
        params, tok0, tok4, stage1, cap_tab, strides,
        packed[:N0b], packed[N0b:2 * N0b], packed[2 * N0b:o] != 0,
        packed[o:o + H], packed[o + H:o + 2 * H], packed[-1], n_gpus_f,
    )
    return jnp.concatenate([
        lax.bitcast_convert_type(scores, jnp.int32),
        sels.astype(jnp.int32),
        elims[:, None],
        actives.astype(jnp.int32)[:, None],
        capped[:, None],
    ], axis=1)


def _unpack_result(out: np.ndarray, n0b: int):
    """``_pts_scan``'s packed rows -> (scores f32, sels bool, elims i32,
    actives bool, n_capped i32), each ``[N0b - 1, ...]``."""
    return (
        np.ascontiguousarray(out[:, :n0b]).view(np.float32),
        out[:, n0b:2 * n0b] != 0,
        out[:, 2 * n0b],
        out[:, 2 * n0b + 1] != 0,
        out[:, 2 * n0b + 2],
    )


# (N0b, H_all, 2**max_g, lattice_size) -> AOT-compiled executable.  Tables
# and scalars are runtime args, so e.g. H100 and Het-4Mix (both 4x8) share
# every bucket's executable — and so do every ledger state and every k.
_SCAN_COMPILED: Dict[Tuple[int, int, int, int], Any] = {}

_pts_scan_jit = jax.jit(_pts_scan)


def _scan_args(params, dt, cap_tab, packed, host_norm):
    """Build a descent's argument tuple — ONE code path used at both AOT
    lower time and call time, so avals (shape/dtype/weak_type) always match
    the compiled executable's signature.  The cluster's tables are its
    resident device copies; ``cap_tab`` is uploaded only when it comes as
    numpy, and ``packed`` (:func:`_pack_args`) always is: one transfer."""
    res = dt.resident()
    return (
        params,
        res.tok0,
        res.tok4 if host_norm else res.tok4_zero,
        res.stage1,
        jnp.asarray(cap_tab),
        res.strides,
        res.n_gpus_f,
        jnp.asarray(packed),
    )


def _compiled_scan(key: Tuple[int, int, int, int], args):
    """Fetch (or AOT lower+compile) the executable for one shape bucket."""
    exe = _SCAN_COMPILED.get(key)
    if exe is None:
        exe = _pts_scan_jit.lower(*args).compile()
        _SCAN_COMPILED[key] = exe
    return exe


class SurrogatePredictor:
    """Deployable B̂(S): Stage-1 exact lookup for single-host allocations,
    Stage-2 Transformer for multi-host ones (Fig. 4).

    Batched evaluation pads the batch to a power of two so the jitted apply
    function compiles only O(log B_max) times; with ``bucket_shapes`` (the
    default) the *token* dimension is likewise bucketed to the power-of-two
    cover of the batch's max participating-host count instead of always
    ``cluster.n_hosts`` — padded tokens are exactly masked out, so the
    pinned trace goldens select identical subsets (``tests/test_fast_path``).
    ``vectorized=False`` falls back to the legacy per-candidate loop
    featurizer (the throughput bench's before-side).

    ``eliminate_to`` runs a whole PTS elimination descent as one fused
    on-device ``lax.scan`` (``use_scan=False`` disables it — the scan-off
    side of the throughput bench and the trace goldens); ``warm_scan``
    AOT-compiles the descent executables ahead of the first admission.
    """

    def __init__(
        self,
        cluster: Cluster,
        tables: IntraHostTables,
        params: PyTree,
        naive: bool = False,
        max_k: Optional[int] = None,
        host_norm: bool = True,
        vectorized: bool = True,
        bucket_shapes: bool = True,
        use_scan: bool = True,
    ):
        self.cluster = cluster
        self.tables = tables
        self.params = params
        self.naive = naive
        self.host_norm = host_norm
        self.vectorized = vectorized
        self.bucket_shapes = bucket_shapes
        self.use_scan = use_scan
        self.max_k = max_k or cluster.n_gpus
        self.stats = PredictorStats()  # instrumentation for Fig. 8
        self._apply = _apply_naive_bw if naive else _apply_hierarchical_bw

    # legacy instrumentation names (benchmarks read/reset these directly)
    @property
    def n_model_calls(self) -> int:
        return self.stats.n_model_calls

    @n_model_calls.setter
    def n_model_calls(self, v: int) -> None:
        self.stats.n_model_calls = v

    @property
    def predict_seconds(self) -> float:
        return self.stats.predict_seconds

    @predict_seconds.setter
    def predict_seconds(self, v: float) -> None:
        self.stats.predict_seconds = v

    # hierarchical stage dispatch --------------------------------------------

    def predict(self, subsets: Sequence[Sequence[int]]) -> np.ndarray:
        """B̂ for a batch of allocations (GB/s, denormalized)."""
        t0 = time.time()
        out = np.zeros((len(subsets),), np.float64)
        model_idx: List[int] = []
        model_subsets: List[Sequence[int]] = []
        for i, s in enumerate(subsets):
            if not self.naive and len(self.cluster.partition_by_host(s)) == 1:
                out[i] = self.tables.lookup_global(list(s))  # Stage-1: exact
            else:
                model_idx.append(i)
                model_subsets.append(s)
        if model_subsets:
            preds = self._predict_model(model_subsets)
            for i, p in zip(model_idx, preds):
                out[i] = p
        self.stats.predict_seconds += time.time() - t0
        return out

    def predict_one(self, subset: Sequence[int]) -> float:
        return float(self.predict([subset])[0])

    def predict_children(self, parent: Sequence[int]) -> np.ndarray:
        """Fused featurize+predict of one PTS elimination round: all
        ``|parent|`` remove-one children in parent order, with the child
        token batch assembled incrementally from the parent's per-host
        grids (:func:`repro.core.features.featurize_children` machinery)
        and single-host children answered by Stage-1 gathers — no
        per-candidate Python.  Predictions are bit-identical to
        ``predict(children)``: same channels, same shape buckets."""
        parent = list(parent)
        n = len(parent)
        if self.naive or n < 2 or not self.vectorized:
            # vectorized=False is the pre-PR reference: every child goes
            # through the ordinary batch predict (loop featurizer)
            return self.predict(
                [parent[:i] + parent[i + 1:] for i in range(n)]
            )
        t0 = time.time()
        with telemetry.span("featurize"):
            arrays = feat_lib.host_arrays(self.cluster, self.tables)
            bits, counts = feat_lib.child_bits_counts(arrays, parent)
            part = counts > 0
            n_part = part.sum(axis=1)
            out = np.zeros((n,), np.float64)
            for i in np.nonzero(n_part == 1)[0]:
                h = int(np.argmax(part[i]))
                out[i] = arrays.intra_bw[h, bits[i, h]]  # Stage-1: exact
            model = np.nonzero(n_part > 1)[0]
            if len(model):
                ks = np.full((len(model),), n - 1, np.int64)
                tokens = feat_lib._isolated_channels(
                    arrays, bits[model], counts[model], ks, self.host_norm
                )
                feats, mask = feat_lib._pack_tokens(
                    tokens, counts[model], self.cluster.n_hosts,
                    feat_lib.N_FEATURES,
                )
        self.stats.featurize_seconds += time.time() - t0
        if len(model):
            out[model] = self._apply_model(feats, mask)
        self.stats.predict_seconds += time.time() - t0
        return out

    # fused on-device descent --------------------------------------------

    def _scan_envelope(self):
        """The (arrays, device tables) pair when this predictor/cluster is
        inside the scan envelope, else None."""
        if self.naive or not self.vectorized or not self.use_scan:
            return None
        arrays = feat_lib.host_arrays(self.cluster, self.tables)
        if arrays.max_host_gpus > _SCAN_MAX_HOST_GPUS:
            return None
        dt = feat_lib.device_tables(self.cluster, self.tables)
        if dt.lattice_size > _SCAN_MAX_LATTICE:
            return None
        return arrays, dt

    def eliminate_to(
        self,
        parent: Sequence[int],
        k: int,
        caps: Optional[np.ndarray] = None,
    ) -> Optional[ScanResult]:
        """Run the whole PTS elimination descent ``|parent| -> k`` as one
        fused on-device ``lax.scan`` (see the module section above).

        ``caps`` is a float32 ``[lattice_size]`` analytic-cap table, numpy
        or already on the device (the contention wrapper uploads one per
        ledger version); None means uncapped (isolated scoring).  Returns
        a :class:`ScanResult`, or None when the configuration is outside
        the scan envelope — the caller falls back to the host loop, which
        is always correct."""
        env = self._scan_envelope()
        if env is None:
            return None
        arrays, dt = env
        parent = sorted(parent)
        n0 = len(parent)
        if k < 1 or n0 <= k:
            return None
        if len(self.cluster.partition_by_host(parent)) < 2:
            return None  # single-host descent: Stage-1 host loop is exact
        N0b = max(_round_up_pow2(n0), SCAN_MIN_SLOTS)
        if N0b > SCAN_MAX_SLOTS:
            return None
        t0 = time.time()
        with telemetry.span("descent") as sp:
            with telemetry.span("descent.prep"):
                if caps is None:
                    caps = dt.resident().caps_inf
                pad = np.zeros((N0b - n0,), np.int32)
                pbits, pcounts, _, _, _ = feat_lib._batch_bits_counts(
                    arrays, [parent]
                )
                packed = _pack_args(
                    np.concatenate([arrays.gpu_host[parent], pad]),
                    np.concatenate([arrays.gpu_bit[parent], pad]),
                    np.arange(N0b) < n0, pbits[0], pcounts[0], k,
                )
            with telemetry.span("descent.upload"):
                args = _scan_args(self.params, dt, caps, packed,
                                  self.host_norm)
                self.stats.n_descent_uploads += 1 + isinstance(
                    caps, np.ndarray)
            with telemetry.span("descent.launch"):
                exe = _compiled_scan(
                    (N0b, pbits.shape[1], dt.mask_size, caps.shape[0]), args
                )
                out = exe(*args)
            with telemetry.span("descent.sync"):
                scores, sels, elims, actives, capped = _unpack_result(
                    np.asarray(out), N0b
                )
            R = int(actives.sum())
            sel = np.arange(N0b) < n0
            sel[elims[:R]] = False
            subset = [parent[i] for i in np.nonzero(sel[:n0])[0]]
            if R != n0 - k or len(subset) != k:
                # never expected: counted, so a smoke or bench run can fail
                self.stats.n_scan_declines += 1
                return None
            sp["steps"] = R
            self.stats.scan_seconds += time.time() - t0
        self.stats.n_scan_steps += R
        return ScanResult(
            subset=subset,
            bw=float(scores[R - 1, elims[R - 1]]),
            n_rounds=R,
            n_capped=int(capped[:R].sum()),
            scores=scores[:R],
            sels=sels[:R],
            elims=elims[:R],
        )

    def warm_scan(self, buckets: Optional[Sequence[int]] = None) -> float:
        """AOT-compile (lower + compile, no execution) the descent
        executables for the cluster's slot buckets, so the first admission
        carries no compile spike.  Returns seconds spent; 0.0 when every
        bucket was already compiled (the executables are process-wide and
        shared across same-shaped clusters)."""
        env = self._scan_envelope()
        if env is None:
            return 0.0
        _, dt = env
        if buckets is None:
            top = min(
                max(_round_up_pow2(self.cluster.n_gpus), SCAN_MIN_SLOTS),
                SCAN_MAX_SLOTS,
            )
            buckets = []
            b = SCAN_MIN_SLOTS
            while b <= top:
                buckets.append(b)
                b *= 2
        spent = 0.0
        H = self.cluster.n_hosts
        caps = dt.resident().caps_inf
        for N0b in buckets:
            key = (N0b, H, dt.mask_size, caps.shape[0])
            if key in _SCAN_COMPILED:
                continue
            args = _scan_args(
                self.params, dt, caps,
                _pack_args(
                    np.zeros((N0b,), np.int32), np.ones((N0b,), np.int32),
                    np.ones((N0b,), bool), np.zeros((H,), np.int32),
                    np.zeros((H,), np.int32), 1,
                ),
                self.host_norm,
            )
            t0 = time.time()
            _compiled_scan(key, args)
            spent += time.time() - t0
        return spent

    def _predict_model(self, subsets: Sequence[Sequence[int]]) -> np.ndarray:
        if self.naive:
            t0 = time.time()
            with telemetry.span("featurize"):
                B = len(subsets)
                Bp = _round_up_pow2(max(B, 1))
                ids, mask = feat_lib.featurize_gpu_ids(
                    self.cluster, subsets, self.max_k
                )
                ids = np.pad(ids, ((0, Bp - B), (0, 0)))
                mask_p = np.pad(mask, ((0, Bp - B), (0, 0)))
                mask_p[B:, 0] = 1.0  # keep padded rows non-degenerate
            self.stats.featurize_seconds += time.time() - t0
            t1 = time.time()
            with telemetry.span("apply"):
                preds = self._apply(
                    self.params, jnp.asarray(ids), jnp.asarray(mask_p)
                )
                decoded = np.asarray(preds)[:B]
            self.stats.n_model_calls += B
            self.stats.infer_seconds += time.time() - t1
            return decoded
        t0 = time.time()
        featurize = (
            feat_lib.featurize_batch if self.vectorized
            else feat_lib.featurize_batch_loop
        )
        with telemetry.span("featurize"):
            feats, mask = featurize(
                self.cluster, self.tables, subsets, host_norm=self.host_norm
            )
        self.stats.featurize_seconds += time.time() - t0
        return self._apply_model(feats, mask)

    def _apply_model(self, feats: np.ndarray, mask: np.ndarray) -> np.ndarray:
        """Bucket + pad + jitted apply, shared by the batch and fused-round
        paths so the two produce identical floats for identical batches."""
        t1 = time.time()
        B = feats.shape[0]
        with telemetry.span("apply"):
            if self.bucket_shapes:
                used = int(mask.sum(axis=1).max()) if B else 1
                H = _round_up_pow2(max(used, 1))
                if H < feats.shape[1]:
                    feats = feats[:, :H]
                    mask = mask[:, :H]
            batcher = active_batcher()
            if batcher is not None:
                # cross-search fusion: the batcher performs the same B
                # padding (value-neutral), possibly alongside other
                # searches' requests
                decoded = batcher.apply(self._apply, self.params, feats, mask)
            else:
                Bp = _round_up_pow2(max(B, 1))
                feats = np.pad(feats, ((0, Bp - B), (0, 0), (0, 0)))
                mask_p = np.pad(mask, ((0, Bp - B), (0, 0)))
                mask_p[B:, 0] = 1.0  # keep padded rows non-degenerate
                preds = self._apply(
                    self.params, jnp.asarray(feats), jnp.asarray(mask_p)
                )
                decoded = np.asarray(preds)[:B]
        self.stats.n_model_calls += B
        self.stats.infer_seconds += time.time() - t1
        return decoded


# ---------------------------------------------------------------------------
# Contended predictor: the deployable B̂(S | L)
# ---------------------------------------------------------------------------

class ContendedSurrogatePredictor:
    """Deployable learned-contention B̂(S | L) (the ContendedSurrogate).

    Same two-stage dispatch as :class:`SurrogatePredictor`: single-host
    allocations never touch a NIC, so Stage-1 exact lookups answer them
    regardless of the ledger; multi-host allocations are featurized together
    with their ledger context and scored by the contended Transformer.

    ``predict(subsets, ledger)`` scores a batch against one live ledger (the
    search path); ``predict_pairs`` takes explicit (subset, ledger) pairs
    (the dataset-evaluation path, where every sample has its own ledger).
    """

    def __init__(
        self,
        cluster: Cluster,
        tables: IntraHostTables,
        params: PyTree,
        max_tokens: Optional[int] = None,
        include_contenders: bool = True,
        host_norm: bool = True,
        vectorized: bool = True,
        bucket_shapes: bool = True,
    ):
        self.cluster = cluster
        self.tables = tables
        self.params = params
        self.max_tokens = max_tokens or feat_lib.default_max_tokens(cluster)
        self.include_contenders = include_contenders
        self.host_norm = host_norm
        self.vectorized = vectorized
        self.bucket_shapes = bucket_shapes
        self.stats = PredictorStats()
        self._apply = _apply_contended_bw

    @property
    def n_model_calls(self) -> int:
        return self.stats.n_model_calls

    @n_model_calls.setter
    def n_model_calls(self, v: int) -> None:
        self.stats.n_model_calls = v

    @property
    def predict_seconds(self) -> float:
        return self.stats.predict_seconds

    @predict_seconds.setter
    def predict_seconds(self, v: float) -> None:
        self.stats.predict_seconds = v

    def predict(self, subsets: Sequence[Sequence[int]], ledger) -> np.ndarray:
        """Contended B̂ for a batch of allocations against one live ledger."""
        return self.predict_pairs([(s, ledger) for s in subsets])

    def predict_one(self, subset: Sequence[int], ledger) -> float:
        return float(self.predict([subset], ledger)[0])

    def predict_pairs(self, pairs: Sequence[Tuple[Sequence[int], Any]]) -> np.ndarray:
        t0 = time.time()
        out = np.zeros((len(pairs),), np.float64)
        model_idx: List[int] = []
        model_pairs: List[Tuple[Sequence[int], Any]] = []
        for i, (s, ledger) in enumerate(pairs):
            if len(self.cluster.partition_by_host(s)) == 1:
                out[i] = self.tables.lookup_global(list(s))  # Stage-1: exact
            else:
                model_idx.append(i)
                model_pairs.append((s, ledger))
        if model_pairs:
            tf = time.time()
            B = len(model_pairs)
            Bp = _round_up_pow2(B)
            featurize = (
                feat_lib.featurize_contended_batch if self.vectorized
                else feat_lib.featurize_contended_batch_loop
            )
            with telemetry.span("featurize"):
                feats, mask = featurize(
                    self.cluster, self.tables, model_pairs,
                    max_tokens=self.max_tokens,
                    include_contenders=self.include_contenders,
                    host_norm=self.host_norm,
                )
                if self.bucket_shapes:
                    used = int(mask.sum(axis=1).max())
                    T = _round_up_pow2(max(used, 1))
                    if T < feats.shape[1]:
                        feats = feats[:, :T]
                        mask = mask[:, :T]
            self.stats.featurize_seconds += time.time() - tf
            ti = time.time()
            with telemetry.span("apply"):
                batcher = active_batcher()
                if batcher is not None:
                    decoded = batcher.apply(
                        self._apply, self.params, feats, mask
                    )
                else:
                    feats = np.pad(feats, ((0, Bp - B), (0, 0), (0, 0)))
                    mask_p = np.pad(mask, ((0, Bp - B), (0, 0)))
                    mask_p[B:, 0] = 1.0
                    preds = self._apply(
                        self.params, jnp.asarray(feats), jnp.asarray(mask_p)
                    )
                    decoded = np.asarray(preds)[:B]
            self.stats.n_model_calls += B
            self.stats.infer_seconds += time.time() - ti
            for i, p in zip(model_idx, decoded):
                out[i] = p
        self.stats.predict_seconds += time.time() - t0
        return out
