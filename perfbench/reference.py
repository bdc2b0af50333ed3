"""Plain reference of the dispatcher's bandwidth scores, and its control.

The dispatcher ranks candidate placements S by a score:

* a single-host S: the host's measured intra-host bandwidth (Stage 1);
* a multi-host S: the hierarchical surrogate's B(S), one token per
  participating host (BandPilot, arXiv:2506.15595, Sec. 4.2), then
  - analytic contention (Sec. 4.4): min(B(S), cap(S | L)), the fair-share
    rail cap of the live ledger L;
  - learned contention: min(B(S), B_c(S | L)) for a contended S, where B_c
    is the contended surrogate (the same trunk, plus a context embedding of
    ledger channels and one token per contending job and shared host).

This module recomputes that score from first principles: its own token
features, its own Transformer forward pass in ``jax.numpy``, its own cap
arithmetic and its own intra-host bandwidths.  It imports nothing of the
program.  It reads the deployment's configuration file (hosts, each host
class's link table from the paper's Appendix E, link and NIC rail
bandwidths) and the model weights the deployment serves.

``precision="highest"`` is float32 at full matmul precision, the precision
the program states.  ``precision="bf16x3"`` is the control: every matmul as
three bfloat16 passes (hi*hi + hi*lo + lo*hi, float32 accumulation), the
arithmetic of ``Precision.HIGH``, written out so that it is the same on the
CPU and on the TPU.
"""

from __future__ import annotations

import functools
import hashlib
import itertools
import math
from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

LOG_SCALE = 5.0          # log-space bandwidth encoding of the surrogate
N_HEADS = 4
INTER_EFF = 0.92         # fabric efficiency of the rail model
JITTER = 0.02            # fabric calibration amplitude (per hosts/counts)
C_NORM = 4.0             # contender-count channel normaliser
SINGLE_GPU_BW = 500.0    # the fabric's "bandwidth" of a one-GPU placement
SWITCH_EFF = 0.82        # switch derate for GPU counts not in BALANCED
BALANCED = (1, 2, 4, 8)
BLOCK = 512              # reference batch rows per device call

Subset = Tuple[int, ...]
Snapshot = Tuple[Tuple[str, Tuple[int, ...]], ...]   # live (job id, gpus)


class Fabric:
    """The deployment the scores are about: topology and measured links,
    from the configuration file alone (``Fabric.from_config``)."""

    def __init__(self, name: str, host_types: Sequence[Dict],
                 link_bw: Dict[str, float]):
        self.name = name
        self.host_types = list(host_types)
        self.link_bw = dict(link_bw)
        self.gpu_host: List[int] = []
        self.host_gpus: List[Tuple[int, ...]] = []
        for hid, ht in enumerate(self.host_types):
            lo = len(self.gpu_host)
            self.host_gpus.append(tuple(range(lo, lo + len(ht["topology"]))))
            self.gpu_host.extend([hid] * len(ht["topology"]))
        self.rail_bw = [float(ht["nic_rail_bw"]) for ht in self.host_types]
        self.n_gpus = len(self.gpu_host)
        self.n_hosts = len(self.host_gpus)
        self._memo: Dict[Tuple[int, Tuple[int, ...]], float] = {}

    @classmethod
    def from_config(cls, cfg: Dict) -> "Fabric":
        """GPUs are numbered host after host, in the order of ``hosts``."""
        fab = cfg["fabric"]
        types = {name: dict(t, topology=[r.split() for r in t["topology"]])
                 for name, t in fab["host_types"].items()}
        hosts = [types[name] for name, n in cfg["hosts"] for _ in range(n)]
        return cls(cfg["cluster_name"], hosts, fab["link_bw"])

    def by_host(self, gpus: Sequence[int]) -> Dict[int, List[int]]:
        out: Dict[int, List[int]] = {}
        for g in sorted(gpus):
            out.setdefault(self.gpu_host[g], []).append(g)
        return out

    def intra(self, hid: int, gpus: Sequence[int]) -> float:
        base = self.host_gpus[hid][0]
        key = (hid, tuple(sorted(g - base for g in gpus)))
        bw = self._memo.get(key)
        if bw is None:
            bw = self._memo[key] = intra_bw(self.host_types[hid],
                                            self.link_bw, key[1]) \
                * _jitter(self.name, *key)
        return bw


def intra_bw(ht: Dict, link_bw: Dict[str, float],
             local: Tuple[int, ...]) -> float:
    """Aggregate collective bandwidth of GPUs ``local`` (sorted indices) of
    one host: n times the link of a switched host (derated for unbalanced
    counts), else n times the best ring's slowest link."""
    n = len(local)
    if n == 1:
        return SINGLE_GPU_BW

    def link(i, j):
        return link_bw[ht["topology"][i][j]]
    if ht["nvswitch"]:
        return link(local[0], local[1]) * n * (
            1.0 if n in BALANCED else SWITCH_EFF)
    rings = ((local[0],) + p for p in itertools.permutations(local[1:]))
    return n * max(min(link(r[i], r[(i + 1) % n]) for i in range(n))
                   for r in rings)


# -- token features ---------------------------------------------------------

def host_token(fab: Fabric, hid: int, gpus: Sequence[int], k: int):
    """(log intra, n/8, n/k, k/N, log intra against the host's rails)."""
    n = len(gpus)
    li = math.log1p(fab.intra(hid, gpus))
    return [li / LOG_SCALE, n / 8.0, n / k, k / fab.n_gpus,
            (li - math.log1p(fab.rail_bw[hid] * n)) / LOG_SCALE]


def _contenders(fab: Fabric, snap: Snapshot, hid: int, sset) -> list:
    """Live cross-host jobs with a GPU on ``hid`` and none in ``sset``,
    in job-id order."""
    out = []
    for job_id, gpus in sorted(snap):
        hosts = {fab.gpu_host[g] for g in gpus}
        if len(hosts) > 1 and hid in hosts and sset.isdisjoint(gpus):
            out.append((job_id, gpus))
    return out


def iso_tokens(fab: Fabric, subset: Sequence[int]) -> np.ndarray:
    parts = fab.by_host(subset)
    return np.asarray([host_token(fab, h, g, len(subset))
                       for h, g in sorted(parts.items())], np.float64)


def contended_tokens(fab: Fabric, subset: Sequence[int],
                     snap: Snapshot) -> np.ndarray:
    """Candidate-host tokens with ledger channels (segment 0), then one
    token per (shared host, contending job) (segment 1), at most
    3 x hosts tokens."""
    parts = sorted(fab.by_host(subset).items())
    k, sset = len(subset), set(subset)
    busy = {g for _, gpus in snap for g in gpus}
    rows, ctx, jobs = [], {}, {}
    for hid, _ in parts:
        jobs[hid] = _contenders(fab, snap, hid, sset)
        demand = sum(1 for _, gpus in jobs[hid] for g in gpus
                     if fab.gpu_host[g] == hid)
        occ = sum(1 for g in fab.host_gpus[hid]
                  if g in busy and g not in sset) / len(fab.host_gpus[hid])
        ctx[hid] = [len(jobs[hid]) / C_NORM, demand / 8.0, occ, 0.0]
    for hid, gpus in parts:
        rows.append(host_token(fab, hid, gpus, k) + [0.0] + ctx[hid])
    max_tokens = 3 * fab.n_hosts
    if len(parts) > 1:
        for hid, _ in parts:
            for _, gpus in jobs[hid]:
                if len(rows) >= max_tokens:
                    break
                mine = [g for g in gpus if fab.gpu_host[g] == hid]
                rows.append(host_token(fab, hid, mine, len(gpus))
                            + [1.0] + ctx[hid])
    return np.asarray(rows[:max_tokens], np.float64)


def contended(fab: Fabric, subset: Sequence[int], snap: Snapshot) -> bool:
    parts = fab.by_host(subset)
    sset = set(subset)
    return len(parts) > 1 and any(
        _contenders(fab, snap, h, sset) for h in parts)


# -- analytic cap -------------------------------------------------------------

def _jitter(*key) -> float:
    """The fabric's calibration of one host subset or one inter-host
    split: a deterministic +-2% factor keyed by an MD5 of the key's repr."""
    h = hashlib.md5(repr(key).encode()).digest()
    v = int.from_bytes(h[:8], "little") / 2**64
    return 1.0 + JITTER * (2.0 * v - 1.0)


def cap(fab: Fabric, subset: Sequence[int], snap: Snapshot) -> float:
    """Fair-share rail cap: min_h(rail_h / c_h) * min_h(n_h) * 2(k-1)/k
    * eta * calibration; inf when no rail is shared."""
    parts = fab.by_host(subset)
    if len(parts) <= 1:
        return math.inf
    sset = set(subset)
    shares = {h: 1 + len(_contenders(fab, snap, h, sset)) for h in parts}
    if all(c == 1 for c in shares.values()):
        return math.inf
    rail = min(fab.rail_bw[h] / shares[h] for h in parts)
    counts = [len(parts[h]) for h in parts]
    k = sum(counts)
    inter = rail * min(counts) * (2.0 * (k - 1) / k) * INTER_EFF
    return inter * _jitter(fab.name, "inter",
                           tuple(sorted((h, len(g)) for h, g in parts.items())))


# -- Transformer forward ------------------------------------------------------

def _bf16(x):
    """x rounded to bfloat16's 8 significant bits, kept in float32.
    (A float32 -> bfloat16 -> float32 convert pair may be elided by XLA,
    which keeps excess precision; reduce_precision is not.)"""
    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


def _contract(spec: str, a, b, precision: str):
    def f(x, y):
        return jnp.einsum(spec, x, y, precision=jax.lax.Precision.HIGHEST)
    if precision == "highest":
        return f(a, b)
    if precision != "bf16x3":
        raise ValueError(f"unknown precision {precision!r}")
    # bfloat16 operands multiply exactly in float32: the three passes are
    # hi*hi + hi*lo + lo*hi with float32 accumulation, lo*lo dropped
    ah, bh = _bf16(a), _bf16(b)
    al, bl = _bf16(a - ah), _bf16(b - bh)
    return f(ah, bh) + f(ah, bl) + f(al, bh)


def _dense(p, x, precision):
    return _contract("...i,io->...o", x, p["w"], precision) + p["b"]


def _layernorm(p, x):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + 1e-6) * p["g"] + p["b"]


def _gelu(x):   # tanh approximation, as jax.nn.gelu's default
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def _attention(p, x, mask, precision):
    B, T, D = x.shape
    dh = D // N_HEADS
    qkv = _dense(p["qkv"], x, precision)
    q, k, v = (qkv[..., i * D:(i + 1) * D].reshape(B, T, N_HEADS, dh)
               for i in range(3))
    s = _contract("bind,bjnd->bnij", q, k, precision) / math.sqrt(dh)
    s = jnp.where(mask[:, None, None, :] > 0, s, -jnp.inf)
    w = jnp.exp(s - s.max(-1, keepdims=True))
    w = w / w.sum(-1, keepdims=True)
    out = _contract("bnij,bjnd->bind", w, v, precision).reshape(B, T, D)
    return _dense(p["o"], out, precision)


@functools.partial(jax.jit, static_argnames=("precision", "n_base"))
def forward(params, feats, mask, precision: str, n_base: int = 5):
    """Normalised log-bandwidth per row: feats [B, T, F], mask [B, T]."""
    x = _dense(params["embed"], feats[..., :n_base], precision)
    if "ctx_embed" in params:
        x = x + _contract("...i,io->...o", feats[..., n_base:],
                          params["ctx_embed"]["w"], precision)
    trunk = params["trunk"]
    for layer in trunk["layers"]:
        x = x + _attention(layer, _layernorm(layer["ln1"], x), mask, precision)
        h = _gelu(_dense(layer["ff1"], _layernorm(layer["ln2"], x), precision))
        x = x + _dense(layer["ff2"], h, precision)
    x = _layernorm(trunk["ln_f"], x)
    pooled = (x * mask[..., None]).sum(1) / jnp.maximum(
        mask.sum(-1, keepdims=True), 1.0)
    h = _gelu(_dense(trunk["head"][0], pooled, precision))
    h = _gelu(_dense(trunk["head"][1], h, precision))
    return _dense(trunk["head"][2], h, precision)[..., 0]


def decode(y: np.ndarray) -> np.ndarray:
    return np.expm1(np.clip(np.asarray(y, np.float64), 0.0, 2.0) * LOG_SCALE)


def _apply(params, token_rows: List[np.ndarray], n_tokens: int,
           precision: str) -> np.ndarray:
    """Bandwidth per token matrix, in blocks of BLOCK rows."""
    n = len(token_rows)
    if n == 0:
        return np.zeros((0,), np.float64)
    F = token_rows[0].shape[1]
    out = []
    for lo in range(0, n, BLOCK):
        rows = token_rows[lo:lo + BLOCK]
        feats = np.zeros((BLOCK, n_tokens, F), np.float32)
        mask = np.zeros((BLOCK, n_tokens), np.float32)
        mask[len(rows):, 0] = 1.0
        for i, r in enumerate(rows):
            feats[i, :len(r)] = r
            mask[i, :len(r)] = 1.0
        y = forward(params, jnp.asarray(feats), jnp.asarray(mask),
                    precision=precision)
        out.append(decode(np.asarray(y))[:len(rows)])
    return np.concatenate(out)


def scores(fab: Fabric, items: Sequence[Tuple[Subset, Snapshot]], mode: str,
           params, contended_params=None,
           precision: str = "highest") -> np.ndarray:
    """The dispatcher's score of each (subset, live ledger) item."""
    out = np.zeros((len(items),), np.float64)
    iso_idx, iso_rows, c_idx, c_rows = [], [], [], []
    for i, (subset, snap) in enumerate(items):
        parts = fab.by_host(subset)
        if len(parts) == 1:
            (hid, gpus), = parts.items()
            out[i] = fab.intra(hid, gpus)
            continue
        iso_idx.append(i)
        iso_rows.append(iso_tokens(fab, subset))
        if mode == "learned" and contended(fab, subset, snap):
            c_idx.append(i)
            c_rows.append(contended_tokens(fab, subset, snap))
    out[iso_idx] = _apply(params, iso_rows, fab.n_hosts, precision)
    if mode == "analytic":
        for i in iso_idx:
            out[i] = min(out[i], cap(fab, items[i][0], items[i][1]))
    elif mode == "learned":
        learned = _apply(contended_params, c_rows, 3 * fab.n_hosts, precision)
        out[c_idx] = np.minimum(out[c_idx], learned)
    else:
        raise ValueError(f"unknown contention mode {mode!r}")
    return out
