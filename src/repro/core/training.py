"""Surrogate training: offline initialization + online adaptation (Sec. 4.1.2).

The paper trains the hierarchical Transformer on a deliberately sparse set of
inter-host measurements (250 samples in the headline results) and keeps it
fresh by fine-tuning on bandwidths observed from live jobs.  Both paths share
one jitted AdamW step.

The learned-contention subsystem adds a third trainee: the
**ContendedSurrogate** (`train_contended_surrogate`), fitted on (subset,
ledger, contended-bandwidth) triples — synthetic ones from
:mod:`repro.core.contended_dataset` or live ones from its telemetry
harvester (`online_finetune_contended`, the Sec. 4.1.2 adaptation loop under
tenancy).  The curriculum deliberately mixes isolated (empty-ledger) and
contended samples so the model keeps its isolated accuracy while absorbing
the rail split.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import features as feat_lib
from repro.core import surrogate as surr
from repro.core.bandwidth_sim import BW_SCALE, BandwidthSimulator
from repro.core.cluster import Cluster
from repro.core.intra_host import IntraHostTables
from repro.train.optimizer import AdamWConfig, adamw, cosine_schedule

PyTree = Any

# One contended-training sample: (subset, ledger-or-None, bandwidth GB/s).
# ``ledger`` duck-types JobLedger (the featurizer reads contender_demands /
# cross_host_jobs_on / busy); None means isolated.
ContendedTriple = Tuple[Sequence[int], Any, float]


@dataclasses.dataclass
class TrainConfig:
    steps: int = 3000
    batch_size: int = 64
    lr: float = 3e-3
    weight_decay: float = 1e-4
    warmup_steps: int = 100
    seed: int = 0
    log_every: int = 0  # 0 = silent


def _mse_loss(apply_fn, params, x, mask, y):
    pred = apply_fn(params, x, mask)
    return jnp.mean(jnp.square(pred - y))


def train_step(apply_fn, config: TrainConfig):
    """-> (opt_init, step): the jitted AdamW step of every trainee.

    ``step(params, opt_state, x, mask, targets, idx)`` gathers the minibatch
    ``idx`` from the device-resident dataset and returns
    ``(params, opt_state, loss)``."""
    opt_cfg = AdamWConfig(
        lr=config.lr, weight_decay=config.weight_decay, grad_clip_norm=1.0
    )
    opt_init, opt_update = adamw(
        opt_cfg, cosine_schedule(config.steps, config.warmup_steps)
    )

    @jax.jit
    def step(params, opt_state, x, mask, targets, idx):
        xb, mb, yb = x[idx], mask[idx], targets[idx]
        loss, grads = jax.value_and_grad(
            lambda p: _mse_loss(apply_fn, p, xb, mb, yb)
        )(params)
        params, opt_state, metrics = opt_update(grads, opt_state, params)
        return params, opt_state, loss

    return opt_init, step


def _fit(
    apply_fn,
    params: PyTree,
    x: jnp.ndarray,
    mask: jnp.ndarray,
    targets: jnp.ndarray,
    config: TrainConfig,
) -> Tuple[PyTree, Dict[str, float]]:
    """The shared AdamW loop: minibatch MSE on normalized log-bandwidth."""
    n = int(x.shape[0])
    opt_init, step = train_step(apply_fn, config)
    opt_state = opt_init(params)

    rng = np.random.default_rng(config.seed)
    t0 = time.time()
    loss = np.inf
    for i in range(config.steps):
        idx = jnp.asarray(rng.integers(0, n, size=min(config.batch_size, n)))
        params, opt_state, loss = step(params, opt_state, x, mask, targets,
                                       idx)
        if config.log_every and (i + 1) % config.log_every == 0:
            print(f"  surrogate step {i + 1}/{config.steps} loss={float(loss):.5f}")
    jax.block_until_ready(params)  # steps are enqueued, not yet done
    info = {
        "train_seconds": time.time() - t0,
        "final_loss": float(loss),
        "n_samples": n,
        "param_bytes": surr.param_bytes(params),
    }
    return params, info


def train_surrogate(
    cluster: Cluster,
    tables: IntraHostTables,
    dataset: Sequence[Tuple[Sequence[int], float]],
    config: TrainConfig = TrainConfig(),
    naive: bool = False,
    init_params: Optional[PyTree] = None,
    host_norm: bool = True,
) -> Tuple[PyTree, Dict[str, float]]:
    """Train hierarchical (or naive) surrogate on (allocation, bandwidth) pairs.

    Returns (params, info) where info records wall time and final loss.
    """
    key = jax.random.PRNGKey(config.seed)
    subsets = [list(s) for s, _ in dataset]
    targets = np.asarray(
        surr.encode_bw(np.asarray([bw for _, bw in dataset], np.float32))
    )

    if naive:
        x, mask = feat_lib.featurize_gpu_ids(cluster, subsets, cluster.n_gpus)
        apply_fn = surr.apply_naive
        params = init_params or surr.init_naive_params(key, cluster.n_gpus)
    else:
        x, mask = feat_lib.featurize_batch(
            cluster, tables, subsets, host_norm=host_norm
        )
        apply_fn = surr.apply_hierarchical
        params = init_params or surr.init_hierarchical_params(key)

    return _fit(
        apply_fn, params, jnp.asarray(x), jnp.asarray(mask),
        jnp.asarray(targets), config,
    )


def online_finetune(
    cluster: Cluster,
    tables: IntraHostTables,
    params: PyTree,
    new_measurements: Sequence[Tuple[Sequence[int], float]],
    steps: int = 200,
    lr: float = 5e-4,
    seed: int = 1,
) -> PyTree:
    """Online adaptation: a few low-LR steps on freshly observed bandwidths
    (Sec. 4.2.2).  No architecture change, no full retraining."""
    cfg = TrainConfig(steps=steps, lr=lr, warmup_steps=0, seed=seed)
    params, _ = train_surrogate(
        cluster, tables, new_measurements, cfg, init_params=params
    )
    return params


# ---------------------------------------------------------------------------
# ContendedSurrogate training (the learned-contention subsystem)
# ---------------------------------------------------------------------------

def train_contended_surrogate(
    cluster: Cluster,
    tables: IntraHostTables,
    dataset: Sequence[ContendedTriple],
    config: TrainConfig = TrainConfig(),
    base_params: Optional[PyTree] = None,
    init_params: Optional[PyTree] = None,
    include_contenders: bool = True,
    max_tokens: Optional[int] = None,
    host_norm: bool = True,
) -> Tuple[PyTree, Dict[str, float]]:
    """Fit the ContendedSurrogate on (subset, ledger, bandwidth) triples.

    ``base_params`` (the trained isolated surrogate) warm-starts the trunk;
    without it a fresh isolated init is used.  ``init_params`` resumes an
    existing contended model (the online fine-tune path).  The dataset is
    the curriculum: :func:`repro.core.contended_dataset.build_contended_dataset`
    mixes isolated (empty-ledger) and contended samples so the model's
    zero-context behaviour stays anchored to the isolated one.
    """
    key = jax.random.PRNGKey(config.seed)
    pairs = [(list(s), led) for s, led, _ in dataset]
    targets = np.asarray(
        surr.encode_bw(np.asarray([bw for _, _, bw in dataset], np.float32))
    )
    x, mask = feat_lib.featurize_contended_batch(
        cluster, tables, pairs, max_tokens=max_tokens,
        include_contenders=include_contenders, host_norm=host_norm,
    )
    if init_params is None:
        init_params = surr.init_contended_params(
            base_params
            if base_params is not None
            else surr.init_hierarchical_params(key)
        )
    return _fit(
        surr.apply_contended, init_params, jnp.asarray(x), jnp.asarray(mask),
        jnp.asarray(targets), config,
    )


def online_finetune_contended(
    cluster: Cluster,
    tables: IntraHostTables,
    params: PyTree,
    new_samples: Sequence[ContendedTriple],
    steps: int = 200,
    lr: float = 5e-4,
    seed: int = 1,
    **featurize_kwargs,
) -> PyTree:
    """Online adaptation under tenancy: a few low-LR steps on contended
    bandwidths harvested from live admissions (telemetry harvester)."""
    cfg = TrainConfig(steps=steps, lr=lr, warmup_steps=0, seed=seed)
    params, _ = train_contended_surrogate(
        cluster, tables, new_samples, cfg, init_params=params,
        **featurize_kwargs,
    )
    return params


# ---------------------------------------------------------------------------
# Accuracy metrics (Sec. 5.2): R^2 and MAPE
# ---------------------------------------------------------------------------

def _accuracy(y: np.ndarray, pred: np.ndarray) -> Dict[str, float]:
    resid = y - pred
    ss_res = float(np.sum(resid**2))
    ss_tot = float(np.sum((y - y.mean()) ** 2))
    r2 = 1.0 - ss_res / max(ss_tot, 1e-12)
    mape = float(np.mean(np.abs(resid) / np.maximum(np.abs(y), 1e-9))) * 100.0
    return {"r2": r2, "mape": mape, "n": len(y)}


def evaluate_surrogate(
    predictor: "surr.SurrogatePredictor",
    dataset: Sequence[Tuple[Sequence[int], float]],
) -> Dict[str, float]:
    subsets = [list(s) for s, _ in dataset]
    y = np.asarray([bw for _, bw in dataset], np.float64)
    return _accuracy(y, predictor.predict(subsets))


def evaluate_contended_predictor(
    predictor,
    dataset: Sequence[ContendedTriple],
) -> Dict[str, float]:
    """R^2 / MAPE of a contended predictor over (subset, ledger, bw)
    triples.  ``predictor`` must expose ``predict_pairs`` (the
    ContendedSurrogate): each sample is scored against its *own* ledger.
    For the analytic even-split baseline use :func:`evaluate_analytic_cap`
    — a plain ``predict(subsets)`` wrapper reads only the single ledger it
    wraps and would silently mis-score a per-sample-ledger dataset."""
    if not hasattr(predictor, "predict_pairs"):
        raise TypeError(
            "evaluate_contended_predictor needs a predict_pairs predictor; "
            "for the analytic cap baseline use evaluate_analytic_cap"
        )
    y = np.asarray([bw for _, _, bw in dataset], np.float64)
    pred = predictor.predict_pairs([(list(s), led) for s, led, _ in dataset])
    return _accuracy(y, np.asarray(pred, np.float64))


def evaluate_analytic_cap(
    cluster: Cluster,
    base_predictor,
    dataset: Sequence[ContendedTriple],
) -> Tuple[np.ndarray, Dict[str, float]]:
    """The analytic baseline over (subset, ledger, bw) triples:
    ``min(B̂_iso(S), even-split cap(S, L))`` with each sample's own ledger.
    One batched isolated predict; the caps are closed-form (no model
    calls).  Returns (predictions, accuracy dict)."""
    from repro.core.contention import contended_inter_cap

    subsets = [list(s) for s, _, _ in dataset]
    preds = np.asarray(base_predictor.predict(subsets), np.float64).copy()
    for i, (s, ledger, _) in enumerate(dataset):
        if ledger is not None and len(ledger) > 0:
            cap = contended_inter_cap(cluster, ledger, s)
            if cap < preds[i]:
                preds[i] = cap
    y = np.asarray([bw for _, _, bw in dataset], np.float64)
    return preds, _accuracy(y, preds)


def make_train_test_split(
    sim: BandwidthSimulator,
    n_train: int,
    test_mult: int = 5,
    seed: int = 0,
) -> Tuple[List, List]:
    """Paper protocol: test set is 5x the training set, all inter-host, and
    disjoint from the training allocations."""
    rng = np.random.default_rng(seed)
    total = sim.build_dataset(n_train * (test_mult + 1), rng, noisy=True)
    train = total[:n_train]
    # test targets are *noiseless* ground truth: we grade the model against
    # reality, not against one noisy measurement of it.
    test = [(s, sim.true_bandwidth(s)) for s, _ in total[n_train:]]
    return train, test
