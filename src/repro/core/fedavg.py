"""FedAVG and FedSGD [McMahan et al. 2016] — the traditional-FL baselines
the paper shows FAIL in the meta-learning (heterogeneous-client) regime
(Fig. 2): their objective is Eq. (2) (one model good for all clients NOW),
not Eq. (1) (a model that adapts).

Both are thin bindings of the shared round engine (repro.core.engine):
the per-client work runs vmapped across the sampled cohort, the rounds
between evals run as one on-device scan."""
from __future__ import annotations

from typing import Callable, Dict, Optional

from repro.core.engine import CommChannel, run_federated
from repro.core.pipeline import SamplingPolicy
from repro.core.pool import BufferedAggregation, ClientPool
from repro.core.strategies import FedAvgStrategy, FedSGDStrategy
from repro.data.tasks import TaskDistribution


def fedavg_train(loss_fn: Callable, init_params,
                 task_dist: TaskDistribution, *,
                 rounds: int = 1000, beta: float = 0.01, support: int = 32,
                 epochs: int = 8, clients_per_round: int = 8, seed: int = 0,
                 eval_every: int = 0,
                 eval_kwargs: Optional[dict] = None,
                 channel: Optional[CommChannel] = None,
                 prefetch: int = 2, max_block: int = 512,
                 sampling: Optional[SamplingPolicy] = None,
                 pool: Optional[ClientPool] = None,
                 buffered: Optional[BufferedAggregation] = None,
                 mesh=None) -> Dict:
    """FedAVG: clients run E local epochs; server averages the MODELS
    (participation-weighted under a heterogeneity `sampling` policy)."""
    return run_federated(
        init_params, task_dist, FedAvgStrategy(loss_fn, epochs=epochs),
        rounds=rounds, clients_per_round=clients_per_round, alpha=1.0,
        beta=beta, support=support, anneal=False, seed=seed,
        eval_every=eval_every, eval_kwargs=eval_kwargs, channel=channel,
        prefetch=prefetch, max_block=max_block,
        sampling=sampling, pool=pool, buffered=buffered, mesh=mesh)


def fedsgd_train(loss_fn: Callable, init_params,
                 task_dist: TaskDistribution, *,
                 rounds: int = 1000, beta: float = 0.01, support: int = 32,
                 clients_per_round: int = 8, seed: int = 0,
                 eval_every: int = 0,
                 eval_kwargs: Optional[dict] = None,
                 channel: Optional[CommChannel] = None,
                 prefetch: int = 2, max_block: int = 512,
                 sampling: Optional[SamplingPolicy] = None,
                 pool: Optional[ClientPool] = None,
                 buffered: Optional[BufferedAggregation] = None,
                 mesh=None) -> Dict:
    """FedSGD: each client sends ONE gradient; server applies the mean
    (participation-weighted under a heterogeneity `sampling` policy)."""
    return run_federated(
        init_params, task_dist, FedSGDStrategy(loss_fn),
        rounds=rounds, clients_per_round=clients_per_round, alpha=1.0,
        beta=beta, support=support, anneal=False, seed=seed,
        eval_every=eval_every, eval_kwargs=eval_kwargs, channel=channel,
        prefetch=prefetch, max_block=max_block,
        sampling=sampling, pool=pool, buffered=buffered, mesh=mesh)
