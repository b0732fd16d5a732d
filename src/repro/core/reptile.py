"""Reptile baseline [Nichol et al. 2018], batched & serial variants —
the paper's main comparison (Figs. 2-4, Tables II-IV).

Difference from TinyReptile: the client trains on its ENTIRE support set
in batch for E epochs (data stored and reused — the resource cost the
paper measures in Table II).

The loop lives in the shared round engine (repro.core.engine); with
clients_per_round > 1 the per-client inner loops run vmapped on-device
instead of one Python iteration per client."""
from __future__ import annotations

from typing import Callable, Dict, Optional

from repro.core.engine import CommChannel, run_federated
from repro.core.pipeline import SamplingPolicy
from repro.core.pool import BufferedAggregation, ClientPool
from repro.core.strategies import ReptileStrategy
from repro.data.tasks import TaskDistribution


def reptile_train(loss_fn: Callable, init_params,
                  task_dist: TaskDistribution, *,
                  rounds: int = 1000, alpha: float = 1.0, beta: float = 0.01,
                  support: int = 32, epochs: int = 8,
                  clients_per_round: int = 1, anneal: bool = True,
                  seed: int = 0, eval_every: int = 0,
                  eval_kwargs: Optional[dict] = None,
                  channel: Optional[CommChannel] = None,
                  prefetch: int = 2, max_block: int = 512,
                  sampling: Optional[SamplingPolicy] = None,
                  pool: Optional[ClientPool] = None,
                  buffered: Optional[BufferedAggregation] = None,
                  mesh=None) -> Dict:
    """clients_per_round == 1 -> serial Reptile; > 1 -> batched Reptile
    (server averages the per-client pseudo-gradients; requires concurrent
    connections to all sampled clients — the cost the paper calls out).
    `sampling` plugs in a heterogeneity schedule (partial participation /
    stragglers over the cohort)."""
    return run_federated(
        init_params, task_dist, ReptileStrategy(loss_fn, epochs=epochs),
        rounds=rounds, clients_per_round=clients_per_round, alpha=alpha,
        beta=beta, support=support, anneal=anneal, seed=seed,
        eval_every=eval_every, eval_kwargs=eval_kwargs, channel=channel,
        prefetch=prefetch, max_block=max_block,
        sampling=sampling, pool=pool, buffered=buffered, mesh=mesh)
