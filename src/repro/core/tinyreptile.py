"""TinyReptile (the paper's Algorithm 1), faithful implementation.

Per round:
  1. sample ONE client t from the training population (serial schema);
  2. send phi to the client                      (bytes accounted);
  3. the client runs one SGD step per STREAMING support sample
     (online learning: the sample is discarded after its update —
     at any time only one sample lives in memory);
  4. the client returns phi_hat                  (bytes accounted);
  5. server: phi <- phi + alpha (phi_hat - phi).

Optionally the server learning rate anneals linearly (Appendix A notes
annealing helps; the Reptile paper uses it too).

The loop itself lives in the shared round engine (repro.core.engine);
this module only binds the TinyReptile strategy. `channel` selects the
transport (fp32/fp16/int8 byte accounting + optional quantization).
"""
from __future__ import annotations

from typing import Callable, Dict, Optional

from repro.core.engine import CommChannel, run_federated
from repro.core.pipeline import SamplingPolicy
from repro.core.pool import BufferedAggregation, ClientPool
from repro.core.strategies import TinyReptileStrategy
from repro.data.tasks import TaskDistribution


def tinyreptile_train(loss_fn: Callable, init_params,
                      task_dist: TaskDistribution, *,
                      rounds: int = 1000, alpha: float = 1.0,
                      beta: float = 0.01, support: int = 32,
                      anneal: bool = True, seed: int = 0,
                      eval_every: int = 0, eval_kwargs: Optional[dict] = None,
                      use_pallas: Optional[bool] = None,
                      channel: Optional[CommChannel] = None,
                      prefetch: int = 2, max_block: int = 512,
                      clients_per_round: int = 1,
                      sampling: Optional[SamplingPolicy] = None,
                      pool: Optional[ClientPool] = None,
                      buffered: Optional[BufferedAggregation] = None,
                      mesh=None) -> Dict:
    """Returns {"params", "history", "comm_bytes", "per_client_bytes"};
    history rows are per-eval dicts. `prefetch`/`max_block` tune the
    engine's host/device pipeline; `sampling` picks the host data path
    and plugs in a heterogeneity schedule (partial participation /
    stragglers) and `clients_per_round` > 1 grows the paper's serial
    schema into a cohort for such policies (see
    repro.core.engine.run_federated)."""
    return run_federated(
        init_params, task_dist,
        TinyReptileStrategy(loss_fn, use_pallas=use_pallas),
        rounds=rounds, clients_per_round=clients_per_round, alpha=alpha,
        beta=beta, support=support, anneal=anneal, seed=seed,
        eval_every=eval_every, eval_kwargs=eval_kwargs, channel=channel,
        prefetch=prefetch, max_block=max_block,
        sampling=sampling, pool=pool, buffered=buffered, mesh=mesh)
