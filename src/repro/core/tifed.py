"""TIFeD integer-only federated training [arXiv 2307.03102] — the
compute half of int8 federation (the transport half has been
``CommChannel("int8")`` since PR 1).

Clients train in integer arithmetic: int8 weights on per-tensor
power-of-two grids, int32 accumulators, direct-feedback-alignment
updates with bit-shift learning rates and stochastic-rounding
requantization (see ``core.strategies.TifedStrategy`` and the fused
``kernels/online_sgd_int8.py`` epoch kernel). The uplink is the native
int8 result tree, billed at 1 byte/param; the server dequantizes,
aggregates in one fused psum, and snaps phi back onto the integer grid.

The loop lives in the shared round engine, so tifed composes with
pools, FedBuff, availability processes, schedules, and the client mesh
exactly like the fp32 strategies."""
from __future__ import annotations

from typing import Callable, Dict, Optional

from repro.core.engine import CommChannel, run_federated
from repro.core.pipeline import SamplingPolicy
from repro.core.pool import BufferedAggregation, ClientPool
from repro.core.strategies import TifedStrategy
from repro.data.tasks import TaskDistribution
from repro.models.paper_nets import relu_mlp_loss


def tifed_train(init_params, task_dist: TaskDistribution, *,
                rounds: int = 1000, alpha: float = 1.0,
                support: int = 32, epochs: int = 8, lr_shift: int = 6,
                feedback_seed: int = 0, clients_per_round: int = 1,
                anneal: bool = True, seed: int = 0, eval_every: int = 0,
                eval_kwargs: Optional[dict] = None,
                channel: Optional[CommChannel] = None,
                prefetch: int = 2, max_block: int = 512,
                sampling: Optional[SamplingPolicy] = None,
                pool: Optional[ClientPool] = None,
                buffered: Optional[BufferedAggregation] = None,
                mesh=None, loss_fn: Optional[Callable] = None,
                use_pallas: Optional[bool] = None) -> Dict:
    """Integer-only federated training on the paper's sine MLP shapes.

    No ``beta``: the client learning rate is the integer bit-shift
    ``lr_shift`` (effective rate 2^-(lr_shift + log2(support))).
    ``channel`` defaults to the non-simulating int8 channel — the
    payload already IS int8, so the channel only bills it (a simulating
    or fp32 channel is rejected by the engine). ``loss_fn`` (default
    ``relu_mlp_loss``) is only used for fp32 eval finetuning; keep its
    eval lr <= 0.01 — the ReLU net diverges at the tanh-tuned 0.02 when
    k_steps is large."""
    if channel is None:
        channel = CommChannel("int8", quantize=False)
    strategy = TifedStrategy(
        relu_mlp_loss if loss_fn is None else loss_fn, epochs=epochs,
        lr_shift=lr_shift, feedback_seed=feedback_seed,
        use_pallas=use_pallas)
    return run_federated(
        init_params, task_dist, strategy,
        rounds=rounds, clients_per_round=clients_per_round, alpha=alpha,
        beta=0.0, support=support, anneal=anneal, seed=seed,
        eval_every=eval_every, eval_kwargs=eval_kwargs, channel=channel,
        prefetch=prefetch, max_block=max_block,
        sampling=sampling, pool=pool, buffered=buffered, mesh=mesh)
