"""Mesh-scale federated meta-learning (beyond-paper scale, paper-faithful
semantics).

Two mappings of the paper's schema onto the production mesh:

1. COHORT mode (``make_meta_train_step`` in repro.runtime.steps): the
   data-parallel section of the mesh acts as one composite client; the K
   inner SGD steps consume the streaming microbatches; Reptile
   interpolation closes the round. Collective structure: K gradient
   all-reduces over ("pod","data") + the interpolation.

2. POD-CLIENT mode (here): each POD is one federated client. Inner SGD
   all-reduces stay WITHIN the pod (cheap intra-pod ICI); the pods'
   pseudo-gradients are exchanged across the (slow) pod axis ONCE per
   round — TinyReptile's communication thriftiness expressed as a
   collective schedule: O(K) intra-pod collectives, O(1) cross-pod
   collectives.

Pod-client mode no longer hand-rolls the round: it is a thin
CONFIGURATION of the round engine's building blocks — each pod runs
``repro.core.engine.streaming_sgd`` (the engine's inner loop) on its own
client stream, and the server fold is the strategies' collective
aggregation hook (``reptile_aggregate_weighted(..., axis_name="pod")``:
each pod contributes weight 1/n_pods and the weighted client mean
all-reduces across the pod axis — exactly the masked-psum form the
client-sharded engine uses over its "clients" axis, see
``run_federated(mesh=...)``). ``jax.shard_map`` is manual over "pod"
and leaves ("data","model") to GSPMD inside.
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def make_pod_client_meta_step(model, mesh, *, beta: float = 0.01,
                              alpha: float = 0.5) -> Callable:
    """TinyReptile round with pods as clients. batch leaves have leading
    dims (K, mb, ...) with mb sharded over ("pod","data"); inside
    shard_map each pod sees mb/n_pods rows = its OWN client stream."""
    if "pod" not in mesh.axis_names:
        raise ValueError("pod-client mode needs the multi-pod mesh")

    n_pods = mesh.shape["pod"]

    def round_body(phi, batch, alpha_t):
        # runs per-pod (manual over "pod"; auto over data/model);
        # internal constraints must not mention the manual axes
        from repro.core.engine import streaming_sgd
        from repro.core.strategies import reptile_aggregate_weighted
        from repro.runtime.shardctx import manual_axes

        with manual_axes("pod"):
            # the engine's inner loop: one SGD step per arriving
            # microbatch, fp32 update math
            phi_hat, losses = streaming_sgd(model.loss_fn, phi, batch,
                                            beta)
            # the engine's server fold: this pod is ONE client of the
            # n_pods cohort (weight 1/n_pods); the weighted client mean
            # all-reduces across "pod" — the O(1) cross-pod exchange
            new_phi = reptile_aggregate_weighted(
                phi, jax.tree.map(lambda q: q[None], phi_hat), alpha_t,
                jnp.full((1,), 1.0 / n_pods, jnp.float32),
                use_pallas=False, axis_name="pod")
            loss = jax.lax.pmean(losses.mean(), "pod")
            return new_phi, {"loss": loss,
                             "inner_first": jax.lax.pmean(losses[0], "pod"),
                             "inner_last": jax.lax.pmean(losses[-1], "pod")}

    def step(phi, batch, alpha_t=None):
        # manual ONLY over "pod": params replicated across pods (each pod =
        # one client starting from the same phi), batch split per pod on
        # the microbatch dim. "data"/"model" stay auto (GSPMD shards them
        # via the model's internal constraints). alpha_t optionally
        # overrides the static server rate with a traced (annealed)
        # scalar — launch/train.py's --mesh pod path.
        if alpha_t is None:
            alpha_t = jnp.float32(alpha)
        in_specs = (
            jax.tree.map(lambda x: P(), phi),
            jax.tree.map(lambda x: P(None, "pod"), batch),
            P(),
        )
        out_specs = (jax.tree.map(lambda x: P(), phi), P())
        fn = jax.shard_map(
            round_body, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
            axis_names={"pod"}, check_vma=False)
        return fn(phi, batch, jnp.asarray(alpha_t, jnp.float32))

    return step
