"""Federated strategies: each core/ algorithm as pure-JAX hooks.

A ``FedStrategy`` tells the round engine (repro.core.engine) WHAT a
client computes and HOW the server folds the results back; the engine
owns everything else (scheduling, scanning, metering, annealing, eval).
All hooks must be jax-traceable — ``client_update`` runs under ``vmap``
across the round's clients inside a ``lax.scan`` over rounds:

  client_update(phi, client_batch, beta) -> (result_tree, inner_losses)
      phi: broadcast parameters; client_batch: {"x","y"} with leading
      support dim; beta: client learning rate (fp32 scalar).
  server_aggregate(phi, client_results, alpha_t, beta) -> phi
      client_results: result_tree with a leading clients_per_round axis;
      alpha_t: the (possibly annealed) server rate for this round.

Heterogeneity-scheduled runs (any ``SamplingPolicy`` whose
``schedule_kind`` != "uniform", see repro.core.pipeline) use the
schedule-aware variants instead:

  client_update_steps(phi, client_batch, beta, k)
      k: this client's TRACED local step budget from the round's
      ClientSchedule, in the strategy's own units (stream samples for
      TinyReptile, epochs for Reptile/FedAVG). The default ignores k —
      right for one-shot workloads (FedSGD's single gradient, Transfer's
      raw-batch forward) that have no straggler axis.
  server_aggregate_weighted(phi, client_results, alpha_t, beta, weights,
                            axis_name=None)
      weights: (clients,) per-round-normalized aggregation weights
      (0 for non-participants) — partial participation, arrival-weighted
      straggler aggregation, AND FedBuff-style buffered flushes
      (repro.core.pool.BufferedAggregation: the buffered updates arrive
      with a leading buffer-capacity axis and staleness-discounted
      weights, zeros on empty slots) all reduce to this one hook.
      ``axis_name`` is the COLLECTIVE form (mesh-sharded engine runs,
      see run_federated(mesh=...)): client_results and weights then
      carry only this device's cohort shard, and the hook must reduce
      the weighted sum across the named mesh axis (``psum``) — routing
      through ``weighted_client_mean(..., axis_name=...)`` gives that
      for free. ``axis_name=None`` (the default, and the only form the
      engine uses when mesh is None) is bit-for-bit the pre-mesh hook.
  local_step_budget(support) -> int
      The full per-client workload in scheduler units; scheduling
      policies draw each k_i from [1, budget].

A new algorithm is one strategy object — not a new file-long loop.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.engine import meta_interpolate, resolve_use_pallas
from repro.core.meta import (finetune_batch, finetune_batch_masked,
                             finetune_online, finetune_online_masked)
from repro.kernels import ref as kref


def weighted_client_mean(trees, weights, axis_name=None):
    """sum_c weights[c] * tree_c along the leading clients axis, in fp32.
    With per-round-normalized weights this is the participation-weighted
    client mean (uniform weights 1/C recover the plain mean).

    Zero-weight clients are truly INERT: their results are zeroed before
    the sum, so a scheduled-out client whose hook still ran on its
    zeroed batch (one-shot strategies ignore local_steps) cannot poison
    the round with a NaN/inf — 0 * NaN would otherwise be NaN.

    ``axis_name`` is the collective form for mesh-sharded runs: the
    leading axis then holds only this device's cohort shard (weights
    likewise), and the local partial sum is all-reduced across the
    named mesh axis. Because the weights are normalized over the FULL
    cohort, psum of the per-shard partial sums IS the global weighted
    mean. The per-leaf partials go through ONE multi-operand ``psum``
    (a single psum primitive bind over the whole tree -> a single
    all-reduce) — XLA CPU (and most backends) execute each all-reduce
    as its own synchronization, so per-leaf psum CALLS would pay one
    cross-device rendezvous per parameter tensor per round. The
    per-leaf form (vs the old flatten-and-concatenate into one vector)
    sums the same elements in the same cross-device order — bitwise
    identical — while preserving each leaf's shape AND sharding: on a
    2-D (clients, model) mesh the partials of model-sharded leaves
    reduce over the clients axis IN PLACE, where the concat would
    force an all-gather of every shard onto every device."""
    def local_sum(q):
        qf = q.astype(jnp.float32)
        w = weights.reshape((-1,) + (1,) * (qf.ndim - 1))
        return jnp.sum(w * jnp.where(w > 0, qf, 0.0), axis=0)
    local = jax.tree.map(local_sum, trees)
    if axis_name is None or not jax.tree.leaves(local):
        return local
    return jax.lax.psum(local, axis_name)


def reptile_aggregate(phi, phi_hats, alpha_t, *,
                      use_pallas: Optional[bool] = None):
    """Server update shared by TinyReptile (C=1) and batched Reptile:
    phi <- phi + alpha_t * (mean_c(phi_hat_c) - phi). The client mean is
    taken in fp32; the interpolation (dtype policy, Pallas routing) is
    engine.meta_interpolate's."""
    mean = jax.tree.map(
        lambda q: jnp.mean(q.astype(jnp.float32), axis=0), phi_hats)
    return meta_interpolate(phi, mean, alpha_t, use_pallas=use_pallas)


def reptile_aggregate_weighted(phi, phi_hats, alpha_t, weights, *,
                               use_pallas: Optional[bool] = None,
                               axis_name=None):
    """Participation/arrival-weighted Reptile server update:
    phi <- phi + alpha_t * (sum_c w_c phi_hat_c - phi). Weights are the
    round's normalized ClientSchedule weights; zero-weight (scheduled
    out) clients contribute nothing. ``axis_name`` reduces the weighted
    client mean across a mesh axis (sharded cohorts / pod clients)."""
    mean = weighted_client_mean(phi_hats, weights, axis_name=axis_name)
    return meta_interpolate(phi, mean, alpha_t, use_pallas=use_pallas)


@dataclasses.dataclass(frozen=True)
class FedStrategy:
    """Base strategy. Subclasses set the class attributes and hooks."""
    loss_fn: Callable

    data_mode = "batch"          # "batch" | "stream" client data layout
    meters_comm = True           # account CommChannel bytes + report them
    tracks_inner_loss = False    # report last-round client loss at evals
    uplink_ref = "params"        # what a partial uplink falls back to for
    #                              untransmitted entries: "params" (the
    #                              broadcast phi — model-returning
    #                              uplinks), "zeros" (gradient uplinks),
    #                              or "none" (no reference; transmit the
    #                              result tree as-is)
    payload_dtype = "float32"    # wire dtype of the client result tree.
    #                              "float32" (default) leaves transport
    #                              simulation to the CommChannel;
    #                              anything else declares NATIVE
    #                              quantized uplinks — the engine then
    #                              requires a matching non-simulating
    #                              channel (e.g. CommChannel("int8",
    #                              quantize=False)) so bytes are billed
    #                              at the true rate and the channel never
    #                              re-quantizes already-integer payloads

    def uplink_template(self, phi):
        """A zero-cost template tree with the SHAPES/DTYPES of this
        strategy's client result (what client_update returns), given the
        broadcast phi. The engine sizes FedBuff buffer slabs from it, so
        quantized strategies stage int8 updates at int8 width. Default:
        phi itself (model- and gradient-shaped uplinks)."""
        return phi

    def client_update(self, phi, client_batch, beta):
        raise NotImplementedError

    def server_aggregate(self, phi, client_results, alpha_t, beta):
        raise NotImplementedError

    def local_step_budget(self, support: int) -> int:
        """Full per-client workload in scheduler units. Default: one
        unit per support sample (stream strategies); epoch-loop and
        one-shot strategies override."""
        return support

    def client_update_steps(self, phi, client_batch, beta, k):
        """Schedule-aware client hook: honor a traced local step budget
        k. Default ignores k (one-shot workloads); strategies with a
        real local loop mask steps >= k via the lax.cond machinery."""
        del k
        return self.client_update(phi, client_batch, beta)

    def server_aggregate_weighted(self, phi, client_results, alpha_t,
                                  beta, weights, axis_name=None):
        raise NotImplementedError(
            f"{type(self).__name__} does not implement weighted "
            "aggregation; define server_aggregate_weighted to run under "
            "scheduled sampling policies (partial participation / "
            "stragglers) — accept axis_name=None too if the strategy "
            "should run on a client-sharded mesh")


@dataclasses.dataclass(frozen=True)
class TinyReptileStrategy(FedStrategy):
    """Paper Algorithm 1: the client consumes its support STREAM one
    sample at a time (online SGD); the server interpolates toward the
    returned phi_hat."""
    use_pallas: Optional[bool] = None

    data_mode = "stream"
    tracks_inner_loss = True

    def client_update(self, phi, client_batch, beta):
        return finetune_online(self.loss_fn, phi,
                               client_batch["x"], client_batch["y"], beta)

    def client_update_steps(self, phi, client_batch, beta, k):
        """Straggler clients consume only their first k stream samples."""
        return finetune_online_masked(self.loss_fn, phi, client_batch["x"],
                                      client_batch["y"], beta, k)

    def server_aggregate(self, phi, client_results, alpha_t, beta):
        return reptile_aggregate(phi, client_results, alpha_t,
                                 use_pallas=self.use_pallas)

    def server_aggregate_weighted(self, phi, client_results, alpha_t,
                                  beta, weights, axis_name=None):
        return reptile_aggregate_weighted(phi, client_results, alpha_t,
                                          weights,
                                          use_pallas=self.use_pallas,
                                          axis_name=axis_name)


@dataclasses.dataclass(frozen=True)
class ReptileStrategy(FedStrategy):
    """Reptile [Nichol et al. 2018]: the client trains on its whole
    support set for E epochs; server averages pseudo-gradients. C=1 is
    serial Reptile, C>1 batched Reptile."""
    epochs: int = 8
    use_pallas: Optional[bool] = None

    tracks_inner_loss = True

    def client_update(self, phi, client_batch, beta):
        return finetune_batch(self.loss_fn, phi, client_batch,
                              self.epochs, beta)

    def local_step_budget(self, support):
        return self.epochs

    def client_update_steps(self, phi, client_batch, beta, k):
        """Straggler clients complete only their first k local epochs."""
        return finetune_batch_masked(self.loss_fn, phi, client_batch,
                                     self.epochs, beta, k)

    def server_aggregate(self, phi, client_results, alpha_t, beta):
        return reptile_aggregate(phi, client_results, alpha_t,
                                 use_pallas=self.use_pallas)

    def server_aggregate_weighted(self, phi, client_results, alpha_t,
                                  beta, weights, axis_name=None):
        return reptile_aggregate_weighted(phi, client_results, alpha_t,
                                          weights,
                                          use_pallas=self.use_pallas,
                                          axis_name=axis_name)


@dataclasses.dataclass(frozen=True)
class FedAvgStrategy(FedStrategy):
    """FedAVG [McMahan et al. 2016]: E local epochs, server averages the
    MODELS (the Eq.-2 objective the paper shows failing in the meta
    regime)."""
    epochs: int = 8

    def client_update(self, phi, client_batch, beta):
        return finetune_batch(self.loss_fn, phi, client_batch,
                              self.epochs, beta)

    def local_step_budget(self, support):
        return self.epochs

    def client_update_steps(self, phi, client_batch, beta, k):
        return finetune_batch_masked(self.loss_fn, phi, client_batch,
                                     self.epochs, beta, k)

    def server_aggregate(self, phi, client_results, alpha_t, beta):
        n = jax.tree.leaves(client_results)[0].shape[0]
        return jax.tree.map(lambda q: q.sum(0) / n, client_results)

    def server_aggregate_weighted(self, phi, client_results, alpha_t,
                                  beta, weights, axis_name=None):
        """Weighted model average over the participating clients only."""
        avg = weighted_client_mean(client_results, weights,
                                   axis_name=axis_name)
        return jax.tree.map(lambda p, q: q.astype(p.dtype), phi, avg)


@dataclasses.dataclass(frozen=True)
class FedSGDStrategy(FedStrategy):
    """FedSGD: every client ships ONE gradient; the server applies the
    mean with the client rate beta."""

    uplink_ref = "zeros"         # untransmitted gradient entries are 0

    def client_update(self, phi, client_batch, beta):
        loss, g = jax.value_and_grad(self.loss_fn)(phi, client_batch)
        return g, loss

    def local_step_budget(self, support):
        return 1                 # one gradient: no straggler axis

    def server_aggregate(self, phi, client_results, alpha_t, beta):
        n = jax.tree.leaves(client_results)[0].shape[0]
        return jax.tree.map(
            lambda p, g: p - beta * g.sum(0) / n, phi, client_results)

    def server_aggregate_weighted(self, phi, client_results, alpha_t,
                                  beta, weights, axis_name=None):
        """Apply the participation-weighted mean gradient."""
        g = weighted_client_mean(client_results, weights,
                                 axis_name=axis_name)
        return jax.tree.map(
            lambda p, gg: (p - beta * gg).astype(p.dtype), phi, g)


@dataclasses.dataclass(frozen=True)
class TransferStrategy(FedStrategy):
    """Joint-training baseline (paper Fig. 1): clients just forward their
    raw batches; the server takes one SGD step on the pooled data. No
    federation, so no comm accounting."""

    meters_comm = False
    uplink_ref = "none"          # raw-data uplink: no phi-shaped reference

    def client_update(self, phi, client_batch, beta):
        return client_batch, jnp.zeros(())

    def local_step_budget(self, support):
        return 1                 # raw-batch forward: no straggler axis

    def server_aggregate(self, phi, client_results, alpha_t, beta):
        pooled = jax.tree.map(
            lambda a: a.reshape(-1, *a.shape[2:]), client_results)
        g = jax.grad(self.loss_fn)(phi, pooled)
        return jax.tree.map(lambda w, gg: w - beta * gg, phi, g)

    def server_aggregate_weighted(self, phi, client_results, alpha_t,
                                  beta, weights, axis_name=None):
        """Per-client pool gradients, weighted — scheduled-out clients'
        (zeroed) batches get weight 0 instead of polluting the pool.
        Mathematically the pooled-gradient with client weights; not
        bitwise the unweighted pool (sum order differs)."""
        grads = jax.vmap(
            lambda b: jax.grad(self.loss_fn)(phi, b))(client_results)
        g = weighted_client_mean(grads, weights, axis_name=axis_name)
        return jax.tree.map(
            lambda w, gg: (w - beta * gg).astype(w.dtype), phi, g)


# ---------------------------------------------------------------------------
# TIFeD: integer-only local training with direct feedback alignment
# ---------------------------------------------------------------------------

# Static exponent policy (powers of two throughout, so every requant
# multiplier is an exact fp32 scaling and quantization error is pure
# rounding): inputs land on the 2^EX grid (sine x in [-5, 5] fits int8
# at 2^-4 — the MCU-realistic a-priori input scale), hidden activations
# on 2^ACT as unsigned 7-bit, and the quantized error SERR grid-steps
# below the output accumulator. Weight exponents are tracked per tensor
# (kref.pow2_exponent); biases live at accumulator scale (int32,
# clipped to +-2^23 so downstream products stay fp32-exact).
TIFED_EX = -4
TIFED_ACT = -3
TIFED_SERR = -5


@functools.lru_cache(maxsize=32)
def _tifed_constants(seed, epochs, dims):
    """Fixed DFA feedback matrices + per-epoch stochastic-rounding
    dither planes, as NumPy so they bake into the jit trace as
    constants — stochastic rounding at zero runtime cost. The dither is
    shared across the round's clients (it is a fresh draw per epoch and
    per weight entry, so each client's requantization stays unbiased;
    clients are not mutually decorrelated — documented in
    docs/PLUGINS.md §6)."""
    din, h1, h2, dout = dims
    npr = np.random.default_rng(seed)
    fb = tuple(np.asarray(npr.integers(-127, 128, (dout, h)), np.float32)
               for h in (h1, h2))
    dith = tuple(np.asarray(npr.random((epochs, a, b)), np.float32)
                 for a, b in ((din, h1), (h1, h2), (h2, dout)))
    return fb, dith


def tifed_dequantize(result):
    """Client result tree -> fp32 params: q * 2^exp per leaf (weight
    leaves carry their per-tensor exponent, biases their accumulator
    scale)."""
    out = {}
    for k, q in result["q"].items():
        e = result["exp"][k].astype(jnp.float32)
        out[k] = q.astype(jnp.float32) * jnp.exp2(
            e.reshape(e.shape + (1,) * (q.ndim - e.ndim)))
    return out


def tifed_requantize(phi):
    """Snap fp32 phi back onto the integer grids (weights to their
    per-tensor int8 grid, biases to the matching accumulator grid), so
    the phi the scan carries is always exactly representable — the
    value every client would reconstruct from an int8 broadcast."""
    out = {}
    for i, ea in enumerate((TIFED_EX, TIFED_ACT, TIFED_ACT)):
        q, e = kref.quantize_pow2(phi[f"w{i}"])
        ef = e.astype(jnp.float32)
        out[f"w{i}"] = q * jnp.exp2(ef)
        eb = ef + ea
        out[f"b{i}"] = jnp.clip(
            jnp.round(phi[f"b{i}"] * jnp.exp2(-eb)),
            -kref.BIAS_MAX, kref.BIAS_MAX) * jnp.exp2(eb)
    return out


@dataclasses.dataclass(frozen=True)
class TifedStrategy(FedStrategy):
    """TIFeD [arXiv 2307.03102]: integer-only local training with direct
    feedback alignment, as a first-class engine strategy.

    Clients never touch fp32 weights: phi is quantized to per-tensor
    power-of-two int8 grids, and each local epoch runs an int8 forward
    pass with int32 accumulation, projects the quantized output error
    straight to one layer through a fixed random feedback matrix (no
    backprop transposes), and requantizes that layer's update to int8
    with stochastic rounding (the layer-cyclic single-layer variant:
    epoch t trains layer t mod 3). Learning rates are pure bit-shifts —
    ``lr_shift`` plus log2(support) folds the batch mean in.

    The uplink is the NATIVE int8/int32 result tree
    ``{"q": {w*, b*}, "exp": {w*, b*}}`` (payload_dtype="int8" — the
    engine bills it at 1 byte/param through a non-simulating
    CommChannel("int8", quantize=False); the six scalar exponents ride
    free like PartialCommChannel's chunk-index side channel). The
    server dequantizes, takes the weighted client mean in the same
    single fused psum as every other strategy, Reptile-interpolates,
    and snaps phi back onto the integer grid — so int8 runs keep both
    engine invariants and compose with pool/FedBuff/mesh/schedules
    unchanged.

    ``loss_fn`` is only used by the engine's fp32 eval finetune (use
    ``models.paper_nets.relu_mlp_loss``: the integer forward is a ReLU
    MLP, not the tanh paper net). Eval finetune rates above ~0.01
    diverge on the ReLU net at k_steps >= 16; the tifed_train wrapper
    defaults accordingly. ``use_pallas`` routes each epoch through the
    fused ``kernels/online_sgd_int8.py`` kernel (None = TPU only; CPU
    uses the oracle math, which XLA fuses at the floor)."""
    epochs: int = 8
    lr_shift: int = 6
    feedback_seed: int = 0
    unroll: int = 2
    use_pallas: Optional[bool] = None

    tracks_inner_loss = True
    payload_dtype = "int8"

    @staticmethod
    def _dims(phi):
        for i in range(3):
            if f"w{i}" not in phi or f"b{i}" not in phi:
                raise ValueError(
                    "TifedStrategy expects the paper MLP pytree "
                    "{w0,b0,w1,b1,w2,b2} (models.paper_nets); got keys "
                    f"{sorted(phi)}")
        return (phi["w0"].shape[0], phi["w0"].shape[1],
                phi["w1"].shape[1], phi["w2"].shape[1])

    def uplink_template(self, phi):
        self._dims(phi)
        q = {f"w{i}": jnp.zeros(phi[f"w{i}"].shape, jnp.int8)
             for i in range(3)}
        q.update({f"b{i}": jnp.zeros(phi[f"b{i}"].shape, jnp.int32)
                  for i in range(3)})
        return {"q": q, "exp": {k: jnp.zeros((), jnp.int32) for k in q}}

    def _run_epochs(self, phi, client_batch, k):
        dims = self._dims(phi)
        x = client_batch["x"].reshape(-1, dims[0])
        y = client_batch["y"].reshape(x.shape[0], dims[3])
        n = x.shape[0]
        # fold the 1/n batch mean into the shift (exact for pow2 n)
        lrs = self.lr_shift + int(np.floor(np.log2(n)))
        fb_np, dith_np = _tifed_constants(self.feedback_seed, self.epochs,
                                          dims)
        fb = tuple(jnp.asarray(f) for f in fb_np)
        dith = tuple(jnp.asarray(d) for d in dith_np)

        f32 = jnp.float32
        ws, ew = [], []
        for i in range(3):
            q, e = kref.quantize_pow2(phi[f"w{i}"])
            ws.append(q)
            ew.append(e)
        ea = (TIFED_EX, TIFED_ACT, TIFED_ACT)
        sacc = [ew[i] + ea[i] for i in range(3)]
        bs = [jnp.clip(jnp.round(phi[f"b{i}"]
                                 * jnp.exp2(-sacc[i].astype(f32))),
                       -kref.BIAS_MAX, kref.BIAS_MAX) for i in range(3)]
        xq = jnp.clip(jnp.round(x * 2.0 ** -TIFED_EX), -127.0, 127.0)
        yal = jnp.round(y * jnp.exp2(-sacc[2].astype(f32)))
        scales = {
            "f0": jnp.exp2((sacc[0] - TIFED_ACT).astype(f32)),
            "f1": jnp.exp2((sacc[1] - TIFED_ACT).astype(f32)),
            "fe": jnp.exp2((sacc[2] - TIFED_SERR).astype(f32)),
            "floss": jnp.exp2(2.0 * sacc[2].astype(f32)) / n,
            "ftw": tuple(
                jnp.exp2((ea[i] + TIFED_SERR - ew[i] - lrs).astype(f32))
                for i in range(3)),
            "ftb": tuple(
                jnp.exp2((TIFED_SERR - sacc[i] - lrs).astype(f32))
                for i in range(3)),
        }
        if resolve_use_pallas(self.use_pallas):
            from repro.kernels import ops as kops
            epoch_fn = kops.dfa_epoch_int8
            init = (tuple(w.astype(jnp.int8) for w in ws),
                    tuple(b.astype(jnp.int32) for b in bs))
            xq_n, yal_n = xq.astype(jnp.int8), yal.astype(jnp.int32)
        else:
            epoch_fn = kref.dfa_int8_epoch
            init = (tuple(ws), tuple(bs))
            xq_n, yal_n = xq, yal

        def run_one(carry, layer, dither):
            cw, cb = carry
            nw, nb, loss = epoch_fn(cw, cb, xq_n, yal_n, layer, fb,
                                    dither, scales)
            return (nw, nb), loss

        def epoch(carry, xs):
            if k is None:
                layer, d0, d1, d2 = xs
                return run_one(carry, layer, (d0, d1, d2))
            idx, layer, d0, d1, d2 = xs
            return jax.lax.cond(
                idx < k,
                lambda c: run_one(c, layer, (d0, d1, d2)),
                lambda c: (c, jnp.float32(0.0)), carry)

        layers = jnp.arange(self.epochs, dtype=jnp.int32) % 3
        xs = (layers,) + dith
        if k is not None:
            xs = (jnp.arange(self.epochs, dtype=jnp.int32),) + xs
        (cw, cb), losses = jax.lax.scan(epoch, init, xs,
                                        unroll=self.unroll)
        result = {
            "q": {"w0": cw[0].astype(jnp.int8),
                  "w1": cw[1].astype(jnp.int8),
                  "w2": cw[2].astype(jnp.int8),
                  "b0": cb[0].astype(jnp.int32),
                  "b1": cb[1].astype(jnp.int32),
                  "b2": cb[2].astype(jnp.int32)},
            "exp": {"w0": ew[0], "w1": ew[1], "w2": ew[2],
                    "b0": sacc[0], "b1": sacc[1], "b2": sacc[2]},
        }
        return result, losses

    def client_update(self, phi, client_batch, beta):
        del beta                      # learning rate is the bit-shift
        return self._run_epochs(phi, client_batch, None)

    def local_step_budget(self, support):
        return self.epochs

    def client_update_steps(self, phi, client_batch, beta, k):
        """Straggler clients complete only their first k integer epochs
        (masked epochs pass the carry through and report loss 0, which
        the engine's weighted round loss expects)."""
        del beta
        return self._run_epochs(phi, client_batch, k)

    def server_aggregate(self, phi, client_results, alpha_t, beta):
        deq = jax.vmap(tifed_dequantize)(client_results)
        mean = jax.tree.map(lambda q: jnp.mean(q, axis=0), deq)
        return tifed_requantize(meta_interpolate(phi, mean, alpha_t))

    def server_aggregate_weighted(self, phi, client_results, alpha_t,
                                  beta, weights, axis_name=None):
        """Quantization-aware weighted aggregation: dequantize each
        client's int8 tree, weighted-mean in the SAME single fused psum
        as the fp32 strategies (the dequantized leaves join
        weighted_client_mean's one multi-operand all-reduce),
        Reptile-interpolate,
        requantize phi back onto the integer grid."""
        deq = jax.vmap(tifed_dequantize)(client_results)
        mean = weighted_client_mean(deq, weights, axis_name=axis_name)
        return tifed_requantize(meta_interpolate(phi, mean, alpha_t))
