"""The federated round engine: one pipelined loop for every core/ algorithm.

Historically each algorithm file (tinyreptile, reptile, fedavg, fedsgd,
transfer) hand-rolled the same Python-side server loop — client sampling,
comm-byte metering, annealing, eval cadence — and paid one host->device
dispatch per client per round. This module owns all of that once:

  run_federated(init_params, task_dist, strategy, ...)

* A ``FedStrategy`` (see repro.core.strategies) supplies the two
  algorithm-specific hooks: ``client_update`` (what one device does with
  the broadcast parameters and its local data) and ``server_aggregate``
  (how the server folds the client results back into phi).
* Rounds execute as fixed-shape on-device blocks: ``jax.vmap`` across the
  clients_per_round axis and ``jax.lax.scan`` across the rounds between
  evals, with the parameter buffers donated between blocks. Every block —
  including the uneven eval-boundary tail — is padded on the host to ONE
  per-run length and carries a per-round validity mask (``lax.cond``
  skips padded rounds at runtime), so the block runner compiles exactly
  once per (strategy, beta, channel) config; ``_BlockRunner.trace_count``
  makes that observable.
* The host side is a producer/consumer pipeline (repro.core.pipeline):
  per-round round state is a structured ``ClientSchedule`` (participation
  mask, per-client local step counts, aggregation weights, absolute
  round index) planned by a pluggable ``SamplingPolicy`` — uniform
  i.i.d. by default (with a legacy-exact "reference" RNG order and a
  vectorized one-allocation fast path), ``PartialParticipation`` and
  ``StragglerSampling`` as deployment-scenario plugins — and a
  background prefetch thread plans, samples, and ``device_put``s block
  N+1 while the device runs block N (double buffered). ``prefetch=0``
  is the synchronous escape hatch; pipelined and synchronous runs are
  bit-for-bit identical because the producer consumes the host RNG in
  exactly the synchronous block order.
* A pluggable ``CommChannel`` does the paper's Table-II byte accounting
  for fp32/fp16/int8 payloads and can optionally *simulate* the quantized
  transport (int8 motivated by TIFeD's integer-based FL).
  ``PartialCommChannel`` additionally transmits only a per-round
  parameter FRACTION (TinyMetaFed-style partial communication): masked
  uplink deltas plus fraction-scaled accounting, billed per
  participating client, with optional per-round rotating masks that
  cover every parameter entry once per ``ceil(1/fraction)`` rounds.
* Persistent identities are one layer up: a ``repro.core.pool.
  ClientPool`` gives every client a stable task/data shard and a
  cross-round state pytree (last-seen round, staleness counters, the
  FedBuff pending-update buffer) that rides the scan carry next to phi
  and is gathered/scattered by the round's cohort indices inside the
  scan. ``BufferedAggregation`` makes aggregation FedBuff-style async
  (flush every K arrivals, staleness-discounted weights);
  ``DiurnalAvailability`` / ``MarkovAvailability`` drive who checks in.
  ``pool=None`` keeps the legacy anonymous-cohort path bit-for-bit.
* The server update routes through the fused Pallas kernel
  (``repro.kernels.ops.meta_update``) by default on TPU backends;
  elsewhere the same fp32 math runs as plain XLA (the kernel would only
  interpret there), and so it does on 2-D meshes, whose GSPMD-partitioned
  blocks cannot hold a Mosaic kernel (``resolve_use_pallas``).
* ``run_federated(..., mesh=...)`` SHARDS THE CLIENT AXIS across a
  device mesh: the block runner wraps its scan in ``shard_map`` (manual
  over a 1-D "clients" mesh axis), each device vmaps over its local
  cohort shard, and server aggregation becomes a weighted all-reduce
  (``server_aggregate_weighted(..., axis_name="clients")`` — a masked
  psum of per-shard partial sums). The round scan carries REPLICATED
  phi next to the client-sharded ``ClientSchedule`` and ``PoolState``;
  cohorts are padded to a multiple of the shard count via the existing
  validity/participation masks, so uneven cohorts never retrace, and
  the two hot-path invariants survive sharding: zero per-round host
  dispatches and one jit trace per (strategy, beta, channel,
  schedule-shape, pool-shape, mesh) config. ``mesh=None`` (the
  default) is bit-for-bit the single-device engine.

``meta_interpolate`` and ``streaming_sgd`` are the engine's round
building blocks, shared with the mesh-scale cohort step in
``repro.runtime.steps``. Jitted block runners are memoized per
(strategy, beta, channel); ``runner_cache_stats`` / ``clear_runner_cache``
expose and reset that cache (long sweeps over many configs would
otherwise pin up to 64 stale executables).
"""
from __future__ import annotations

import collections
import contextlib
import copy
import dataclasses
import inspect
import logging
import math
import threading
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.checkpoint.ckpt import (AsyncCheckpointWriter, RoundState,
                                   restore_round_state, save_round_state)
from repro.core.meta import evaluate_init
from repro.core.pipeline import (SAMPLERS, ClientSchedule, SamplingPolicy,
                                 UniformSampling, block_shardings,
                                 plan_blocks, prefetch_items,
                                 single_device_of)
from repro.core.pool import (BufferedAggregation, ClientPool, PoolState,
                             pool_state_specs)
from repro.data.tasks import TaskDistribution

logger = logging.getLogger(__name__)

#: the engine's mesh axis: run_federated(mesh=...) shards the per-round
#: cohort over it (see client_mesh).
CLIENT_AXIS = "clients"

#: bytes per parameter for each transport payload dtype (paper Table II
#: generalized: the paper ships fp32; fp16/int8 model compressed uplinks).
PAYLOAD_ITEMSIZE = {"float32": 4, "float16": 2, "int8": 1}


#: set while a GSPMD-partitioned (2-D mesh) block body traces
_GSPMD_TRACE = threading.local()


@contextlib.contextmanager
def _gspmd_trace():
    prev = getattr(_GSPMD_TRACE, "on", False)
    _GSPMD_TRACE.on = True
    try:
        yield
    finally:
        _GSPMD_TRACE.on = prev


def resolve_use_pallas(use_pallas: Optional[bool] = None) -> bool:
    """A ``use_pallas`` option as given, or, for None, whether the Pallas
    kernels compile natively here (a TPU backend; elsewhere they would
    only interpret). Inside a 2-D mesh's GSPMD block body the answer is
    the XLA route: the compiler cannot partition a Mosaic kernel over
    model-sharded operands, so an explicit True is refused there."""
    if getattr(_GSPMD_TRACE, "on", False):
        if use_pallas:
            raise ValueError(
                "use_pallas=True on a model-sharded mesh: Mosaic kernels "
                "cannot be partitioned automatically; leave use_pallas at "
                "None there")
        return False
    if use_pallas is None:
        return jax.default_backend() == "tpu"
    return use_pallas


def client_mesh(devices=None) -> Mesh:
    """A 1-D device mesh over the engine's client axis ("clients").

    ``devices``: None uses every ``jax.devices()``; an int takes the
    first n; a sequence of Devices is used as given. Pass the result
    (or just the int / "auto") to ``run_federated(mesh=...)`` to shard
    each round's cohort across the devices.
    """
    if devices is None:
        devs = jax.devices()
    elif isinstance(devices, int):
        avail = jax.devices()
        if not 1 <= devices <= len(avail):
            raise ValueError(f"client_mesh asked for {devices} devices; "
                             f"this process has {len(avail)} (forcing "
                             f"host devices needs XLA_FLAGS="
                             f"--xla_force_host_platform_device_count)")
        devs = avail[:devices]
    else:
        devs = list(devices)
    return Mesh(np.array(devs), (CLIENT_AXIS,))


#: the optional second mesh axis: a 2-D (clients, model) mesh
#: additionally shards phi's weight matrices per the run's
#: ModelPartitioner (see repro.runtime.sharding.client_model_mesh).
MODEL_AXIS = "model"


def _resolve_mesh(mesh) -> Optional[Mesh]:
    """Normalize run_federated's mesh argument: None passes through,
    "auto" builds a mesh over every device, an int over the first n,
    and an explicit Mesh must be 1-D over the "clients" axis or 2-D
    over ("clients", "model")."""
    if mesh is None:
        return None
    if mesh == "auto":
        return client_mesh()
    if isinstance(mesh, int):
        return client_mesh(mesh)
    if tuple(mesh.axis_names) not in ((CLIENT_AXIS,),
                                      (CLIENT_AXIS, MODEL_AXIS)):
        raise ValueError(
            f"run_federated shards the cohort over a '{CLIENT_AXIS}' "
            f"mesh axis — 1-D ('{CLIENT_AXIS}',) or 2-D ('{CLIENT_AXIS}', "
            f"'{MODEL_AXIS}'); got axes {tuple(mesh.axis_names)} (build "
            f"one with repro.core.engine.client_mesh / "
            f"repro.runtime.sharding.client_model_mesh, or pass an int / "
            f"'auto')")
    return mesh


def _model_sharded(mesh) -> bool:
    return mesh is not None and MODEL_AXIS in mesh.axis_names


def meta_interpolate(phi, phi_hat, alpha, *, use_pallas: Optional[bool] = None):
    """Reptile server update phi <- phi + alpha (phi_hat - phi), fp32 math,
    cast back to each leaf's storage dtype. Routed through the fused Pallas
    kernel when `use_pallas` (default: on TPU)."""
    if resolve_use_pallas(use_pallas):
        from repro.kernels import ops as kops
        return jax.tree.map(
            lambda p, q: kops.meta_update(p, q, alpha), phi, phi_hat)
    return jax.tree.map(
        lambda p, q: (p.astype(jnp.float32)
                      + alpha * (q.astype(jnp.float32)
                                 - p.astype(jnp.float32))).astype(p.dtype),
        phi, phi_hat)


def streaming_sgd(loss_fn, phi, batch, beta):
    """The inner loop: one SGD step per arriving microbatch (the paper's
    online learning), scanned on-device; fp32 update math, params cast
    back to their storage dtype. In probe mode the scan unrolls so XLA
    cost analysis counts every step (see repro.runtime.flags)."""
    def inner(phi_hat, micro):
        loss, g = jax.value_and_grad(loss_fn)(phi_hat, micro)
        phi_hat = jax.tree.map(
            lambda p, gg: (p.astype(jnp.float32)
                           - beta * gg.astype(jnp.float32)).astype(p.dtype),
            phi_hat, g)
        return phi_hat, loss

    from repro.runtime.flags import probe_mode
    if probe_mode():
        k = jax.tree.leaves(batch)[0].shape[0]
        phi_hat, losses = phi, []
        for i in range(k):
            micro = jax.tree.map(lambda a: a[i], batch)
            phi_hat, l = inner(phi_hat, micro)
            losses.append(l)
        return phi_hat, jnp.stack(losses)
    return jax.lax.scan(inner, phi, batch)


@dataclasses.dataclass(frozen=True)
class CommChannel:
    """Server<->client transport: byte accounting + optional quantization.

    dtype: payload dtype on the wire ("float32" | "float16" | "int8").
      Accounting scales `tree_bytes` by the itemsize ratio — the paper's
      Table II generalized beyond fp32.
    quantize: simulate the lossy payload in-round (cast round-trip for
      fp16, per-leaf symmetric affine quantization for int8). Default:
      quantize iff dtype != float32. Accounting-only studies can set
      quantize=False to meter a compressed link while training in fp32;
      quantize=True on an fp32 wire is rejected (an exact wire has
      nothing to simulate).
    """
    dtype: str = "float32"
    quantize: Optional[bool] = None

    #: set on subclasses whose transmit() needs the engine to pass a
    #: server-side reference tree for the uplink (delta-style transports).
    needs_uplink_ref = False

    def __post_init__(self):
        if self.dtype not in PAYLOAD_ITEMSIZE:
            raise ValueError(f"unknown payload dtype {self.dtype!r}; "
                             f"expected one of {sorted(PAYLOAD_ITEMSIZE)}")
        if self.quantize and self.dtype == "float32":
            raise ValueError("quantize=True with an fp32 wire: the payload "
                             "is exact, there is no quantization to "
                             "simulate (drop quantize or pick fp16/int8)")

    @property
    def simulates_quantization(self) -> bool:
        if self.quantize is None:
            return self.dtype != "float32"
        return self.quantize

    def payload_bytes(self, tree) -> int:
        """One direction, one client: every leaf at the wire itemsize."""
        itemsize = PAYLOAD_ITEMSIZE[self.dtype]
        return sum(x.size * itemsize for x in jax.tree.leaves(tree))

    def payload_bytes_at(self, tree, round_index: int) -> int:
        """Per-round exact payload. Equal to ``payload_bytes`` for every
        channel except rotating partial masks, whose per-round payload
        is the round's chunk (see PartialCommChannel)."""
        del round_index
        return self.payload_bytes(tree)

    def round_bytes(self, tree, clients: int) -> int:
        """Downlink (phi out) + uplink (result back) for every client."""
        return 2 * clients * self.payload_bytes(tree)

    def _wire(self, tree):
        """Simulated dtype round-trip (encode + decode), jax-traceable.
        The fp32 wire is exact."""
        if self.dtype == "float16":
            return jax.tree.map(
                lambda x: x.astype(jnp.float16).astype(x.dtype), tree)
        if self.dtype == "int8":
            def q_int8(x):
                scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-8) / 127.0
                q = jnp.round(x / scale).astype(jnp.int8)
                return (q.astype(x.dtype) * scale).astype(x.dtype)
            return jax.tree.map(q_int8, tree)
        return tree

    def transmit(self, tree, ref=None, masks=None, round_index=None):
        """Simulated wire round-trip. ``ref`` is the engine-provided
        server-side reference tree for delta-style transports, ``masks``
        a precomputed keep-mask tree, and ``round_index`` the absolute
        round for rotating masks (see PartialCommChannel); the base
        channel ignores all three."""
        del ref, masks, round_index
        if not self.simulates_quantization:
            return tree
        return self._wire(tree)


@dataclasses.dataclass(frozen=True)
class PartialCommChannel(CommChannel):
    """TinyMetaFed-style partial communication: each round only a fixed
    FRACTION of the parameter vector crosses the wire.

    Accounting: per leaf, ``kept_entries(n) = max(1, round(fraction*n))``
    entries at the wire itemsize, both directions. The kept-index set is
    derived deterministically from ``mask_seed`` (shared by both ends),
    so no index side-channel is metered.

    Simulation: on the uplink the engine passes a server-side reference
    tree — kept entries carry the client result (after any base dtype
    quantization), dropped entries fall back to the reference, i.e. the
    server keeps its own value where the client sent nothing (reference =
    phi for model-returning strategies, 0 for gradient uplinks; see
    ``FedStrategy.uplink_ref``). On the downlink, transmitted entries
    ride the dtype wire (fp16/int8 quantized); untransmitted entries
    approximate the client's stale copy with the server's exact value
    (clients are stateless in this simulation). Both directions converge
    to the base channel as fraction -> 1.

    rotate=False (default): ONE fixed keep mask for the whole run, with
    exactly ``kept_entries(n) = max(1, round(fraction * n))`` entries
    per leaf. rotate=True: the mask ROTATES every round — each leaf's
    entries are split (in a fixed ``mask_seed``-keyed permutation order)
    into ``rotation_period = ceil(1/fraction)`` near-equal chunks, and
    round r transmits chunk ``r % rotation_period``, so EVERY parameter
    entry crosses the wire within one rotation period and a full period
    accounts exactly one complete tree at the wire itemsize (per-round
    chunk sizes differ by at most one entry per leaf;
    ``payload_bytes_at`` is the per-round exact meter). Both ends derive
    the round's mask from (mask_seed, round index), so no index
    side-channel is metered; inside the engine's scan the round index is
    folded in from the ClientSchedule carry — no per-round host work.
    """
    fraction: float = 0.5
    mask_seed: int = 0
    rotate: bool = False

    needs_uplink_ref = True

    def __post_init__(self):
        super().__post_init__()
        if not 0.0 < self.fraction <= 1.0:
            raise ValueError(f"fraction must be in (0, 1], got "
                             f"{self.fraction!r}")

    def kept_entries(self, n: int) -> int:
        """How many of a leaf's n entries are transmitted per round.
        Fixed masks: max(1, round(fraction * n)). Rotating masks
        transmit the round's CHUNK — 1/rotation_period of the entries,
        which only equals the fraction when 1/fraction is an integer —
        so this reports round 0's (largest) chunk and
        ``kept_entries_at`` is the per-round exact count."""
        if self.rotate:
            return self.kept_entries_at(n, 0)
        return max(1, int(round(self.fraction * n)))

    @property
    def rotation_period(self) -> int:
        """Rounds until a rotating mask has covered every entry:
        ceil(1/fraction), guarded against float noise (1/(1/3) slightly
        above 3 must still give period 3)."""
        return max(1, math.ceil(1.0 / self.fraction - 1e-9))

    def kept_entries_at(self, n: int, round_index: int) -> int:
        """Entries of an n-entry leaf transmitted at ``round_index`` under
        rotation: the size of chunk (round_index % period) in the
        balanced split (first n % period chunks get the extra entry)."""
        period = self.rotation_period
        j = round_index % period
        return n // period + (1 if j < n % period else 0)

    def payload_bytes(self, tree) -> int:
        itemsize = PAYLOAD_ITEMSIZE[self.dtype]
        return sum(self.kept_entries(x.size) * itemsize
                   for x in jax.tree.leaves(tree))

    def payload_bytes_at(self, tree, round_index: int) -> int:
        if not self.rotate:
            return self.payload_bytes(tree)
        itemsize = PAYLOAD_ITEMSIZE[self.dtype]
        return sum(self.kept_entries_at(x.size, round_index) * itemsize
                   for x in jax.tree.leaves(tree))

    @property
    def simulates_quantization(self) -> bool:
        if self.fraction < 1.0:
            return True
        return CommChannel.simulates_quantization.fget(self)

    def chunk_id_tree(self, tree):
        """Static rotation state: per leaf, an int32 array (leaf-shaped)
        assigning every entry to one of ``rotation_period`` balanced
        chunks in ``mask_seed``-keyed permutation order. Round r's keep
        mask is just ``chunk_ids == r % rotation_period`` — cheap enough
        to evaluate inside the scan with a traced round index."""
        period = self.rotation_period
        leaves, treedef = jax.tree.flatten(tree)
        key = jax.random.PRNGKey(self.mask_seed)
        ids = []
        for i, leaf in enumerate(leaves):
            n = leaf.size
            perm = jax.random.permutation(jax.random.fold_in(key, i), n)
            sizes = np.full(period, n // period, np.int32)
            sizes[: n % period] += 1
            chunk_of_pos = jnp.asarray(
                np.repeat(np.arange(period, dtype=np.int32), sizes))
            leaf_ids = jnp.zeros((n,), jnp.int32).at[perm].set(chunk_of_pos)
            ids.append(leaf_ids.reshape(leaf.shape))
        return jax.tree.unflatten(treedef, ids)

    def masks_for_round(self, chunk_ids, round_index):
        """Round ``round_index``'s keep-masks from precomputed chunk ids
        — the single source of the rotation rule (the engine's scan body
        calls this with the ClientSchedule's traced round index)."""
        phase = jnp.mod(round_index, self.rotation_period)
        return jax.tree.map(lambda ids: ids == phase, chunk_ids)

    def mask_tree(self, tree, round_index=None):
        """Boolean keep-masks, one per leaf. Fixed masks (rotate=False)
        have exactly ``kept_entries(leaf.size)`` True entries (matches
        the accounting); rotating masks select round ``round_index``'s
        chunk (default round 0). ``round_index`` may be traced."""
        if self.rotate:
            return self.masks_for_round(
                self.chunk_id_tree(tree),
                0 if round_index is None else round_index)
        leaves, treedef = jax.tree.flatten(tree)
        key = jax.random.PRNGKey(self.mask_seed)
        masks = []
        for i, leaf in enumerate(leaves):
            n = leaf.size
            perm = jax.random.permutation(jax.random.fold_in(key, i), n)
            m = jnp.zeros((n,), jnp.bool_)
            m = m.at[perm[:self.kept_entries(n)]].set(True)
            masks.append(m.reshape(leaf.shape))
        return jax.tree.unflatten(treedef, masks)

    def transmit(self, tree, ref=None, masks=None, round_index=None):
        # the base dtype simulation is gated on the BASE quantize decision
        # (quantize=False keeps the accounting-only contract: values pass
        # untouched even though fraction < 1 makes this channel simulate)
        base_wire = CommChannel.simulates_quantization.fget(self)
        if self.fraction >= 1.0:                 # degenerate: base channel
            return self._wire(tree) if base_wire else tree
        if ref is None and not base_wire:        # exact wire, nothing sent
            return tree                          # differs from the fallback
        if masks is None:
            # inside a scan, pass precomputed masks instead: the keep
            # masks (or the rotating chunk ids behind them) are constant
            # per run, the permutations are not free
            masks = self.mask_tree(tree if ref is None else ref,
                                   round_index)
        sent = self._wire(tree) if base_wire else tree
        if ref is None:
            # downlink: kept entries ride the wire dtype; dropped entries
            # approximate the client's stale copy with the exact value
            return jax.tree.map(lambda t, s, m: jnp.where(m, s, t),
                                tree, sent, masks)
        # uplink: masks/ref broadcast over the leading clients axis
        return jax.tree.map(lambda r, s, m: jnp.where(m, s, r),
                            ref, sent, masks)


class _BlockRunner:
    """Compiled block executor: lax.scan over the padded round axis whose
    body vmaps the client hook across clients; per-round validity via
    ``lax.cond`` so padded rounds are runtime no-ops (phi passes through
    untouched — bit-for-bit identical to an unpadded scan). phi is
    donated — successive blocks update in place.

    The scan's xs are ``(ClientSchedule, batch)``: the whole per-round,
    per-client round state (participation, local step counts,
    aggregation weights, absolute round index) rides the scan carry as
    device arrays, so heterogeneous rounds cost ZERO extra host
    dispatches. ``scheduled`` is a static flag baked in from the
    sampling policy's ``schedule_kind``:

    * scheduled=False (UniformSampling): the legacy unweighted body —
      ``client_update`` + ``server_aggregate`` — bit-for-bit identical
      to the pre-schedule engine (the schedule arrays are threaded but
      unused, so XLA drops them).
    * scheduled=True: ``client_update_steps`` honors each client's
      traced step budget and ``server_aggregate_weighted`` applies the
      round's normalized weights; the reported round loss is the
      weighted mean of each client's per-live-step mean loss.

    Rotating partial-comm masks fold the schedule's round index into the
    mask inside the scan body (``chunk_ids == round % period``); the
    expensive per-leaf permutations happen once per block, outside it.

    Pooled runs (``pooled=True``) scan the carry ``(phi, PoolState)``
    instead: the round body gathers the cohort's per-client state rows
    by the schedule's cohort indices, runs the scheduled client phase,
    aggregates (immediately, or into the FedBuff buffer when
    ``buffered`` is set — the buffer flushes through
    ``server_aggregate_weighted`` with staleness-discounted weights
    every ``buffer_size`` arrivals), and scatters the updated rows back
    — all inside the scan, so persistent identities and async
    aggregation still cost ZERO per-round host dispatches.

    Mesh runs (``mesh`` is a 1-D "clients" Mesh) wrap the same scan in
    ``shard_map`` manual over the client axis: each device holds phi
    REPLICATED and runs the client phase over its local cohort shard
    (the schedule's per-client rows and the batch arrive pre-sharded
    from the prefetcher's NamedSharding device_put), then aggregation
    reduces across shards — ``server_aggregate_weighted(...,
    axis_name="clients")``, whose ``weighted_client_mean`` fuses the
    per-leaf partial sums into ONE psum. Collectives are the sharded
    hot path's scarce resource (every all-reduce is a cross-device
    rendezvous), so that fused psum is the only per-round collective on
    the flat path: round losses stay shard-local partial sums and the
    whole (rounds,) vector all-reduces once per block. Pooled mesh runs
    shard the per-client ``PoolState`` rows too: one fused all_gather
    of the round's (tiny) cohort+participation rows lets each shard
    scatter updates for exactly the pool clients it OWNS (foreign
    indices route out of range and drop), while the FedBuff buffer
    becomes per-shard slabs — the flush predicate runs on REPLICATED
    count/oldest-tag counters carried by the scan (no per-round
    collective), and the flush itself normalizes by a psum-reduced
    weight denominator and folds through the collective aggregation
    hook: "the buffer reduced across shards at flush". The mesh path
    always runs the scheduled body (uniform schedules are just uniform
    weights there, with the per-step masking skipped — see ``masked``).

    2-D runs (``mesh`` is a ("clients", "model") Mesh from
    ``client_model_mesh``) take the GSPMD route instead: the GLOBAL
    block bodies (``axis is None`` — the same code a flat run traces)
    compile under plain ``jax.jit`` against the mesh, with all sharding
    flowing from the COMMITTED input layouts — phi carries the run's
    ``ModelPartitioner`` NamedShardings (weight matrices split on the
    model axis, norms/biases replicated; ``pin_phi`` re-asserts them at
    block entry/exit so the donated carry keeps one layout and the
    runner keeps one trace), and the schedule/batch rows arrive sharded
    over "clients". The partitioner vmaps the client phase over the
    clients axis and emits the cross-client reduction plus any in-loop
    model-axis collectives itself, compiler-scheduled. No manual
    ``shard_map`` is involved: partial-manual lowering (manual over
    "clients", auto over "model") hits an XLA sharding-propagation
    CHECK on this toolchain for scan-with-outputs under vmap inside
    lax.cond — a shape user-pluggable strategy hooks are free to
    produce — so the manual route is 1-D only. Pool state stages in
    the flat (``shards == 1``) layout.

    ``clients_per_pass`` (flat and 1-D mesh routes, strategies whose
    ``server_reads_mean`` is set; see ``clients_per_pass``) runs each
    round's (local) cohort in passes of that many clients under
    ``lax.scan``: a pass vmaps the client phase over its clients and
    adds their weighted uplinks to a float32 sum that the scan carries,
    so only one pass's working copies of phi are live at a time. The
    server step reads the sum (psum-reduced across the client axis on a
    mesh) through ``strategy.server_apply_mean``. None is the whole
    cohort in one vmap, the route above, unchanged.

    ``trace_count`` increments once per jit trace; with the engine's
    fixed per-run block shape it stays at 1 per (strategy, beta,
    channel, schedule-shape, pool-shape, masked, mesh, clients per
    pass) config — the retrace-free contract's observable.
    """

    def __init__(self, strategy, beta, channel: CommChannel,
                 scheduled: bool = False, pooled: bool = False,
                 buffered: Optional[BufferedAggregation] = None,
                 mesh: Optional[Mesh] = None,
                 masked: Optional[bool] = None, partitioner=None,
                 clients_per_pass: Optional[int] = None):
        self.trace_count = 0
        # 2-D (clients, model) meshes take the GSPMD route: axis=None
        # selects the global block bodies (no manual shard_map, no named
        # collectives) and the mesh partitions them from the committed
        # input shardings — see the model_sharded comment below.
        axis = (CLIENT_AXIS if mesh is not None
                and MODEL_AXIS not in mesh.axis_names else None)
        if mesh is not None:
            if not scheduled:
                raise ValueError("mesh runs always use the scheduled "
                                 "body (engine-internal invariant)")
            self._check_collective_hook(strategy)
        # masked: whether the scheduled client phase honors per-client
        # step budgets via the lax.cond-masked hooks. Uniform schedules
        # (full budget everywhere — every mesh run of UniformSampling,
        # every pooled uniform run) skip the per-step masking: the
        # masked hooks reproduce the unmasked ones op-for-op at k ==
        # budget (pinned in tests), but pay one lax.cond per inner
        # step, which is pure overhead on the hot path.
        self.masked = scheduled if masked is None else bool(masked)
        masked_hooks = self.masked
        beta_f = jnp.float32(beta)
        simulate = channel.simulates_quantization
        uplink_ref = getattr(strategy, "uplink_ref", "params")
        needs_ref = getattr(channel, "needs_uplink_ref", False)
        partial = getattr(channel, "fraction", 1.0) < 1.0
        rotating = partial and bool(getattr(channel, "rotate", False))
        # the server update's device ops carry this scope in their HLO
        # metadata, whichever of the scan bodies below calls it
        server_aggregate = jax.named_scope("server_update")(
            strategy.server_aggregate)
        server_aggregate_weighted = jax.named_scope("server_update")(
            strategy.server_aggregate_weighted)
        if clients_per_pass is not None:
            if pooled or (mesh is not None and axis is None):
                raise ValueError("clients_per_pass runs on the flat and "
                                 "1-D mesh routes only (engine-internal "
                                 "invariant)")
            server_apply_mean = jax.named_scope("server_update")(
                strategy.server_apply_mean)

        @jax.named_scope("client_phase")
        def client_phase(phi, sched, batch, masks, chunk_ids):
            """Downlink -> vmapped client hook -> uplink: the wire-and-
            compute half of a round, shared by every scan body."""
            m = masks
            if chunk_ids is not None:
                m = channel.masks_for_round(chunk_ids, sched.round_index)
            phi_down = (channel.transmit(phi, masks=m)
                        if simulate else phi)
            if scheduled and masked_hooks:
                results, losses = jax.vmap(
                    lambda b, k: strategy.client_update_steps(
                        phi_down, b, beta_f, k))(batch, sched.local_steps)
            else:
                results, losses = jax.vmap(
                    lambda b: strategy.client_update(phi_down, b,
                                                     beta_f))(batch)
            if simulate:
                # the uplink fallback is the SERVER's own state
                # (phi, pre-wire), not the quantized broadcast
                # the clients saw
                ref = None
                if needs_ref and uplink_ref == "params":
                    ref = phi
                elif needs_ref and uplink_ref == "zeros":
                    ref = jax.tree.map(jnp.zeros_like, phi)
                results = channel.transmit(
                    results, ref=ref,
                    masks=m if ref is not None else None)
            return results, losses

        def uplink_sum_by_passes(phi, sched, batch, masks, chunk_ids):
            """The (local) cohort's weighted uplink sum, in passes of
            ``clients_per_pass`` clients under ``lax.scan``, and every
            client's losses. Uniform rounds weigh each client 1/C;
            zero-weight clients add nothing (``weighted_client_mean``'s
            rule)."""
            from repro.core.strategies import weighted_client_mean
            c = jax.tree.leaves(batch)[0].shape[0]
            n = c // clients_per_pass
            w = (sched.weights if scheduled
                 else jnp.full((c,), 1.0 / c, jnp.float32))

            def split(a):
                return a.reshape((n, clients_per_pass) + a.shape[1:])

            def one_pass(total, xs):
                b, k, wp = xs
                results, losses = client_phase(
                    phi, ClientSchedule(sched.valid, sched.alpha,
                                        sched.round_index, None, k, wp),
                    b, masks, chunk_ids)
                total = jax.tree.map(jnp.add, total,
                                     weighted_client_mean(results, wp))
                return total, losses

            total = jax.tree.map(lambda q: jnp.zeros(q.shape, jnp.float32),
                                 strategy.uplink_template(phi))
            total, losses = jax.lax.scan(
                one_pass, total,
                (jax.tree.map(split, batch), split(sched.local_steps),
                 split(w)))
            return total, losses.reshape((c,) + losses.shape[2:])

        def weighted_round_loss(losses, sched):
            k = jnp.maximum(sched.local_steps, 1).astype(jnp.float32)
            per_client = losses.reshape(
                (losses.shape[0], -1)).sum(axis=1) / k
            # zero-weight clients are inert here too: their loss on a
            # zeroed batch may be non-finite and 0 * NaN would poison
            # the round loss (same guard as
            # strategies.weighted_client_mean)
            return jnp.sum(sched.weights * jnp.where(
                sched.weights > 0, per_client, 0.0))

        def make_round_fn(masks, chunk_ids):
            def round_fn(phi, xs):
                sched, batch = xs    # sched: one ClientSchedule row;
                #                      batch leaves: (C, S, ...) — the
                #                      LOCAL cohort shard on mesh runs

                def live(phi):
                    if clients_per_pass is not None:
                        mean, losses = uplink_sum_by_passes(
                            phi, sched, batch, masks, chunk_ids)
                        if axis is not None:
                            # one multi-operand all-reduce of the
                            # per-shard sums, as weighted_client_mean
                            mean = jax.lax.psum(mean, axis)
                        phi = server_apply_mean(phi, mean, sched.alpha,
                                                beta_f)
                        loss = (weighted_round_loss(losses, sched)
                                if scheduled else jnp.mean(losses))
                        return phi, loss
                    results, losses = client_phase(phi, sched, batch,
                                                   masks, chunk_ids)
                    if axis is not None:
                        phi = server_aggregate_weighted(
                            phi, results, sched.alpha, beta_f,
                            sched.weights, axis_name=axis)
                        # the round loss stays a SHARD-LOCAL partial sum
                        # here; the block body all-reduces the whole
                        # (rounds,) vector once per block — a per-round
                        # scalar psum would pay one extra cross-device
                        # rendezvous every round
                        loss = weighted_round_loss(losses, sched)
                    elif scheduled:
                        phi = server_aggregate_weighted(
                            phi, results, sched.alpha, beta_f,
                            sched.weights)
                        loss = weighted_round_loss(losses, sched)
                    else:
                        phi = server_aggregate(phi, results,
                                               sched.alpha, beta_f)
                        loss = jnp.mean(losses)
                    return phi, loss

                def dead(phi):
                    return phi, jnp.float32(0.0)

                return jax.lax.cond(sched.valid, live, dead, phi)
            return round_fn

        _NEVER = jnp.int32(2 ** 30)      # "no buffered update" round tag

        def staleness_overdue(buf_round, count, cap, round_index):
            """The availability-aware flush predicate (one extra
            comparison OR-ed into the flush cond): True when holding
            the buffer past this round would let its oldest update
            reach the staleness deadline. (Unsharded path; the mesh
            path tracks the replicated oldest tag in the scan carry —
            see make_pooled_round_fn — so no per-round collective is
            needed there either.)"""
            valid = jnp.arange(cap) < count
            oldest = jnp.where(valid, buf_round, _NEVER).min()
            return (count > 0) & (round_index - oldest + 1
                                  >= buffered.flush_staleness)

        def make_pooled_round_fn(masks, chunk_ids):
            def round_fn(carry, xs):
                sched, batch = xs

                def live(carry):
                    if axis is not None:
                        # mesh carry: (phi, PoolState, replicated flush
                        # counters) — see live_sharded
                        phi, ps, gcount, goldest = carry
                        results, losses = client_phase(phi, sched, batch,
                                                       masks, chunk_ids)
                        return live_sharded(phi, ps, gcount, goldest,
                                            sched, results, losses)
                    phi, ps = carry
                    results, losses = client_phase(phi, sched, batch,
                                                   masks, chunk_ids)
                    if buffered is None:
                        phi = server_aggregate_weighted(
                            phi, results, sched.alpha, beta_f,
                            sched.weights)
                        buf, buf_round = ps.buf_updates, ps.buf_round
                        count, flushes = ps.buf_count, ps.flushes
                    else:
                        # append this round's arrivals at the buffer's
                        # write positions (a prefix-sum compaction of the
                        # participation mask); non-participants scatter
                        # to an out-of-range slot and are dropped
                        cap = ps.buf_round.shape[0]
                        arrive = sched.participation.astype(jnp.int32)
                        slot = jnp.where(
                            sched.participation,
                            ps.buf_count + jnp.cumsum(arrive) - 1, cap)
                        buf = jax.tree.map(
                            lambda b, q: b.at[slot].set(
                                q.astype(b.dtype), mode="drop"),
                            ps.buf_updates, results)
                        buf_round = ps.buf_round.at[slot].set(
                            sched.round_index, mode="drop")
                        count = ps.buf_count + arrive.sum()

                        def flush(args):
                            phi, buf, buf_round, count, flushes = args
                            tau = (sched.round_index
                                   - buf_round).astype(jnp.float32)
                            w = (buffered.staleness_fn(tau)
                                 * (jnp.arange(cap) < count))
                            w = (w / jnp.maximum(w.sum(), 1e-8)
                                 ).astype(jnp.float32)
                            phi = server_aggregate_weighted(
                                phi, buf, sched.alpha, beta_f, w)
                            return phi, jnp.int32(0), flushes + 1

                        def hold(args):
                            phi, buf, buf_round, count, flushes = args
                            return phi, count, flushes

                        do_flush = count >= buffered.buffer_size
                        if buffered.flush_staleness is not None:
                            do_flush = do_flush | staleness_overdue(
                                buf_round, count, cap, sched.round_index)
                        phi, count, flushes = jax.lax.cond(
                            do_flush, flush, hold,
                            (phi, buf, buf_round, count, ps.flushes))

                    # scatter the cohort's identity-state rows back:
                    # non-participants route to the out-of-range index
                    # n and are dropped; cohort indices are unique per
                    # round, so set/add never collide
                    n = ps.last_seen.shape[0]
                    idx = jnp.where(sched.participation, sched.cohort, n)
                    gap = (sched.round_index
                           - ps.last_seen[sched.cohort]).astype(jnp.int32)
                    ps = PoolState(
                        last_seen=ps.last_seen.at[idx].set(
                            sched.round_index, mode="drop"),
                        staleness=ps.staleness.at[idx].set(
                            gap, mode="drop"),
                        checkins=ps.checkins.at[idx].add(1, mode="drop"),
                        buf_updates=buf, buf_round=buf_round,
                        buf_count=count, flushes=flushes)
                    return (phi, ps), weighted_round_loss(losses, sched)

                def live_sharded(phi, ps, gcount, goldest, sched, results,
                                 losses):
                    # mesh round: phi replicated, per-client state rows
                    # and the cohort/batch sharded over the client
                    # axis. Per-round collectives are kept to the bare
                    # minimum — ONE fused all_gather of the (tiny)
                    # cohort+participation rows and the aggregation's
                    # fused psum; the flush predicate runs on the
                    # REPLICATED (gcount, goldest) counters carried by
                    # the scan, and the round loss stays a shard-local
                    # partial (all-reduced once per block).
                    c_local = sched.cohort.shape[0]
                    packed = jnp.concatenate(
                        [sched.cohort,
                         sched.participation.astype(jnp.int32)])
                    packed = jax.lax.all_gather(packed, axis)
                    cohort_f = packed[:, :c_local].reshape(-1)
                    part_f = packed[:, c_local:].reshape(-1) > 0

                    if buffered is None:
                        phi = server_aggregate_weighted(
                            phi, results, sched.alpha, beta_f,
                            sched.weights, axis_name=axis)
                        buf, buf_round = ps.buf_updates, ps.buf_round
                        count, flushes = ps.buf_count, ps.flushes
                    else:
                        # per-shard slab: local arrivals compact into
                        # THIS shard's buffer; the flush predicate is
                        # on the replicated global count, and the flush
                        # itself is a weighted all-reduce with a
                        # psum-normalized denominator — "the buffer
                        # reduced across shards at flush"
                        cap = ps.buf_round.shape[0]
                        arrive = sched.participation.astype(jnp.int32)
                        cnt = ps.buf_count[0]        # local fill level
                        slot = jnp.where(
                            sched.participation,
                            cnt + jnp.cumsum(arrive) - 1, cap)
                        buf = jax.tree.map(
                            lambda b, q: b.at[slot].set(
                                q.astype(b.dtype), mode="drop"),
                            ps.buf_updates, results)
                        buf_round = ps.buf_round.at[slot].set(
                            sched.round_index, mode="drop")
                        cnt = cnt + arrive.sum()
                        gcount = gcount + part_f.sum()
                        goldest = jnp.where(part_f.any(),
                                            jnp.minimum(goldest,
                                                        sched.round_index),
                                            goldest)

                        def flush(args):
                            phi, buf, buf_round, cnt, flushes = args
                            tau = (sched.round_index
                                   - buf_round).astype(jnp.float32)
                            w = (buffered.staleness_fn(tau)
                                 * (jnp.arange(cap) < cnt))
                            denom = jax.lax.psum(w.sum(), axis)
                            w = (w / jnp.maximum(denom, 1e-8)
                                 ).astype(jnp.float32)
                            phi = server_aggregate_weighted(
                                phi, buf, sched.alpha, beta_f, w,
                                axis_name=axis)
                            return phi, jnp.int32(0), flushes + 1

                        def hold(args):
                            phi, buf, buf_round, cnt, flushes = args
                            return phi, cnt, flushes

                        do_flush = gcount >= buffered.buffer_size
                        if buffered.flush_staleness is not None:
                            do_flush = do_flush | (
                                (gcount > 0)
                                & (sched.round_index - goldest + 1
                                   >= buffered.flush_staleness))
                        phi, cnt, flushes = jax.lax.cond(
                            do_flush, flush, hold,
                            (phi, buf, buf_round, cnt, ps.flushes))
                        gcount = jnp.where(do_flush, 0, gcount)
                        goldest = jnp.where(do_flush, _NEVER, goldest)
                        count = cnt[None]            # back to (1,) local

                    # scatter identity rows for the pool clients THIS
                    # shard owns, wherever in the cohort they sat:
                    # foreign/idle indices route out of range and drop
                    n_local = ps.last_seen.shape[0]
                    base = jax.lax.axis_index(axis) * n_local
                    loc = cohort_f - base
                    own = part_f & (loc >= 0) & (loc < n_local)
                    idx = jnp.where(own, loc, n_local)
                    safe = jnp.clip(loc, 0, n_local - 1)
                    gap = (sched.round_index
                           - ps.last_seen[safe]).astype(jnp.int32)
                    ps = PoolState(
                        last_seen=ps.last_seen.at[idx].set(
                            sched.round_index, mode="drop"),
                        staleness=ps.staleness.at[idx].set(
                            gap, mode="drop"),
                        checkins=ps.checkins.at[idx].add(1, mode="drop"),
                        buf_updates=buf, buf_round=buf_round,
                        buf_count=count, flushes=flushes)
                    loss = weighted_round_loss(losses, sched)
                    return (phi, ps, gcount, goldest), loss

                def dead(carry):
                    return carry, jnp.float32(0.0)

                return jax.lax.cond(sched.valid, live, dead, carry)
            return round_fn

        def mask_state(phi):
            # the partial-channel mask state is constant for the whole
            # run: build it here, OUTSIDE the scan body, so the per-leaf
            # permutations execute once per block instead of every round
            # (rotating channels precompute chunk ids; the per-round mask
            # is one elementwise compare against the scanned round index)
            masks = (channel.mask_tree(phi)
                     if simulate and partial and not rotating else None)
            chunk_ids = (channel.chunk_id_tree(phi)
                         if simulate and rotating else None)
            return masks, chunk_ids

        def sched_spec():
            # specs for the whole padded block: per-round vectors
            # replicated, per-client rows sharded on the client axis
            return ClientSchedule(
                valid=P(), alpha=P(), round_index=P(),
                participation=P(None, axis), local_steps=P(None, axis),
                weights=P(None, axis),
                cohort=P(None, axis) if pooled else None)

        # 2-D (clients, model) meshes run the GLOBAL (unsharded) block
        # body under plain jit — NO shard_map. All sharding flows from
        # the committed input layouts (phi carries the ModelPartitioner's
        # per-leaf NamedShardings, batch/schedule rows are split over the
        # clients axis), so GSPMD partitions the vmapped client phase
        # over "clients" and every model-axis collective the sharded
        # matmuls imply is compiler-scheduled. The weighted client mean
        # then reduces the clients-sharded results axis — one all-reduce
        # with phi's model shards aggregated IN PLACE (no gather of full
        # phi to any device). A per-round partial-manual shard_map form
        # (manual over "clients", auto over "model") would be the
        # alternative, but XLA's partitioner in this toolchain
        # hard-aborts (CHECK sharding.IsManualSubgroup) on
        # scan-emitting-outputs under vmap inside a manual subgroup —
        # strategy hooks are user-pluggable, so that pattern cannot be
        # outlawed. Pure GSPMD keeps both invariants (zero per-round
        # host dispatches, one jit trace) without restricting hooks.
        # ``pin_phi`` pins phi's layout at block entry/exit: GSPMD is
        # otherwise free to pick a different output layout, which would
        # re-commit the donated phi and retrace the next block.
        model_sharded = mesh is not None and MODEL_AXIS in mesh.axis_names
        if model_sharded:
            if partitioner is None:     # direct construction in tests
                from repro.runtime.sharding import DEFAULT_PARTITIONER
                partitioner = DEFAULT_PARTITIONER

            def pin_phi(phi):
                return jax.tree_util.tree_map_with_path(
                    lambda path, leaf: jax.lax.with_sharding_constraint(
                        leaf, NamedSharding(mesh, partitioner.spec(
                            path, leaf.shape, mesh))), phi)
        else:
            def pin_phi(phi):
                return phi

        def gspmd_body(body):
            # GSPMD cannot partition a Mosaic kernel, so on a 2-D mesh the
            # strategy hooks trace their XLA route (resolve_use_pallas)
            if not model_sharded:
                return body

            def traced(*args):
                with _gspmd_trace():
                    return body(*args)
            return traced

        if pooled:
            if axis is not None:
                # buf_count dummy must be RANK 1: this route carries the
                # mesh layout's (shards,) local fill levels, and
                # pool_state_specs replicates rank-0 fill counters (the
                # flat layout the 2-D GSPMD route runs in)
                state_spec = pool_state_specs(
                    PoolState(0, 0, 0,
                              buf_updates=(0 if buffered else None),
                              buf_round=(0 if buffered else None),
                              buf_count=(np.zeros(1, np.int32)
                                         if buffered else None),
                              flushes=(0 if buffered else None)),
                    axis)
            if axis is None:
                def block_body(phi, pool_state, sched, batch):
                    phi = pin_phi(phi)
                    masks, chunk_ids = mask_state(phi)
                    (phi, pool_state), losses = jax.lax.scan(
                        make_pooled_round_fn(masks, chunk_ids),
                        (phi, pool_state), (sched, batch))
                    return pin_phi(phi), pool_state, losses
            else:
                def block_body(phi, pool_state, sched, batch):
                    masks, chunk_ids = mask_state(phi)
                    # replicated flush counters enter the carry ONCE per
                    # block (one psum/pmin here instead of per round)
                    if buffered is not None:
                        cnt = pool_state.buf_count[0]
                        cap = pool_state.buf_round.shape[0]
                        gcount = jax.lax.psum(cnt, axis)
                        goldest = jax.lax.pmin(
                            jnp.where(jnp.arange(cap) < cnt,
                                      pool_state.buf_round, _NEVER).min(),
                            axis)
                    else:
                        gcount, goldest = jnp.int32(0), _NEVER
                    (phi, pool_state, _, _), losses = jax.lax.scan(
                        make_pooled_round_fn(masks, chunk_ids),
                        (phi, pool_state, gcount, goldest),
                        (sched, batch))
                    # per-round losses were shard-local partial sums
                    return phi, pool_state, jax.lax.psum(losses, axis)

            body = gspmd_body(block_body)
            if axis is not None:
                body = jax.shard_map(
                    block_body, mesh=mesh,
                    in_specs=(P(), state_spec, sched_spec(),
                              P(None, axis)),
                    out_specs=(P(), state_spec, P()),
                    check_vma=False)

            def run_block(phi, pool_state, sched, batch):
                self.trace_count += 1             # runs at trace time only
                return body(phi, pool_state, sched, batch)

            self._jit = jax.jit(run_block, donate_argnums=(0, 1))
        else:
            def block_body(phi, sched, batch):
                phi = pin_phi(phi)
                masks, chunk_ids = mask_state(phi)
                phi, losses = jax.lax.scan(make_round_fn(masks, chunk_ids),
                                           phi, (sched, batch))
                if axis is not None:
                    # per-round losses were shard-local partial sums;
                    # one (rounds,)-vector all-reduce per block
                    losses = jax.lax.psum(losses, axis)
                return pin_phi(phi), losses

            body = gspmd_body(block_body)
            if axis is not None:
                body = jax.shard_map(
                    block_body, mesh=mesh,
                    in_specs=(P(), sched_spec(), P(None, axis)),
                    out_specs=(P(), P()),
                    check_vma=False)

            def run_block(phi, sched, batch):
                self.trace_count += 1             # runs at trace time only
                return body(phi, sched, batch)

            self._jit = jax.jit(run_block, donate_argnums=(0,))

    @staticmethod
    def _check_collective_hook(strategy) -> None:
        """Mesh runs need the axis_name-aware collective aggregation
        form; fail at construction with a plugin-author-facing message
        instead of a TypeError from inside the trace."""
        try:
            sig = inspect.signature(strategy.server_aggregate_weighted)
        except (TypeError, ValueError):      # builtins/partials: assume ok
            return
        params = sig.parameters.values()
        if not ("axis_name" in sig.parameters or any(
                p.kind is inspect.Parameter.VAR_KEYWORD for p in params)):
            raise ValueError(
                f"{type(strategy).__name__}.server_aggregate_weighted "
                f"does not accept axis_name=: mesh-sharded runs reduce "
                f"the weighted client aggregate across the "
                f"'{CLIENT_AXIS}' mesh axis — add axis_name=None to the "
                f"hook and route it through weighted_client_mean (see "
                f"docs/PLUGINS.md)")

    def __call__(self, *args):
        return self._jit(*args)


class _RunnerLRU:
    """Hand-rolled LRU replacing the old ``functools.lru_cache``: same
    counters and eviction order, but with INSPECTABLE keys, so
    ``runner_cache_stats`` can account for mesh-keyed entries (the old
    opaque cache could not tell a sharded runner from a flat one)."""

    def __init__(self, maxsize: int = 64):
        self.maxsize = maxsize
        self._entries: "collections.OrderedDict" = collections.OrderedDict()
        self.hits = 0
        self.misses = 0

    def get(self, key, build):
        """Cached runner for ``key`` (raises TypeError on unhashable
        keys, like lru_cache), building and LRU-inserting on a miss."""
        if key in self._entries:
            self._entries.move_to_end(key)
            self.hits += 1
            return self._entries[key]
        self.misses += 1
        runner = build()
        self._entries[key] = runner
        if len(self._entries) > self.maxsize:
            self._entries.popitem(last=False)
        return runner

    def keys(self):
        return list(self._entries.keys())

    def clear(self):
        self._entries.clear()
        self.hits = 0
        self.misses = 0


_RUNNER_CACHE = _RunnerLRU(maxsize=64)
_UNHASHABLE_MISSES = {"count": 0}


def _block_runner(strategy, beta, channel: CommChannel,
                  scheduled: bool = False, pooled: bool = False,
                  buffered: Optional[BufferedAggregation] = None,
                  mesh: Optional[Mesh] = None,
                  masked: Optional[bool] = None,
                  partitioner=None,
                  clients_per_pass: Optional[int] = None) -> _BlockRunner:
    """Strategies and channels are frozen dataclasses, so identically-
    configured runs (every test/bench re-entry) reuse one jitted runner
    instead of recompiling per call; ``scheduled`` (the policy's static
    schedule shape), ``pooled``, the ``buffered`` config, the
    ``partitioner`` (2-D-mesh runs: phi's model-axis layout is part of
    the traced program, so two partitionings never share an
    executable), and the ``mesh`` are part of the key. A Mesh hashes
    over its device list and axis names, so a runner traced for one
    device topology can NEVER be served for another (a 4-device and an
    8-device mesh are distinct keys, a 1-D and a 2-D mesh over the same
    devices differ in axis names, and jax.devices() cannot change
    within a process for the mesh=None entries). Unhashable custom
    strategies still work — they pay a fresh trace per run, counted and
    logged so sweeps notice."""
    masked = bool(scheduled) if masked is None else bool(masked)
    key = (strategy, float(beta), channel, bool(scheduled), bool(pooled),
           buffered, masked, partitioner, clients_per_pass, mesh)

    def build():
        return _BlockRunner(strategy, beta, channel, scheduled, pooled,
                            buffered, mesh, masked, partitioner,
                            clients_per_pass)

    try:
        return _RUNNER_CACHE.get(key, build)
    except TypeError:
        _UNHASHABLE_MISSES["count"] += 1
        logger.warning(
            "block-runner cache miss #%d: strategy %s (channel %s) is "
            "unhashable; building an uncached jitted runner (fresh trace "
            "per run). Make custom strategies frozen dataclasses to cache "
            "them.", _UNHASHABLE_MISSES["count"],
            type(strategy).__name__, type(channel).__name__)
        return build()


def _memory_budget(device) -> Optional[int]:
    """Bytes a program may hold on ``device``: the limit its allocator
    reports, or None where it reports none (the CPU)."""
    stats = device.memory_stats()
    limit = (stats or {}).get("bytes_limit")
    return int(limit) if limit else None


_CLIENT_STEP_BYTES: Dict = {}
#: how far under the device's memory a cohort's parameters and data,
#: four times over, must stay to be taken whole without measuring
_SHAPE_MARGIN = 64


def _client_step_bytes(strategy, beta, phi, batch):
    """Device bytes one client's update holds beyond the broadcast phi
    and its data: its output (the uplink) and its temporaries (the
    working copy's gradients and activations), and the bytes of its
    code, read from the compiled ``client_update`` of one client at these
    shapes. Memoized per shape set (unhashable strategies excepted), so a
    run's calls compile it once."""
    spec = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                        (phi, batch))

    def measure():
        beta_f = jnp.float32(beta)
        mem = jax.jit(lambda p, b: strategy.client_update(p, b, beta_f)) \
            .lower(*spec).compile().memory_analysis()
        return (int(mem.output_size_in_bytes + mem.temp_size_in_bytes),
                int(mem.generated_code_size_in_bytes))

    key = (strategy, float(beta),
           tuple((l.shape, str(l.dtype)) for l in jax.tree.leaves(spec)),
           jax.tree.structure(spec))
    try:
        hit = _CLIENT_STEP_BYTES.get(key)
    except TypeError:                    # an unhashable strategy
        return measure()
    if hit is None:
        hit = _CLIENT_STEP_BYTES[key] = measure()
    return hit


def clients_per_pass(strategy, beta, phi, batch, clients: int,
                     device) -> int:
    """How many of a round's ``clients`` (the cohort, or one device's
    shard of it) the client phase runs in one pass: all of them where
    their working copies fit the device's memory, else the largest
    divisor of ``clients`` that fits, and at least one.

    ``batch`` is one client's data. The fixed part is phi, the float32
    uplink sum the passes carry, the round's data and the program's code
    (taken as the update's); each client adds what its compiled update
    holds (``_client_step_bytes``). Only strategies whose server step
    reads the uplinks' mean alone (``server_reads_mean``) can be split;
    the others, and devices that report no memory, take the whole
    cohort. So does a cohort whose parameters and data, four times over,
    fill under 1/``_SHAPE_MARGIN`` of the device's memory, with no
    update compiled to measure it: the rest is left for activations and
    tile padding (the paper models' cohorts fill under 1/250)."""
    if clients <= 1 or not getattr(strategy, "server_reads_mean", False):
        return clients
    budget = _memory_budget(device)
    if budget is None:
        return clients
    param_bytes = sum(a.size * a.dtype.itemsize
                      for a in jax.tree.leaves(phi))
    client_data = sum(a.size * a.dtype.itemsize
                      for a in jax.tree.leaves(batch))
    if 4 * clients * (param_bytes + client_data) * _SHAPE_MARGIN <= budget:
        return clients
    data_bytes = clients * client_data
    step, code = _client_step_bytes(strategy, beta, phi, batch)
    fixed = param_bytes + 4 * sum(a.size for a in jax.tree.leaves(phi)) \
        + data_bytes + code
    fit = max(1, (budget - fixed) // max(step, 1))
    if fit >= clients:
        return clients
    return max(d for d in range(1, int(fit) + 1) if clients % d == 0)


def runner_cache_stats() -> Dict[str, int]:
    """Block-runner cache counters: lru hits/misses/size, how many
    times an unhashable strategy forced an uncached runner, and how
    many of the cached entries are mesh-keyed (sharded runners pin
    multi-device executables — sweeps over topologies should clear
    between phases)."""
    return {"hits": _RUNNER_CACHE.hits, "misses": _RUNNER_CACHE.misses,
            "currsize": len(_RUNNER_CACHE.keys()),
            "maxsize": _RUNNER_CACHE.maxsize,
            "unhashable_misses": _UNHASHABLE_MISSES["count"],
            "mesh_entries": sum(1 for k in _RUNNER_CACHE.keys()
                                if k[-1] is not None)}


def clear_runner_cache() -> None:
    """Drop every cached jitted block runner — mesh-keyed sharded
    runners included — and reset the counters. Long sweeps over many
    strategy/channel/topology configs should call this between phases
    so up to 64 stale executables don't stay pinned. Also drops the
    memoized client-step sizes of ``clients_per_pass``."""
    _RUNNER_CACHE.clear()
    _CLIENT_STEP_BYTES.clear()
    _UNHASHABLE_MISSES["count"] = 0


@jax.jit
def _snapshot_copy(tree):
    """One fused dispatch copying the whole carry (vs one dispatch per
    leaf with a bare tree.map) — the snapshot path runs between donating
    block launches, so its host cost lands on the round hot path."""
    return jax.tree.map(jnp.copy, tree)


def run_federated(init_params, task_dist: TaskDistribution, strategy, *,
                  rounds: int, clients_per_round: int = 1,
                  alpha: float = 1.0, beta: float = 0.01, support: int = 32,
                  anneal: bool = True, seed: int = 0, eval_every: int = 0,
                  eval_kwargs: Optional[dict] = None,
                  channel: Optional[CommChannel] = None,
                  max_block: int = 512, prefetch: int = 2,
                  sampling: Optional[SamplingPolicy] = None,
                  pool: Optional[ClientPool] = None,
                  buffered: Optional[BufferedAggregation] = None,
                  mesh=None, partitioner=None,
                  ckpt_dir: Optional[str] = None,
                  ckpt_every: int = 10, ckpt_keep: int = 3,
                  ckpt_async: bool = True, resume: bool = False,
                  tracker=None) -> Dict:
    """Run `rounds` federated rounds of `strategy`.

    Returns {"params", "history"} (+ "comm_bytes" and "per_client_bytes"
    for strategies that meter communication — per_client_bytes[c] is the
    total transport paid by cohort slot c over the run; only rounds the
    slot PARTICIPATES in are billed). History rows are per-eval dicts in
    the legacy loops' format: evaluate_init fields + round
    [+ comm_bytes, inner_loss].

    Rounds between evals execute as fixed-shape on-device scan blocks
    (padded to one per-run length, masked, `max_block`-bounded — see
    repro.core.pipeline.plan_blocks), so the block runner compiles once
    per (strategy, beta, channel, schedule-shape, pool-shape) config.
    The host only plans the per-round ClientSchedule and samples client
    data (`sampling` policy) and runs the eval protocol — heterogeneous
    scenarios (partial participation, stragglers, rotating partial-comm
    masks) ride the schedule through the scan with no extra per-round
    host dispatches. With `prefetch` > 0 a background thread plans,
    samples, and stages block N+1 while the device runs block N
    (double-buffered at the default 2); `prefetch=0` is the synchronous
    escape hatch — both are bit-for-bit identical.

    The host data path is the `sampling` policy's `sampler`; with no
    policy the run uses `UniformSampling()`. Every policy's default
    (repro.core.pipeline.DEFAULT_SAMPLER) takes each block's client data
    from `task_dist.sample_support_block` in one call (a distribution
    that does not override it falls back to the per-task loop). The
    built-in distributions draw that block in block RNG order, so a
    seeded run gives different (but identically distributed) tasks than
    the legacy per-round loops; `sampling=UniformSampling("reference")`
    replays those loops' per-task order bit-for-bit. The checkpoint
    fingerprint records the policy's sampler: a run with no `sampling=`
    resumes a snapshot under the sampler it recorded (one written under
    the old "reference" default continues in that order), and a named
    policy must match it.

    `pool` switches the run onto PERSISTENT client identities (a
    repro.core.pool.ClientPool over `task_dist`): each round the policy
    seats a cohort of pool clients (`plan_pool_schedule`), their stable
    per-client data shards feed the round, and the pool's cross-round
    state (last-seen round, staleness, check-in counts) updates inside
    the scan. `buffered` (requires `pool`) turns aggregation
    FedBuff-style async: check-ins append to a server buffer that
    flushes every `buffer_size` arrivals with staleness-discounted
    weights. Pooled metered runs bill per POOL CLIENT
    (per_client_bytes has pool.size entries) and return a "pool_state"
    dict (last_seen / staleness / checkins arrays [+ flushes,
    buffered_pending]); `pool=None` keeps the legacy anonymous-cohort
    path bit-for-bit. The policy only seats a pooled run's cohort; the
    pool draws the data through its own `sampler`.

    `mesh` SHARDS THE CLIENT AXIS across devices: pass a 1-D "clients"
    Mesh (see `client_mesh`), an int (first n devices), or "auto"
    (every device). The cohort is padded to a multiple of the device
    count with scheduled-out slots (participation False, weight 0), the
    prefetcher stages each block with a NamedSharding (client rows
    split, per-round vectors replicated), each device vmaps its local
    shard, and aggregation / transport-weight reductions run as
    collectives inside the scan — still zero per-round host dispatches
    and one jit trace per config. Schedules, host RNG draws, billing,
    and pooled identity state are mesh-INDEPENDENT: an N-device run
    computes the same training trajectory as the 1-device run up to
    float reduction order. `mesh=None` (default) is bit-for-bit the
    single-device engine.

    `ckpt_dir` makes the run PREEMPTION-SAFE: at every block boundary
    crossing a multiple of `ckpt_every` rounds (blocks are additionally
    cut there — bitwise-neutral) the engine snapshots the complete scan
    carry as a repro.checkpoint.RoundState — phi, PoolState (incl.
    FedBuff buffer slabs), per-client transport bills, eval history,
    and the host RNG / pool-stream / policy state captured at the
    prefetch producer — via a background AsyncCheckpointWriter
    (device->host transfer off the critical path, bounded queue, atomic
    checksum-manifested files, last-`ckpt_keep` retention;
    `ckpt_async=False` writes inline). `resume=True` restores the
    newest VALID snapshot (torn/corrupted files fall back with a
    warning) and fast-forwards block planning: a killed-and-resumed run
    is bit-for-bit identical — params, pool state, history rows, and
    bills — to the uninterrupted seeded run. `rounds` may grow between
    the original run and the resume (training continues past the old
    horizon); seed/cohort/pool/mesh-shard mismatches are rejected via a
    config fingerprint.

    `tracker` attaches a `repro.metering.MetricsTracker`: per-round
    inner losses, cumulative transport bytes, eval rows, runner-cache
    gauges, and (pooled runs) the end-of-run staleness distribution
    flow into it. The tracker is host-side observation only — attaching
    one is bit-for-bit inert (the per-block loss fetch happens ONLY when
    a tracker is present, and feeds nothing back).

    `jax.profiler.trace(dir)` around the call records the engine's
    spans (`engine.run` and its per-block phases, listed in
    repro.metering.tracker) on the device trace's clock; with no
    profiler session running they cost about a microsecond each.
    """
    with TraceAnnotation("engine.run", rounds=rounds,
                         clients=clients_per_round,
                         seed=seed) as run_span:
        if channel is None:
            channel = CommChannel()
        # a run with no policy continues a snapshot under the sampler the
        # snapshot recorded (see the resume below)
        policy_named = sampling is not None
        if sampling is None:
            sampling = UniformSampling()
        pooled = pool is not None
        policy_sampler = sampling.sampler
        if buffered is not None:
            if not pooled:
                raise ValueError("buffered aggregation needs persistent "
                                 "clients to be stale against: pass "
                                 "pool=ClientPool(...) alongside buffered=")
            if getattr(strategy, "uplink_ref", "params") == "none":
                raise ValueError(
                    f"{type(strategy).__name__} uplinks raw data "
                    f"(uplink_ref='none'); the FedBuff buffer holds "
                    f"phi-shaped updates and cannot stage it")
        if pooled and pool.size < clients_per_round:
            raise ValueError(f"pool of {pool.size} clients cannot seat a "
                             f"cohort of {clients_per_round} (identities are "
                             f"unique within a round)")
        payload_dtype = getattr(strategy, "payload_dtype", "float32")
        if payload_dtype != "float32" and (channel.simulates_quantization
                                           or channel.dtype != payload_dtype):
            raise ValueError(
                f"{type(strategy).__name__} uplinks NATIVE {payload_dtype} "
                f"result trees (payload_dtype={payload_dtype!r}): the channel "
                f"must bill at that wire rate and must not re-simulate "
                f"quantization on already-quantized payloads — pass "
                f"CommChannel({payload_dtype!r}, quantize=False), got "
                f"{type(channel).__name__}(dtype={channel.dtype!r}, "
                f"simulates_quantization={channel.simulates_quantization})")
        mesh = _resolve_mesh(mesh)
        # the cohort is split over the CLIENTS axis extent only; on a 2-D
        # (clients, model) mesh the model axis splits phi's weight
        # matrices, not the cohort
        shards = int(mesh.shape[CLIENT_AXIS]) if mesh is not None else 1
        model_sharded = _model_sharded(mesh)
        if model_sharded:
            from repro.runtime.sharding import DEFAULT_PARTITIONER
            if partitioner is None:
                partitioner = DEFAULT_PARTITIONER
            if getattr(strategy, "payload_dtype", "float32") == "int8":
                raise ValueError(
                    f"{type(strategy).__name__} uplinks NATIVE int8 trees "
                    f"whose per-tensor quantization grids assume each "
                    f"parameter tensor is whole on every device; a 2-D "
                    f"('{CLIENT_AXIS}', '{MODEL_AXIS}') mesh shards phi's "
                    f"weight matrices — run int8 strategies on a 1-D "
                    f"'{CLIENT_AXIS}' mesh (or mesh=None) instead")
        elif partitioner is not None:
            raise ValueError(
                f"partitioner= only applies to a 2-D ('{CLIENT_AXIS}', "
                f"'{MODEL_AXIS}') mesh (build one with "
                f"repro.runtime.sharding.client_model_mesh); this run's mesh "
                f"is {'1-D' if mesh is not None else 'None'} and phi stays "
                f"replicated")
        # a mesh spanning >1 process (jax.distributed) changes only HOW
        # arrays move: every process runs this same host loop on the same
        # seed (plans, rng draws, and bills are process-replicated), each
        # contributes its addressable shard at staging, and device->host
        # reads of client-sharded state go through a replicating collective
        multiproc = (mesh is not None and
                     len({d.process_index for d in mesh.devices.flat}) > 1)

        def stage_tree(tree, target):
            """device_put — or, cross-host, per-leaf global-array assembly
            from the process-replicated host copy (device_put cannot build
            an array it only partially addresses)."""
            if not multiproc:
                return jax.device_put(tree, target)
            return jax.tree.map(
                lambda x, s: jax.make_array_from_callback(
                    np.shape(x), s, lambda idx, _x=np.asarray(x): _x[idx]),
                tree, target)

        def fetch_tree(tree):
            """device_get — or, cross-host, an all-gather into replicated
            form first (client-sharded leaves are not fully addressable
            from any one process). The gather is a collective: every
            process calls this at the same points, which the lockstep host
            loop guarantees."""
            if not multiproc:
                return jax.device_get(tree)
            rep = jax.jit(
                lambda t: t,
                out_shardings=jax.tree.map(
                    lambda _: NamedSharding(mesh, P()), tree))(tree)
            return jax.tree.map(np.asarray, rep)
        # mesh runs pad the cohort to a multiple of the shard count: the
        # pad slots are permanently scheduled out (participation False,
        # weight 0, zero batch) so every device sees an equal shard and the
        # validity-mask machinery keeps them inert
        c_pad = -(-clients_per_round // shards) * shards
        # pool-state LAYOUT: the 1-D manual shard_map body needs the
        # per-shard layout (per-shard FedBuff slabs, (shards,) local fill
        # levels); the 2-D GSPMD route runs the GLOBAL body, which sees the
        # whole state like a flat run does — build the shards == 1 layout
        # and let the committed input shardings split it
        state_shards = 1 if model_sharded else shards
        # residency="host" pools keep the (N,) identity arrays in host
        # slabs; the device carries only a fixed gathered WINDOW of the
        # rows each block actually touches (O(block cohort), not O(N)) —
        # the producer remaps cohort indices window-local, the consumer
        # stages the window before each block and scatters it back after
        host_resident = pooled and pool.residency == "host"
        slabs = pool.init_slabs(shards=state_shards) if host_resident else None
        rng = np.random.default_rng(seed)
        # private copy: the block runner donates its phi argument, and the
        # caller's init_params must stay usable (they are reused across runs)
        phi = jax.tree.map(jnp.array, init_params)
        # from here on only the shapes: a caller that keeps no reference of
        # its own frees its copy now, not when the call returns
        init_params = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(np.shape(a), a.dtype), init_params)
        history: List[Dict] = []
        comm_bytes = 0
        start_round = 0
        per_client_bytes = np.zeros(pool.size if pooled else clients_per_round,
                                    np.int64)
        uniform = getattr(sampling, "schedule_kind", "scheduled") == "uniform"
        scheduled = pooled or mesh is not None or not uniform
        # uniform schedules run every client at the full budget, so the
        # scheduled body skips the per-step lax.cond masking (bit-for-bit
        # identical at k == budget, without the per-inner-step overhead)
        masked = scheduled and not uniform
        budget = int(strategy.local_step_budget(support))
        # built at the first dispatch, when the staged block gives one
        # client's data shapes for the clients-per-pass choice
        run_block = None
        local_clients = c_pad // shards
        per_pass = local_clients
        run_tokens = 0
        # FedBuff buffers stage whatever the strategy uplinks — sized from
        # its template so quantized strategies buffer int8 trees at int8
        # width, never dequantized copies
        uplink_template = getattr(strategy, "uplink_template", None)
        pool_state = (pool.init_state(
            phi, c_pad, buffered, shards=state_shards,
            template=uplink_template(phi) if uplink_template else None)
            if pooled else None)
        if ckpt_dir is not None:
            if not (isinstance(ckpt_every, int) and ckpt_every >= 1):
                raise ValueError(f"ckpt_every must be an int >= 1, got "
                                 f"{ckpt_every!r}")
            if not (isinstance(ckpt_keep, int) and ckpt_keep >= 1):
                raise ValueError(f"ckpt_keep must be an int >= 1, got "
                                 f"{ckpt_keep!r}")
            # config identity stamped into every snapshot: a resume under a
            # different seed/cohort/pool/mesh would replay a DIFFERENT run
            # from this run's carry — reject it instead of training garbage
            fingerprint = {
                "seed": int(seed), "clients_per_round": int(clients_per_round),
                "support": int(support), "shards": int(shards),
                # full mesh topology + partitioning identity: a snapshot of
                # model-sharded (or differently-mesh-shaped) phi must never
                # silently resume into a run with a different layout
                "mesh": (",".join(f"{a}:{int(mesh.shape[a])}"
                                  for a in mesh.axis_names)
                         if mesh is not None else ""),
                "partitioner": partitioner.name if partitioner is not None
                else "",
                "strategy": type(strategy).__name__,
                "pool_size": int(pool.size) if pooled else 0,
                "pool_sampler": pool.sampler if pooled else "",
                "policy_sampler": policy_sampler,
                "buffered": buffered is not None}
        elif resume:
            raise ValueError("resume=True needs ckpt_dir= to restore from")
        if resume:
            try:
                saved = restore_round_state(
                    ckpt_dir, phi=phi, pool_state=pool_state,
                    per_client_bytes=per_client_bytes)
            except FileNotFoundError:
                logger.info("resume: no snapshot in %s yet; starting fresh",
                            ckpt_dir)
                saved = None
            if saved is not None:
                # e.g. a snapshot written when "reference" was the default
                was = (saved.fingerprint or {}).get("policy_sampler")
                if not policy_named and was in SAMPLERS:
                    policy_sampler = fingerprint["policy_sampler"] = was
                    sampling = UniformSampling(was)
                diff = {k: (saved.fingerprint.get(k), v)
                        for k, v in fingerprint.items()
                        if saved.fingerprint and saved.fingerprint.get(k) != v}
                if diff:
                    raise ValueError(
                        f"checkpoint in {ckpt_dir} was written by a different "
                        f"run config (saved != current): {diff}")
                if saved.round > rounds:
                    raise ValueError(
                        f"checkpoint in {ckpt_dir} is at round {saved.round}, "
                        f"past rounds={rounds}; raise the horizon to continue")
                start_round = int(saved.round)
                phi = jax.tree.map(jnp.asarray, saved.phi)
                if pooled:
                    pool_state = jax.tree.map(jnp.asarray, saved.pool_state)
                    pool.load_host_state(saved.host.get("pool", {}))
                per_client_bytes = np.asarray(saved.per_client_bytes,
                                              np.int64).copy()
                comm_bytes = int(saved.comm_bytes)
                history = list(saved.history)
                # the host rng resumes EXACTLY where the interrupted run's
                # producer stopped drawing — the bit-for-bit contract
                rng.bit_generator.state = saved.host["rng"]
                sampling.load_state_dict(saved.host.get("sampling", {}),
                                         rng=rng)
                logger.info("resumed %s from round %d", ckpt_dir, start_round)
        # the data path each block's `engine.sample` span names; the base
        # class's `sample_support_block` is the per-task loop
        block_call = (policy_sampler == "vectorized"
                      and type(task_dist).sample_support_block
                      is not TaskDistribution.sample_support_block)
        sample_path = ("pool" if pooled else
                       "vectorized" if block_call else "reference")
        blocks, pad = plan_blocks(rounds, eval_every, max_block,
                                  start=start_round,
                                  ckpt_every=ckpt_every if ckpt_dir else 0)
        run_span.set_metadata(blocks=len(blocks))
        if host_resident:
            # flush the full (possibly just-restored) identity into the
            # host slabs, then shrink the device carry to the gathered
            # window: one row per DISTINCT client a block can seat (a block
            # has pad rounds of c_pad slots), fixed for the whole run so
            # the runner still compiles once
            n_full = len(slabs["last_seen"])
            pool.scatter_rows(
                np.arange(n_full),
                {f: np.asarray(getattr(pool_state, f))
                 for f in ClientPool.SLAB_FIELDS})
            slab_rows = min(n_full,
                            -(-pad * c_pad // state_shards) * state_shards)
            win = pool.init_state(
                phi, c_pad, buffered, shards=state_shards,
                template=uplink_template(phi) if uplink_template else None,
                rows=slab_rows)
            # identity rows are re-staged from the slabs every block; the
            # FedBuff buffer is SERVER state and carries over (restored
            # buffers survive the shrink)
            pool_state = PoolState(
                win.last_seen, win.staleness, win.checkins,
                pool_state.buf_updates, pool_state.buf_round,
                pool_state.buf_count, pool_state.flushes)
        if mesh is not None:
            # 1-D mesh: phi fully replicated. 2-D mesh: each leaf carries
            # the partitioner's NamedSharding — weight matrices split on
            # the model axis, norms/biases replicated — and stays that way
            # through the whole run (aggregation psums over the clients
            # axis leave the model-axis shards in place; phi is never
            # gathered whole onto one device)
            phi = jax.device_put(
                phi, partitioner.shardings(phi, mesh) if model_sharded
                else NamedSharding(mesh, P()))
        if mesh is not None and pooled:
            # 1-D manual route: rows and FedBuff slabs are device_put in
            # shard_map's layout. 2-D GSPMD route: the flat-layout state is
            # staged replicated (rows are O(N) int32, not padded to the
            # clients extent) and the compiler re-shards inside the block
            # as the client-phase shardings dictate.
            pool_state = stage_tree(
                jax.tree.map(np.asarray, pool_state) if multiproc
                else pool_state,
                jax.tree.map(lambda s: NamedSharding(mesh, s),
                             (jax.tree.map(lambda _: P(), pool_state)
                              if model_sharded
                              else pool_state_specs(pool_state, CLIENT_AXIS)),
                             is_leaf=lambda x: isinstance(x, P)))

        def ckpt_at(end):
            """Deterministic snapshot predicate, shared by the producer's
            host-state capture and the consumer's device-state snapshot
            (plan_blocks cuts blocks at these rounds when ckpt_dir is set)."""
            return ckpt_dir is not None and (end == rounds
                                             or end % ckpt_every == 0)

        def snapshot_host():
            """Host-side carry at 'all draws for blocks <= i done' — called
            on the prefetch producer right after block i's sampling, so a
            resume continues the rng/pool/policy streams exactly where the
            uninterrupted run's producer would."""
            snap = {"rng": copy.deepcopy(rng.bit_generator.state)}
            if pooled:
                snap["pool"] = pool.host_state()
            policy_state = sampling.state_dict()
            if policy_state:
                snap["sampling"] = policy_state
            return snap

        host_snaps: Dict[int, dict] = {}
        writer = (AsyncCheckpointWriter(ckpt_dir, keep=ckpt_keep)
                  if ckpt_dir is not None and ckpt_async and blocks else None)
        device = single_device_of(phi)   # staging target for the prefetcher
        if strategy.meters_comm:
            # per-round payloads repeat with the channel's rotation period
            # (period 1 = the constant legacy accounting)
            period = (channel.rotation_period
                      if getattr(channel, "rotate", False) else 1)
            payload_by_phase = np.array(
                [channel.payload_bytes_at(init_params, j)
                 for j in range(period)], np.int64)

        def stage(i):
            """Plan the schedule, sample, pad, and device-stage block i.
            Called strictly in block order (inline, or from the single
            prefetch thread), so the host RNG stream is
            prefetch-schedule-independent: plan_schedule (or its pooled
            variant) draws first, then the data sampling, every block."""
            start, end = blocks[i]
            blk = end - start
            with TraceAnnotation("engine.plan", block=i, rounds=blk):
                if pooled:
                    plan = sampling.plan_pool_schedule(
                        rng, start, end, clients_per_round, budget,
                        pool.size)
                    cohort = np.asarray(plan["cohort"], np.int32)
                else:
                    plan = sampling.plan_schedule(rng, start, end,
                                                  clients_per_round, budget)
                    cohort = None
                part = np.asarray(plan["participation"], bool)
            with TraceAnnotation("engine.sample", block=i, rounds=blk,
                                 sampler=sample_path):
                if pooled:
                    batch = pool.sample_cohort_block(cohort, part, support,
                                                     strategy.data_mode)
                else:
                    batch = sampling.sample_block(task_dist, rng, blk,
                                                  clients_per_round, support,
                                                  strategy.data_mode,
                                                  participation=part)
            with TraceAnnotation("engine.stage", block=i,
                                 rounds=blk) as span:
                uniq, sched_cohort = None, cohort
                if host_resident:
                    # remap global cohort ids to window-local rows: the
                    # sorted distinct participants seat the window
                    # prefix, searchsorted inverts the map.
                    # Non-participant slots clamp into range (they are
                    # masked in-scan) and billing keeps the GLOBAL ids.
                    uniq = np.unique(cohort[part]).astype(np.int64)
                    if uniq.size:
                        sched_cohort = np.searchsorted(
                            uniq, cohort).astype(np.int32)
                        np.clip(sched_cohort, 0, uniq.size - 1,
                                out=sched_cohort)
                    else:
                        sched_cohort = np.zeros_like(cohort)
                r = np.arange(start, end)
                alphas = np.zeros(pad, np.float32)
                alphas[:blk] = alpha * (1 - r / rounds) if anneal else alpha
                valid = np.zeros(pad, bool)
                # pooled rounds where nobody checked in (an availability
                # trough) are runtime no-ops, same as the padding mask
                valid[:blk] = part.any(axis=1) if pooled else True
                round_index = np.zeros(pad, np.int32)
                round_index[:blk] = r

                def pad_rows(a, dtype):
                    # pads BOTH axes: short tail blocks on the round axis
                    # and the mesh cohort pad (c_pad == clients_per_round
                    # off-mesh)
                    out = np.zeros((pad, c_pad), dtype)
                    out[:blk, :clients_per_round] = a
                    return out

                sched = ClientSchedule(
                    valid=valid, alpha=alphas, round_index=round_index,
                    participation=pad_rows(part, bool),
                    local_steps=pad_rows(plan["local_steps"], np.int32),
                    weights=pad_rows(plan["weights"], np.float32),
                    cohort=(pad_rows(sched_cohort, np.int32) if pooled
                            else None))
                batch = {k: np.asarray(v) for k, v in batch.items()}
                if c_pad > clients_per_round:
                    batch = {k: np.concatenate(
                        [v, np.zeros((v.shape[0], c_pad - clients_per_round)
                                     + v.shape[2:], v.dtype)], axis=1)
                        for k, v in batch.items()}
                if blk < pad:
                    batch = {k: np.concatenate(
                        [v, np.zeros((pad - blk,) + v.shape[1:], v.dtype)])
                        for k, v in batch.items()}
                span.set_metadata(bytes=sum(
                    x.nbytes for x in jax.tree.leaves((sched, batch))))
                target = (block_shardings(mesh, CLIENT_AXIS, (sched, batch))
                          if mesh is not None else device)
                if ckpt_at(end):
                    host_snaps[end] = snapshot_host()
                return part, cohort, uniq, stage_tree((sched, batch),
                                                      target)

        id_sharding = (NamedSharding(mesh, P(CLIENT_AXIS))
                       if mesh is not None else device)

        def stage_window(uniq):
            """Gather the block's identity rows from the host slabs onto
            device (window prefix = the block's distinct participants, tail
            rows inert). Runs on the CONSUMER, after the previous block's
            write-back — the prefetch thread never races the slabs."""
            uniq_pad = np.zeros(slab_rows, np.int64)
            uniq_pad[:uniq.size] = uniq
            rows = pool.gather_rows(uniq_pad)
            rows = tuple(rows[f] for f in ClientPool.SLAB_FIELDS)
            return stage_tree(rows, (None if id_sharding is None else
                                     tuple(id_sharding for _ in rows)))

        staged_iter = prefetch_items(stage, len(blocks), depth=prefetch)
        try:
            for i, (start, end) in enumerate(blocks):
                blk = end - start
                with TraceAnnotation("engine.wait", block=i, rounds=blk):
                    part, cohort, uniq, (sched_d, batch_d) = next(
                        staged_iter)
                if host_resident:
                    with TraceAnnotation("engine.stage", block=i,
                                         rounds=blk):
                        ls, st, ck = stage_window(uniq)
                    pool_state = PoolState(
                        ls, st, ck, pool_state.buf_updates,
                        pool_state.buf_round, pool_state.buf_count,
                        pool_state.flushes)
                if run_block is None:
                    if not (pooled or model_sharded):
                        one_client = jax.tree.map(
                            lambda a: jax.ShapeDtypeStruct(a.shape[2:],
                                                           a.dtype), batch_d)
                        per_pass = clients_per_pass(
                            strategy, beta, phi, one_client, local_clients,
                            mesh.devices.flat[0] if mesh is not None
                            else device or jax.devices()[0])
                    run_block = _block_runner(
                        strategy, beta, channel, scheduled, pooled=pooled,
                        buffered=buffered, mesh=mesh, masked=masked,
                        partitioner=partitioner,
                        clients_per_pass=(per_pass if per_pass < local_clients
                                          else None))
                    run_span.set_metadata(clients_per_pass=per_pass)
                    if tracker is not None:
                        tracker.gauge("engine.clients_per_pass", per_pass)
                x = batch_d.get("x") if isinstance(batch_d, dict) else None
                tokens = (int(part.sum()) * int(np.prod(x.shape[2:]))
                          if x is not None
                          and jnp.issubdtype(x.dtype, jnp.integer) else 0)
                run_tokens += tokens
                with TraceAnnotation("engine.dispatch", block=i, rounds=blk,
                                     clients_per_pass=per_pass,
                                     tokens=tokens):
                    if pooled:
                        phi, pool_state, round_losses = run_block(
                            phi, pool_state, sched_d, batch_d)
                    else:
                        phi, round_losses = run_block(phi, sched_d, batch_d)
                with TraceAnnotation("engine.fetch", block=i, rounds=blk):
                    if host_resident and uniq.size:
                        got = fetch_tree(
                            tuple(getattr(pool_state, f)
                                  for f in ClientPool.SLAB_FIELDS))
                        pool.scatter_rows(
                            uniq, {f: np.asarray(g)[:uniq.size] for f, g in
                                   zip(ClientPool.SLAB_FIELDS, got)})
                    if tracker is not None:
                        # the loss fetch syncs on the block — done ONLY
                        # when a tracker asks for it, so tracker=None
                        # stays fetch-free
                        tracker.on_block(start, end,
                                         np.asarray(round_losses)[:blk])
                if strategy.meters_comm:
                    # bill downlink + uplink per participating client, at the
                    # round's exact (possibly rotating) payload
                    payloads = payload_by_phase[
                        np.arange(start, end) % len(payload_by_phase)]
                    if pooled:
                        # bill the POOL CLIENT seated in each participating
                        # slot (np.add.at accumulates repeat check-ins)
                        bills = 2 * payloads[:, None] * part
                        np.add.at(per_client_bytes, cohort[part], bills[part])
                    else:
                        per_client_bytes += (
                            2 * payloads[:, None] * part).sum(0)
                    block_bytes = int((2 * payloads * part.sum(axis=1)).sum())
                    comm_bytes += block_bytes
                    if tracker is not None:
                        tracker.on_transport(end, block_bytes, comm_bytes)
                if eval_every and end % eval_every == 0:
                    with TraceAnnotation("engine.eval", block=i, rounds=blk):
                        # cross-host: run the eval protocol on a LOCAL
                        # numpy copy of the replicated phi, so it stays a
                        # per-process computation (identical on every
                        # process) instead of a collective
                        eval_phi = (jax.tree.map(np.asarray, phi)
                                    if multiproc else phi)
                        ev = evaluate_init(
                            strategy.loss_fn, eval_phi, task_dist,
                            np.random.default_rng(10_000 + end - 1),
                            **(eval_kwargs or {}))
                        ev["round"] = end
                        if strategy.meters_comm:
                            ev["comm_bytes"] = comm_bytes
                        if strategy.tracks_inner_loss:
                            ev["inner_loss"] = float(round_losses[blk - 1])
                        history.append(ev)
                        if tracker is not None:
                            tracker.on_eval(ev)
                if ckpt_at(end):
                    with TraceAnnotation("engine.ckpt", block=i, rounds=blk):
                        # block-boundary COPIES: the live carry is donated
                        # to the next block, so the snapshot dispatches a
                        # device copy (async, off the host critical path)
                        # and hands THAT to the writer thread for the D2H
                        # transfer cross-host snapshots materialize to host
                        # numpy HERE (the replicating fetch is a collective
                        # every process must join); single-process runs
                        # keep the async device copy. Only process 0
                        # touches the filesystem.
                        snap_copy = fetch_tree if multiproc else _snapshot_copy
                        if host_resident:
                            # checkpoints always carry the FULL (N,)
                            # layout — identity straight from the host
                            # slabs (post write-back), buffer leaves
                            # device-copied — so snapshots restore into
                            # either residency
                            pool_snap = PoolState(
                                *(np.array(slabs[f])
                                  for f in ClientPool.SLAB_FIELDS),
                                *(snap_copy((
                                    pool_state.buf_updates,
                                    pool_state.buf_round,
                                    pool_state.buf_count,
                                    pool_state.flushes))))
                        elif pooled:
                            pool_snap = snap_copy(pool_state)
                        else:
                            pool_snap = None
                        state = RoundState(
                            round=end,
                            phi=(jax.tree.map(np.asarray, phi) if multiproc
                                 else _snapshot_copy(phi)),
                            pool_state=pool_snap,
                            per_client_bytes=per_client_bytes.copy(),
                            comm_bytes=comm_bytes, history=list(history),
                            host=host_snaps.pop(end),
                            fingerprint=fingerprint)
                        if multiproc and jax.process_index() != 0:
                            pass                 # peers only joined the fetch
                        elif writer is not None:
                            writer.submit_state(state)
                        else:
                            save_round_state(ckpt_dir, state, keep=ckpt_keep)
            if writer is not None:
                writer.close()      # drain pending snapshots; surface errors
            run_span.set_metadata(tokens=run_tokens)
        finally:
            staged_iter.close()
            if writer is not None:
                writer.close(raise_errors=False)

        out = {"params": phi, "history": history}
        if strategy.meters_comm:
            out["comm_bytes"] = comm_bytes
            # C-level tolist(), not a per-element int() loop: the bill has
            # pool.size entries, and a million-client fleet pays ~100ms for
            # the boxing loop vs ~10ms here
            out["per_client_bytes"] = per_client_bytes.tolist()
        if pooled:
            ps = fetch_tree(pool_state)
            # [:pool.size] drops the mesh shard-padding rows (a no-op slice
            # on unsharded runs); host-resident identity reads from the
            # slabs (the device window only holds the last block's rows)
            ident = (slabs if host_resident else
                     {f: getattr(ps, f) for f in ClientPool.SLAB_FIELDS})
            out["pool_state"] = {
                f: np.array(ident[f][:pool.size])
                for f in ClientPool.SLAB_FIELDS}
            if buffered is not None:
                out["pool_state"]["flushes"] = int(ps.flushes)
                # scalar off-mesh; per-shard fill levels (shards,) on mesh
                out["pool_state"]["buffered_pending"] = int(
                    np.asarray(ps.buf_count).sum())
        if tracker is not None:
            tracker.on_run_end(
                runner_cache_stats(),
                staleness=(out["pool_state"]["staleness"] if pooled else None))
        return out
