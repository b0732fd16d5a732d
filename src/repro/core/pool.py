"""Persistent client identities: the ClientPool layer.

TinyReptile's serial protocol assumes each device KEEPS its data and
state across check-ins, but the engine historically resampled anonymous
cohort slots every round. This module makes the population first-class:

- ``ClientPool``: N persistent clients. Client ``i``'s task is
  materialized ONCE from ``(seed, i)`` (``TaskDistribution.
  materialize_client`` — the stable per-device data shard TinyMetaFed
  measures its savings against), and each client owns a private data
  RNG stream advanced only at its own check-ins, so what client ``i``
  sees depends only on how often IT has checked in — not on who else
  was scheduled.
- ``PoolState``: the cross-round per-client state pytree (last-seen
  round, staleness counters, check-in counts, and the FedBuff pending
  update buffer). It lives on device, rides the block runner's scan
  carry next to phi, and is gathered/scattered by the round's cohort
  indices INSIDE the scan — zero per-round host dispatches, one jit
  trace per (strategy, beta, channel, schedule-shape, pool-shape)
  config.
- ``BufferedAggregation``: FedBuff-style async aggregation
  [Nguyen et al. 2022]. Check-ins append their (possibly stale) updates
  to a server-side buffer; the buffer flushes every ``buffer_size``
  arrivals through the strategy's existing ``server_aggregate_weighted``
  hook with staleness-discounted weights (default 1/sqrt(1+tau), the
  FedBuff polynomial discount).
- ``AvailabilityProcess`` policies: check-in schedules beyond i.i.d. —
  ``DiurnalAvailability`` (fleet-wide sine: devices sleep at night) and
  ``MarkovAvailability`` (two-state sticky on/off chains per client).
  Rounds where NOBODY checks in are valid=False scan no-ops: the server
  idles, nobody trains, nobody pays transport.

``UniformSampling`` with ``pool=None`` keeps the engine's legacy
bit-for-bit fast path (pinned in tests/test_pool.py): the pool layer is
strictly additive.
"""
from __future__ import annotations

import collections
import copy
import dataclasses
import os
from typing import Callable, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.pipeline import DEFAULT_SAMPLER, SamplingPolicy, check_sampler
from repro.data.tasks import TaskDistribution

#: stream-key constants: keep a pool's task seeds, per-client data
#: streams, and shape probes on disjoint rng streams. _TASK_STREAM is
#: the ``materialize_client`` derivation (data/tasks.py) — the
#: vectorized sampler re-derives it per check-in instead of caching the
#: task object.
_DATA_STREAM = 0x5EED
_PROBE_STREAM = 0x9
_TASK_STREAM = 0x9E37

#: bound on the (support, data_mode) shape-template cache — a run uses
#: one or two keys; the bound only guards pathological callers.
_MAX_TEMPLATES = 16

#: residency of the per-client identity arrays (see ClientPool).
RESIDENCIES = ("device", "host")


def default_staleness_weight(tau):
    """FedBuff's polynomial staleness discount: s(tau) = 1/sqrt(1+tau).
    ``tau`` is a traced f32 array of "rounds since this update was
    computed" at flush time; fresh updates weigh 1, a 3-round-stale
    update half that."""
    return 1.0 / jnp.sqrt(1.0 + tau)


@dataclasses.dataclass(frozen=True)
class PoolState:
    """Cross-round per-client state, on device, scanned next to phi.

    The first three fields are per-POOL-CLIENT arrays (length N = pool
    size), gathered/scattered by the round's ``ClientSchedule.cohort``
    indices inside the block-runner scan; the last four are the
    server-side FedBuff buffer (None on unbuffered runs).

    last_seen:   (N,) i32 — absolute round of the client's most recent
                 check-in; -1 for clients that never checked in.
    staleness:   (N,) i32 — the gap (in rounds) between the client's two
                 most recent check-ins, stamped AT check-in: a client
                 seen at rounds 3 and 7 carries staleness 4. First
                 check-ins count from round -1 (pool creation). This is
                 the per-device staleness the paper's serial protocol
                 implies and the example prints per client.
    checkins:    (N,) i32 — total rounds the client participated in.
    buf_updates: result-shaped tree, each leaf with a leading
                 (buffer_size + cohort - 1,) capacity axis — the pending
                 (not yet applied) client updates. None when unbuffered.
    buf_round:   (capacity,) i32 — the absolute round each buffered
                 update was computed at (its staleness tag). None when
                 unbuffered.
    buf_count:   () i32 — arrivals since the last flush (valid buffer
                 prefix length). None when unbuffered.
    flushes:     () i32 — how many times the buffer flushed into phi.
                 None when unbuffered.

    Mesh runs (run_federated(mesh=...)) use the SHARDED layout built by
    ``ClientPool.init_state(shards=...)``: per-client arrays padded to a
    multiple of the shard count and split over the "clients" mesh axis,
    the buffer stored as per-shard slabs, and ``buf_count`` a (shards,)
    array of local fill levels (the flush predicate reduces it with
    psum). ``pool_state_specs`` names each field's PartitionSpec.
    """
    last_seen: object
    staleness: object
    checkins: object
    buf_updates: object = None
    buf_round: object = None
    buf_count: object = None
    flushes: object = None

    _FIELDS = ("last_seen", "staleness", "checkins", "buf_updates",
               "buf_round", "buf_count", "flushes")


jax.tree_util.register_pytree_node(
    PoolState,
    lambda s: (tuple(getattr(s, f) for f in PoolState._FIELDS), None),
    lambda _, children: PoolState(*children))


@dataclasses.dataclass(frozen=True)
class BufferedAggregation:
    """FedBuff-style buffered async aggregation [Nguyen et al. 2022].

    Instead of folding each round's cohort into phi immediately, every
    check-in APPENDS its update to a server-side buffer; once
    ``buffer_size`` updates have arrived the whole buffer flushes
    through the strategy's ``server_aggregate_weighted`` hook in one
    step, weighted by ``staleness_fn(tau)`` (tau = flush round minus the
    round each update was computed at) and normalized. Between flushes
    phi does not move — buffered updates are genuinely stale when
    applied, which is exactly the async-fleet regime FedBuff models.

    Arrivals land at round granularity: a round that pushes the count to
    ``buffer_size`` or beyond flushes the ENTIRE buffer (up to
    buffer_size + cohort - 1 updates), so the capacity is static and the
    flush is a single ``lax.cond`` inside the scan — no host round-trip.

    ``flush_staleness`` makes the flush AVAILABILITY-AWARE: in a sparse
    fleet (diurnal troughs, small cohorts) a count-only buffer can sit
    on updates for many rounds, so the flush predicate additionally
    fires whenever HOLDING the buffer one more round would let its
    oldest update reach the staleness deadline — i.e. the buffer
    flushes at the end of round r if ``r - min(buffered rounds) + 1 >=
    flush_staleness`` (one extra comparison OR-ed into the existing
    ``lax.cond`` predicate, still zero host round-trips). No buffered
    update is ever applied with staleness >= flush_staleness, so a
    deadline of 1 degenerates to flush-on-every-arrival (every update
    applied the round it was computed, tau = 0).

    buffer_size:  flush threshold K, in client arrivals (>= 1).
    staleness_fn: traced discount tau -> weight; default FedBuff's
                  1/sqrt(1+tau). Must be a hashable callable (module
                  function or frozen partial) for the runner cache.
    flush_staleness: optional staleness deadline (rounds, >= 1); None
                  (default) keeps the count-only FedBuff flush.
    """
    buffer_size: int = 4
    staleness_fn: Callable = default_staleness_weight
    flush_staleness: Optional[int] = None

    def __post_init__(self):
        if not (isinstance(self.buffer_size, int) and self.buffer_size >= 1):
            raise ValueError(f"buffer_size must be an int >= 1, got "
                             f"{self.buffer_size!r}")
        if self.flush_staleness is not None and not (
                isinstance(self.flush_staleness, int)
                and self.flush_staleness >= 1):
            raise ValueError(f"flush_staleness must be None or an int >= 1, "
                             f"got {self.flush_staleness!r}")


class ClientPool:
    """A population of ``size`` persistent clients over a task
    distribution.

    Host side (this class): each client's STABLE task derives from
    ``(seed, i)`` via ``task_dist.materialize_client``, and each
    client's data stream advances only at its own check-ins, so its
    sample sequence is a function of its check-in count alone.
    ``sample_cohort_block`` draws a block of cohort data in strict block
    order (the prefetch thread's determinism contract).

    Two host-identity representations:

    - ``sampler="reference"`` (legacy bit-for-bit): one cached task
      object and one live ``np.random.Generator`` per client that ever
      checked in — O(active clients) host objects, generators never
      evictable (their stream state is irreplaceable).
    - ``sampler="vectorized"`` (``DEFAULT_SAMPLER``): NO per-client
      host objects. The pool keeps ONE ``(N,)`` int32 check-in counter
      array; client ``i``'s ``k``-th check-in draws from the
      counter-derived streams ``default_rng([seed, _TASK_STREAM, i])``
      (task params — the ``materialize_client`` derivation) and
      ``default_rng([seed, _DATA_STREAM, i, k])`` (data), routed through
      ``TaskDistribution.sample_client_support``. Host memory is
      O(cohort) per round plus the counters; ``host_state()`` shrinks
      from a dict of bit-generator states to the nonzero counters.
      A NEW deterministic stream contract (same precedent as the
      engine's vectorized block sampler), not bit-equal to reference.

    ``residency="host"`` additionally keeps the per-client
    :class:`PoolState` identity arrays in host slabs (optionally
    memory-mapped under ``mmap_dir``): the engine stages only the
    cohort's rows to device each block and scatters them back after —
    see ``init_slabs`` / ``gather_rows`` / ``scatter_rows``.

    Device side: ``init_state`` builds the :class:`PoolState` pytree the
    engine threads through the block-runner scan.
    """

    #: host-slab field names, mirroring PoolState's per-client arrays.
    SLAB_FIELDS = ("last_seen", "staleness", "checkins")

    def __init__(self, task_dist: TaskDistribution, size: int,
                 seed: int = 0, *, sampler: str = DEFAULT_SAMPLER,
                 residency: str = "device",
                 mmap_dir: Optional[str] = None,
                 max_cached_tasks: int = 4096):
        if size < 1:
            raise ValueError(f"pool size must be >= 1, got {size!r}")
        check_sampler(sampler)
        if residency not in RESIDENCIES:
            raise ValueError(f"unknown residency {residency!r}; "
                             f"expected one of {RESIDENCIES}")
        if mmap_dir is not None and residency != "host":
            raise ValueError("mmap_dir only applies to residency='host' "
                             "(device-resident pools have no host slabs "
                             "to back with files)")
        if not (isinstance(max_cached_tasks, int)
                and max_cached_tasks >= 1):
            raise ValueError(f"max_cached_tasks must be an int >= 1, "
                             f"got {max_cached_tasks!r}")
        self.task_dist = task_dist
        self.size = int(size)
        self.seed = int(seed)
        self.sampler = sampler
        self.residency = residency
        self.mmap_dir = mmap_dir
        self.max_cached_tasks = int(max_cached_tasks)
        self._tasks: "collections.OrderedDict[int, object]" = \
            collections.OrderedDict()
        self._rngs: Dict[int, np.random.Generator] = {}
        self._templates: "collections.OrderedDict[tuple, tuple]" = \
            collections.OrderedDict()
        #: vectorized-sampler identity: client i's next check-in index.
        self._checkins = (np.zeros(self.size, np.int32)
                          if sampler == "vectorized" else None)
        self._slabs: Optional[Dict[str, np.ndarray]] = None

    def __repr__(self):
        return (f"ClientPool({type(self.task_dist).__name__}, "
                f"size={self.size}, seed={self.seed}, "
                f"sampler={self.sampler!r}, residency={self.residency!r})")

    def client_task(self, i: int):
        """Pool client ``i``'s stable task. Cached in a bounded LRU
        (``max_cached_tasks``): tasks are pure functions of
        ``(seed, i)``, so eviction only costs rematerialization — a
        long-lived million-client pool no longer accretes one Python
        task object per client it has ever seen."""
        if not 0 <= i < self.size:
            raise IndexError(f"client {i} out of range for pool of "
                             f"{self.size}")
        t = self._tasks.get(i)
        if t is None:
            t = self.task_dist.materialize_client(i, seed=self.seed)
            self._tasks[i] = t
            while len(self._tasks) > self.max_cached_tasks:
                self._tasks.popitem(last=False)
        else:
            self._tasks.move_to_end(i)
        return t

    def _client_rng(self, i: int) -> np.random.Generator:
        # Reference-sampler identity. These generators hold irreplaceable
        # mid-stream state, so the dict grows with the number of DISTINCT
        # clients ever seated — the legacy O(N) liability the
        # sampler="vectorized" counter derivation exists to remove.
        if i not in self._rngs:
            self._rngs[i] = np.random.default_rng(
                [self.seed, _DATA_STREAM, i])
        return self._rngs[i]

    def host_state(self) -> Dict:
        """JSON-able snapshot of the pool's mutable host state, paired
        with :meth:`load_host_state` for bit-for-bit checkpoint resume.
        Tasks and templates are NOT captured — they are pure functions
        of ``(seed, i)`` and rematerialize on demand.

        - reference sampler: ``{"rngs": {client: bit-generator state}}``
          — one entry per client that ever checked in.
        - vectorized sampler: ``{"checkins": {client: count}}`` — just
          the NONZERO check-in counters (the whole mutable state; the
          streams re-derive from ``(seed, i, k)``). Compact even at
          N=10^6."""
        if self.sampler == "vectorized":
            nz = np.flatnonzero(self._checkins)
            return {"checkins": {str(int(i)): int(self._checkins[i])
                                 for i in nz}}
        return {"rngs": {str(i): copy.deepcopy(g.bit_generator.state)
                         for i, g in self._rngs.items()}}

    def load_host_state(self, state: Dict) -> None:
        """Restore a :meth:`host_state` snapshot: captured clients
        resume mid-stream; clients absent from the snapshot fall back
        to their fresh seeded stream (they had never checked in). The
        snapshot format must match this pool's sampler — a legacy rng
        snapshot cannot seed counters (or vice versa) and raises rather
        than silently replaying data."""
        state = state or {}
        if self.sampler == "vectorized":
            if state.get("rngs"):
                raise ValueError(
                    "checkpoint holds a legacy per-client rng snapshot "
                    "('rngs'), but this pool uses sampler='vectorized' "
                    "(counter-based streams); resume with "
                    "ClientPool(..., sampler='reference') or restart "
                    "the run")
            self._checkins = np.zeros(self.size, np.int32)
            for key, k in (state.get("checkins") or {}).items():
                i = int(key)
                if not 0 <= i < self.size:
                    raise ValueError(f"checkpointed counter for client "
                                     f"{i} out of range for pool of "
                                     f"{self.size}")
                self._checkins[i] = int(k)
            return
        if state.get("checkins"):
            raise ValueError(
                "checkpoint holds a check-in counter snapshot "
                "('checkins'), but this pool uses sampler='reference' "
                "(per-client rng streams); resume with "
                "ClientPool(..., sampler='vectorized') or restart the "
                "run")
        self._rngs = {}
        for key, st in state.get("rngs", {}).items():
            g = np.random.default_rng()
            g.bit_generator.state = st
            self._rngs[int(key)] = g

    def _template(self, support: int, data_mode: str):
        """Zero-cost shape probe: one throwaway draw from client 0's
        task on a DEDICATED rng stream (never touches the per-client
        data streams), cached per (support, data_mode)."""
        key = (support, data_mode)
        if key not in self._templates:
            rng = np.random.default_rng([self.seed, _PROBE_STREAM])
            x, y = self._draw(self.client_task(0), rng, support, data_mode)
            self._templates[key] = (np.zeros_like(x), np.zeros_like(y))
            while len(self._templates) > _MAX_TEMPLATES:
                self._templates.popitem(last=False)
        else:
            self._templates.move_to_end(key)
        return self._templates[key]

    @staticmethod
    def _draw(task, rng, support: int, data_mode: str):
        if data_mode == "stream":
            sx, sy = zip(*task.support_stream(rng, support))
            return np.stack(sx), np.stack(sy)
        b = task.support_batch(rng, support)
        return np.asarray(b["x"]), np.asarray(b["y"])

    def sample_cohort_block(self, cohort, participation, support: int,
                            data_mode: str = "batch") -> Dict:
        """Support data for a planned block: for every participating
        (round, slot), draw ``support`` samples from THAT pool client's
        stable task using ITS private stream. Scheduled-out slots (and
        whole no-show rounds) stay zero. Called strictly in block
        order, so a client's data stream advances once per check-in —
        deterministic regardless of prefetch depth or who else was
        scheduled. Dispatches on the pool's ``sampler``: "reference"
        replays the legacy cached-generator path bit-for-bit,
        "vectorized" derives both streams from the check-in counters
        and draws each slot's support set in O(1) array calls."""
        cohort = np.asarray(cohort)
        part = np.asarray(participation, bool)
        rounds, clients = part.shape
        zx, zy = self._template(support, data_mode)
        x = np.zeros((rounds, clients) + zx.shape, zx.dtype)
        y = np.zeros((rounds, clients) + zy.shape, zy.dtype)
        if self.sampler == "vectorized":
            self._fill_block_counter(cohort, part, support, data_mode,
                                     x, y)
        else:
            self._fill_block_reference(cohort, part, support, data_mode,
                                       x, y)
        return {"x": x, "y": y}

    def _fill_block_reference(self, cohort, part, support, data_mode,
                              x, y):
        rounds, clients = part.shape
        for r in range(rounds):
            for c in range(clients):
                if not part[r, c]:
                    continue
                m = int(cohort[r, c])
                x[r, c], y[r, c] = self._draw(
                    self.client_task(m), self._client_rng(m), support,
                    data_mode)

    def _fill_block_counter(self, cohort, part, support, data_mode,
                            x, y):
        # One pass over the PARTICIPATING slots only (np.nonzero, not a
        # rounds x clients scan): each seats client m at its k-th
        # check-in and draws from the (seed, m, k)-derived streams, then
        # advances the counter. Cohorts are unique within a round, so
        # slot order within a round cannot change any client's k.
        counters = self._checkins
        rs, cs = np.nonzero(part)
        for r, c in zip(rs.tolist(), cs.tolist()):
            m = int(cohort[r, c])
            k = int(counters[m])
            x[r, c], y[r, c] = self.task_dist.sample_client_support(
                np.random.default_rng([self.seed, _TASK_STREAM, m]),
                np.random.default_rng([self.seed, _DATA_STREAM, m, k]),
                support, data_mode)
            counters[m] = k + 1

    def init_slabs(self, shards: int = 1) -> Dict[str, np.ndarray]:
        """Allocate (or reuse) the host-resident per-client identity
        slabs for ``residency="host"`` runs: one ``(n,)`` int32 array
        per :class:`PoolState` identity field (n = pool size rounded up
        to the shard multiple; padded rows are never seated). With
        ``mmap_dir`` the slabs are file-backed ``np.memmap``\\ s, so the
        O(N) identity state need not even occupy RAM."""
        if self.residency != "host":
            raise ValueError("init_slabs requires "
                             "ClientPool(residency='host')")
        shards = max(int(shards), 1)
        n = -(-self.size // shards) * shards
        if (self._slabs is not None
                and len(self._slabs["last_seen"]) == n):
            return self._slabs
        fill = {"last_seen": -1, "staleness": 0, "checkins": 0}
        slabs = {}
        for name in self.SLAB_FIELDS:
            if self.mmap_dir is not None:
                os.makedirs(self.mmap_dir, exist_ok=True)
                arr = np.memmap(
                    os.path.join(self.mmap_dir, f"pool_{name}.i32"),
                    dtype=np.int32, mode="w+", shape=(n,))
            else:
                arr = np.empty((n,), np.int32)
            arr[:] = fill[name]
            slabs[name] = arr
        self._slabs = slabs
        return slabs

    def gather_rows(self, idx) -> Dict[str, np.ndarray]:
        """Rows ``idx`` of the host identity slabs, as fresh (len(idx),)
        int32 arrays (fancy indexing copies — safe to stage to device
        while the slabs keep mutating)."""
        if self._slabs is None:
            raise ValueError("no host slabs: call init_slabs first")
        return {name: np.asarray(slab[idx])
                for name, slab in self._slabs.items()}

    def scatter_rows(self, idx, rows: Dict[str, np.ndarray]) -> None:
        """Write a block's updated identity rows back into the host
        slabs (the device->host half of the gathered-slab round trip)."""
        if self._slabs is None:
            raise ValueError("no host slabs: call init_slabs first")
        for name, slab in self._slabs.items():
            slab[idx] = np.asarray(rows[name], np.int32)

    def init_state(self, phi, cohort_size: int,
                   buffered: Optional[BufferedAggregation] = None,
                   shards: int = 1, template=None,
                   rows: Optional[int] = None) -> PoolState:
        """Fresh device-resident pool state. The FedBuff buffer's static
        capacity is ``buffer_size + cohort_size - 1``: a flush triggers
        at count >= buffer_size, and at most cohort_size arrivals land
        per round on top of a count of at most buffer_size - 1.

        ``template`` (default phi) gives the SHAPES/DTYPES of the
        buffer slots — the strategy's uplink tree
        (``FedStrategy.uplink_template``), so quantized strategies
        stage their native int8 result trees at int8 width.

        ``shards`` > 1 builds the MESH layout (run_federated(mesh=...)):
        the per-client arrays are padded to a multiple of ``shards`` so
        the "clients" mesh axis splits them evenly (padded rows are
        never indexed — cohort indices stay < pool size), and the
        FedBuff buffer becomes per-shard: each shard owns a
        ``buffer_size + local_cohort - 1`` slab (any one shard can hold
        the whole count-threshold backlog plus its own round of
        arrivals, since the flush predicate is on the psum-reduced
        GLOBAL count), with ``buf_count`` a (shards,) array of local
        fill levels. ``shards == 1`` is bit-for-bit the legacy layout
        (scalar ``buf_count``, one contiguous buffer).

        ``rows`` overrides the per-client axis length (the
        ``residency="host"`` gathered-slab window: device state holds
        only that many staged rows, remapped window-local by the
        engine, while the full (N,) identity lives in the host slabs).
        The FedBuff buffer is SERVER-side state and keeps its usual
        capacity regardless of ``rows``."""
        if cohort_size % max(shards, 1):
            raise ValueError(f"cohort_size={cohort_size} must be a "
                             f"multiple of shards={shards} (the engine "
                             f"pads the cohort before building state)")
        if rows is None:
            n = -(-self.size // shards) * shards    # ceil to shard multiple
        else:
            if rows % max(shards, 1):
                raise ValueError(f"rows={rows} must be a multiple of "
                                 f"shards={shards}")
            n = int(rows)
        last_seen = jnp.full((n,), -1, jnp.int32)
        staleness = jnp.zeros((n,), jnp.int32)
        checkins = jnp.zeros((n,), jnp.int32)
        if buffered is None:
            return PoolState(last_seen, staleness, checkins)
        if shards == 1:
            cap = buffered.buffer_size + cohort_size - 1
            buf_count = jnp.int32(0)
        else:
            cap = shards * (buffered.buffer_size
                            + cohort_size // shards - 1)
            buf_count = jnp.zeros((shards,), jnp.int32)
        buf = jax.tree.map(
            lambda p: jnp.zeros((cap,) + p.shape, p.dtype),
            phi if template is None else template)
        return PoolState(last_seen, staleness, checkins, buf,
                         jnp.zeros((cap,), jnp.int32), buf_count,
                         jnp.int32(0))


def pool_state_specs(state: PoolState, axis: str) -> PoolState:
    """PartitionSpecs mirroring ``state`` for a client-sharded mesh run:
    per-client arrays and the per-shard FedBuff slabs split over the
    ``axis`` mesh axis, the flush counter replicated. Used both as the
    block runner's shard_map in/out specs and (wrapped in
    NamedSharding) as the host-side device_put target."""
    from jax.sharding import PartitionSpec as P
    sharded = P(axis)
    return PoolState(
        last_seen=sharded, staleness=sharded, checkins=sharded,
        buf_updates=(None if state.buf_updates is None else
                     jax.tree.map(lambda _: sharded, state.buf_updates)),
        buf_round=None if state.buf_round is None else sharded,
        # shards == 1 layout (also the 2-D GSPMD route) keeps a SCALAR
        # fill counter — replicate it; the mesh layout's (shards,)
        # vector of local fill levels splits like the rows
        buf_count=(None if state.buf_count is None else
                   (sharded if jnp.ndim(state.buf_count) else P())),
        flushes=None if state.flushes is None else P())


@dataclasses.dataclass(frozen=True)
class AvailabilityProcess(SamplingPolicy):
    """Base class for check-in processes over a persistent pool: who is
    AVAILABLE each round is a stochastic process over the N pool
    clients, and the round's cohort is whoever showed up (capped at the
    cohort width by a uniform thinning draw).

    Subclasses implement :meth:`availability` — a (blk, N) boolean
    matrix for rounds [start, end), consuming ``rng`` deterministically
    in block order (the prefetch-parity contract; the engine always
    calls contiguous blocks in order, starting at round 0).

    Rounds where nobody checks in plan an all-False participation row;
    the engine marks them valid=False, so the server idles that round
    (phi and pool state pass through, zero transport billed) — the
    fixed-shape scan never retraces. These policies only make sense
    over a pool: ``plan_schedule`` (the anonymous-cohort hook) raises.
    """
    sampler: str = DEFAULT_SAMPLER

    schedule_kind = "scheduled"

    def availability(self, rng, start: int, end: int,
                     pool_size: int) -> np.ndarray:
        raise NotImplementedError

    def plan_schedule(self, rng, start, end, clients, budget):
        raise ValueError(
            f"{type(self).__name__} schedules PERSISTENT clients; pass "
            f"pool=ClientPool(...) to run_federated (anonymous cohort "
            f"slots have no identity to be available or not)")

    def plan_pool_schedule(self, rng, start, end, clients, budget,
                           pool_size):
        avail = np.asarray(
            self.availability(rng, start, end, pool_size), bool)
        blk = end - start
        assert avail.shape == (blk, pool_size)
        if self.sampler == "vectorized":
            cohort, part = self._seat_available_block(rng, avail, clients)
        else:
            cohort = np.zeros((blk, clients), np.int32)
            part = np.zeros((blk, clients), bool)
            for r in range(blk):
                idx = np.flatnonzero(avail[r])
                if len(idx) > clients:  # more volunteers than slots
                    idx = np.sort(
                        rng.choice(idx, size=clients, replace=False))
                m = len(idx)
                cohort[r, :m] = idx
                part[r, :m] = True
        m_per_round = part.sum(axis=1, keepdims=True)
        weights = np.where(
            m_per_round > 0, part / np.maximum(m_per_round, 1), 0.0)
        return {
            "participation": part,
            "local_steps": np.where(part, budget, 0).astype(np.int32),
            "weights": weights.astype(np.float32),
            "cohort": cohort,
        }

    @staticmethod
    def _seat_available_block(rng, avail, clients):
        """Loop-free cohort seating for the whole block: every available
        client draws one uniform key, each round keeps the ``clients``
        smallest keys (a uniform without-replacement thinning), and a
        sort packs the winners ascending into the leading slots — the
        reference layout (sorted cohort, False tail), on a NEW rng
        stream contract (one (blk, N) key draw instead of per-round
        ``choice`` calls)."""
        blk, pool_size = avail.shape
        k = min(clients, pool_size)
        keys = np.where(avail, rng.uniform(size=avail.shape), np.inf)
        cand = np.argpartition(keys, k - 1, axis=1)[:, :k]
        alive = np.isfinite(np.take_along_axis(keys, cand, axis=1))
        seats = np.sort(np.where(alive, cand, pool_size), axis=1)
        cohort = np.zeros((blk, clients), np.int32)
        part = np.zeros((blk, clients), bool)
        part[:, :k] = seats < pool_size
        cohort[:, :k] = np.where(part[:, :k], seats, 0)
        return cohort, part


@dataclasses.dataclass(frozen=True)
class DiurnalAvailability(AvailabilityProcess):
    """Fleet-wide diurnal check-ins: client ``i`` is available at round
    ``r`` with probability
    ``clip(base + amplitude * sin(2*pi*(r/period + phase_i)), 0, 1)``.

    With the default ``phase_spread=0`` the whole fleet shares one sine
    (everyone's devices sleep at night — the classic diurnal load
    curve, including trough rounds where NOBODY may check in);
    ``phase_spread=1`` staggers phases evenly across clients (a fleet
    spanning all timezones, whose aggregate availability is flat).
    """
    period: int = 24
    base: float = 0.5
    amplitude: float = 0.45
    phase_spread: float = 0.0

    def __post_init__(self):
        if self.period < 1:
            raise ValueError(f"period must be >= 1, got {self.period!r}")
        # base/amplitude/phase_spread are probability-curve parameters:
        # reject out-of-range values at construction (parse) time rather
        # than silently clipping into a degenerate fleet.
        if not 0.0 <= self.base <= 1.0:
            raise ValueError(f"base must be in [0, 1] (a check-in "
                             f"probability), got {self.base!r}")
        if not 0.0 <= self.amplitude <= 1.0:
            raise ValueError(f"amplitude must be in [0, 1], got "
                             f"{self.amplitude!r}")
        if not 0.0 <= self.phase_spread <= 1.0:
            raise ValueError(f"phase_spread must be in [0, 1] (fraction "
                             f"of the fleet's phase fan-out), got "
                             f"{self.phase_spread!r}")
        super().__post_init__()

    def availability(self, rng, start, end, pool_size):
        r = np.arange(start, end, dtype=np.float64)[:, None]
        phase = (self.phase_spread
                 * np.arange(pool_size, dtype=np.float64)[None, :]
                 / max(pool_size, 1))
        p = np.clip(self.base + self.amplitude
                    * np.sin(2.0 * np.pi * (r / self.period + phase)),
                    0.0, 1.0)
        return rng.uniform(size=p.shape) < p


@dataclasses.dataclass(frozen=True)
class MarkovAvailability(AvailabilityProcess):
    """Two-state (on/off) Markov check-ins per client: an off client
    turns on with probability ``p_on`` each round, an on client turns
    off with ``p_off`` — sticky sessions and dropouts rather than
    i.i.d. coin flips. Long-run availability is the chain's stationary
    rate ``p_on / (p_on + p_off)``; chains start from a stationary draw
    at round 0.

    The chain state must survive across scan blocks: the policy stashes
    the ONE in-flight trajectory (keyed by the rng stream driving it,
    held strongly so the key can never be a recycled object) and
    requires contiguous in-order blocks — exactly how the engine's
    prefetch producer calls it. A fresh run starts at round 0, which
    resets the stash, so one policy instance serves any number of
    sequential runs without growing state.
    """
    p_on: float = 0.3
    p_off: float = 0.15
    #: single-slot chain stash: (rng, pool_size, next_start, state)
    _chain: list = dataclasses.field(default_factory=list, repr=False,
                                     compare=False)

    def __post_init__(self):
        for name in ("p_on", "p_off"):
            v = getattr(self, name)
            if not 0.0 < v <= 1.0:
                raise ValueError(f"{name} must be in (0, 1], got {v!r}")
        super().__post_init__()

    def availability(self, rng, start, end, pool_size):
        if start == 0:
            self._chain.clear()          # a fresh trajectory begins
            state = rng.uniform(size=pool_size) < (
                self.p_on / (self.p_on + self.p_off))
        elif (self._chain and self._chain[0] is rng
                and self._chain[1] == pool_size
                and self._chain[2] == start):
            state = self._chain[3]
        else:
            raise RuntimeError(
                f"MarkovAvailability needs contiguous in-order blocks "
                f"from one rng stream: got start={start} with no "
                f"matching chain state (blocks must begin at round 0 "
                f"and follow back-to-back)")
        rows = np.zeros((end - start, pool_size), bool)
        for r in range(end - start):
            u = rng.uniform(size=pool_size)
            state = np.where(state, u >= self.p_off, u < self.p_on)
            rows[r] = state
        self._chain[:] = [rng, pool_size, end, state.copy()]
        return rows

    def state_dict(self):
        """The in-flight chain (pool size, next expected block start,
        per-client on/off booleans) — the one piece of policy state the
        restored rng stream alone cannot rebuild, captured into
        round-state checkpoints. {} when no trajectory is in flight."""
        if not self._chain:
            return {}
        return {"pool_size": int(self._chain[1]),
                "next_start": int(self._chain[2]),
                "state": np.asarray(self._chain[3], bool).tolist()}

    def load_state_dict(self, state, rng=None):
        """Prime the chain stash from a ``state_dict`` snapshot so the
        resumed run's first block (``start == next_start``) continues
        the interrupted trajectory; ``rng`` must be the run's restored
        host generator (the stash is keyed by stream identity)."""
        if not state:
            self._chain.clear()
            return
        self._chain[:] = [rng, int(state["pool_size"]),
                          int(state["next_start"]),
                          np.asarray(state["state"], bool)]
