"""Host/device round pipeline: block planning, background prefetch, and
pluggable client-scheduling policies for the federated round engine.

PR 1 moved the round math on-device (vmap x lax.scan); PR 2 closed the
host/device gap (fixed-shape blocks, background prefetch); this module
now also owns the engine's per-round, per-client ROUND STATE:

- ``plan_blocks``: split a run into scan blocks at eval boundaries and
  ``max_block``, and pick ONE fixed padded length for every block in the
  run — the retrace-free shape contract (the block runner compiles once
  per strategy/channel/schedule-shape config; uneven eval/tail blocks
  are padded and masked instead of re-traced).
- ``BlockPrefetcher``: a background producer thread (the levanter
  background-data-loading pattern) that samples and stages block N+1
  while the device runs block N. Double-buffered at depth=2; the
  producer runs strictly in block order, so a seeded host RNG consumed
  inside ``produce`` sees exactly the synchronous draw order — pipelined
  and synchronous runs are bit-for-bit identical.
- ``ClientSchedule``: the structured scan carry that replaced the old
  "scalar validity bit + alpha" tuple. Per padded round it carries the
  validity bit, the annealed server rate, the ABSOLUTE round index
  (rotating ``PartialCommChannel`` masks fold it into their mask key
  inside the scan), and per cohort slot a participation mask, a local
  step count, and an aggregation weight. It is a registered pytree, so
  it device-stages through the prefetcher and slices through
  ``lax.scan`` like any other block input.
- ``SamplingPolicy``: which client tasks feed each round AND what the
  round's schedule looks like. ``UniformSampling`` (the paper's schema:
  everyone shows up, same step count, uniform weights) keeps the
  engine's legacy bit-for-bit fast path; ``PartialParticipation`` and
  ``StragglerSampling`` are the deployment-scenario plugins — a new
  scenario is a policy object, not a sixth training loop. Pooled runs
  (a persistent ``repro.core.pool.ClientPool``) additionally plan a
  per-round ``cohort`` of pool indices via ``plan_pool_schedule``;
  availability processes (diurnal / Markov check-ins) live in
  repro.core.pool and override that hook.
"""
from __future__ import annotations

import dataclasses
import queue
import threading
from typing import Callable, Dict, Iterator, List, Optional, Tuple

import jax
import numpy as np

SAMPLERS = ("reference", "vectorized")
#: the host data path of every policy and ClientPool not told otherwise:
#: one ``sample_support_block`` call a block. "reference" is the legacy
#: per-task loop, kept as the seeded-parity oracle.
DEFAULT_SAMPLER = "vectorized"


def check_sampler(sampler: str) -> None:
    """Raise unless ``sampler`` names a host data path."""
    if sampler not in SAMPLERS:
        raise ValueError(f"unknown sampler {sampler!r}; expected one of "
                         f"{SAMPLERS}")


def plan_blocks(rounds: int, eval_every: int, max_block: int, *,
                start: int = 0,
                ckpt_every: int = 0) -> Tuple[List[Tuple[int, int]], int]:
    """Split ``rounds`` into scan blocks; return ``(blocks, pad)``.

    ``blocks`` is a list of ``(start, end)`` half-open round ranges that
    cover ``[start, rounds)``, cut at every eval boundary (multiples of
    ``eval_every``), at every checkpoint boundary (multiples of
    ``ckpt_every``, when > 0 — snapshots must land on block ends), and
    at most ``max_block`` rounds long. ``pad`` is the single fixed
    length every block is padded to on the host —
    ``min(max_block, stride, ckpt_every, rounds)`` where ``stride`` is
    the eval cadence — so one run uses exactly one block shape
    regardless of ``rounds % eval_every`` or the tail.

    ``start`` > 0 fast-forwards the plan (the checkpoint-resume path):
    cuts are at ABSOLUTE round positions, so resuming from a block
    boundary replays exactly the uninterrupted run's remaining blocks.
    Block splitting itself is bitwise-neutral — the scan executes the
    same per-round ops in the same order however ``[start, rounds)`` is
    chunked — which is what lets checkpoint cuts and resume replans
    preserve bit-for-bit parity.
    """
    if max_block <= 0:
        raise ValueError(f"max_block must be positive, got {max_block!r}")
    if start < 0:
        raise ValueError(f"start must be >= 0, got {start!r}")
    if rounds <= start:
        return [], 0
    stride = eval_every if eval_every else rounds
    blocks: List[Tuple[int, int]] = []
    rnd = start
    while rnd < rounds:
        end = min(rounds, (rnd // stride + 1) * stride, rnd + max_block)
        if ckpt_every:
            end = min(end, (rnd // ckpt_every + 1) * ckpt_every)
        blocks.append((rnd, end))
        rnd = end
    pad = min(max_block, stride, rounds)
    if ckpt_every:
        pad = min(pad, ckpt_every)
    assert all(end - s <= pad for s, end in blocks)
    return blocks, pad


class BlockPrefetcher:
    """Run ``produce(i)`` for ``i in range(n)`` on a daemon thread, keeping
    at most ``depth`` staged results ahead of the consumer.

    ``produce`` typically samples a block on the host and ``device_put``s
    it, so H2D staging of block N+1 hides behind device compute on block N
    (``depth=2`` = classic double buffering). Items are produced strictly
    in order. Producer exceptions are re-raised from :meth:`get`, which
    raises ``StopIteration`` once all ``n`` items were consumed (no
    deadlock on over-consumption); call :meth:`close` (idempotent) to
    stop early without deadlocking the bounded queue.
    """

    _DONE = object()

    def __init__(self, produce: Callable[[int], object], n: int,
                 depth: int = 2):
        self._q: queue.Queue = queue.Queue(maxsize=max(1, depth))
        self._stop = threading.Event()
        self._done = False
        self._thread = threading.Thread(target=self._run, args=(produce, n),
                                        name="block-prefetch", daemon=True)
        self._thread.start()

    def _put(self, item) -> None:
        while not self._stop.is_set():
            try:
                self._q.put(item, timeout=0.1)
                return
            except queue.Full:
                continue

    def _run(self, produce, n) -> None:
        try:
            for i in range(n):
                if self._stop.is_set():
                    return
                self._put((None, produce(i)))
            self._put((None, self._DONE))
        except BaseException as exc:  # propagated to the consumer
            self._put((exc, None))

    def get(self):
        """Next staged item, blocking; re-raises producer exceptions and
        raises StopIteration once the stream is exhausted or closed."""
        if self._done:
            raise StopIteration("prefetcher exhausted")
        exc, item = self._q.get()
        if exc is not None:
            self._done = True
            self._stop.set()
            raise exc
        if item is self._DONE:
            self._done = True
            raise StopIteration("prefetcher exhausted")
        return item

    def close(self) -> None:
        """Stop the producer and drain the queue (safe to call twice)."""
        self._done = True
        self._stop.set()
        while True:
            try:
                self._q.get_nowait()
            except queue.Empty:
                break
        self._thread.join(timeout=5.0)


def block_shardings(mesh, axis: str, tree):
    """NamedSharding staging tree for one padded block on a client mesh.

    The prefetch producer's ``device_put`` target when the engine runs
    mesh-sharded: every leaf with a client axis — schedule rows and
    batch arrays, all shaped (padded rounds, clients, ...) — splits its
    dim 1 over the ``axis`` mesh axis so each device receives exactly
    its cohort shard (H2D staging of block N+1 still hides behind
    device compute on block N); per-round vectors (validity, alpha,
    round index) replicate. The cohort axis is already padded to a
    multiple of the shard count by the engine.
    """
    from jax.sharding import NamedSharding, PartitionSpec

    def of(x):
        spec = (PartitionSpec(None, axis) if np.ndim(x) >= 2
                else PartitionSpec())
        return NamedSharding(mesh, spec)

    return jax.tree.map(of, tree)


def single_device_of(tree):
    """The one device every jax leaf of ``tree`` lives on, or None (plain
    NumPy leaves, sharded/multi-device trees, empty trees). Prefetch
    producers must pin ``device_put`` to this explicitly —
    ``jax.default_device`` is thread-local and does not reach the
    background thread."""
    devices = {d for leaf in jax.tree.leaves(tree)
               if hasattr(leaf, "devices") for d in leaf.devices()}
    return devices.pop() if len(devices) == 1 else None


def prefetch_items(produce: Callable[[int], object], n: int,
                   depth: int = 2) -> Iterator[object]:
    """Yield ``produce(i)`` for ``i in range(n)``, staged up to ``depth``
    ahead by a :class:`BlockPrefetcher` thread. ``depth=0`` (or a single
    item) falls back to inline calls — same order, same numerics. The
    producer is shut down when the generator is exhausted or closed
    (``.close()`` / garbage collection), so early consumer exits don't
    leak the thread.
    """
    if depth <= 0 or n <= 1:
        for i in range(n):
            yield produce(i)
        return
    pf = BlockPrefetcher(produce, n, depth=depth)
    try:
        for _ in range(n):
            yield pf.get()
    finally:
        pf.close()


def seat_cohorts(rng, pool_size: int, clients: int,
                 rows: int) -> np.ndarray:
    """Uniform without-replacement cohort seating in O(rows * clients)
    host work, independent of ``pool_size``.

    ``Generator.choice(n, k, replace=False)`` permutes the full
    population internally — at n=10^6 with k=256 seats that is ~4000x
    more work than the seats drawn, and it dominated pooled-run
    planning at fleet scale. For sparse draws (k << n) rejection
    sampling touches O(k) candidates per row (expected collisions
    ~k^2/2n, vanishing as n grows); near-dense rows (8k >= n) keep the
    permutation draw, which is optimal there. Consumes ``rng``
    deterministically in row order — the stream contract of the default
    sampler (``sampler="reference"`` keeps the legacy per-round
    ``choice`` order bit-for-bit)."""
    out = np.empty((rows, clients), np.int32)
    if clients * 8 >= pool_size:
        for r in range(rows):
            out[r] = rng.choice(pool_size, size=clients, replace=False)
        return out
    for r in range(rows):
        seen = set()
        seats = []
        while len(seats) < clients:
            draw = rng.integers(pool_size,
                                size=clients - len(seats)).tolist()
            for cand in draw:
                if cand not in seen:
                    seen.add(cand)
                    seats.append(cand)
        out[r] = seats
    return out


@dataclasses.dataclass(frozen=True)
class ClientSchedule:
    """Per-round, per-client round state threaded through the block scan.

    One instance describes a whole padded block; ``lax.scan`` slices the
    leading (padded rounds) axis so each scan step sees one round's row.
    It is a registered pytree: it device-stages through the prefetcher
    and scans like any other block input, which is what keeps
    heterogeneous rounds at ZERO per-round host dispatches.

    valid:          (R,)    bool — False on padded rounds AND on pooled
                    rounds where no client checked in (both are runtime
                    no-ops: ``lax.cond`` passes the carry through).
    alpha:          (R,)    f32  — annealed server rate for the round.
    round_index:    (R,)    i32  — ABSOLUTE round number; rotating
                    partial-comm masks fold it into their mask key, and
                    pooled runs use it to stamp ``PoolState.last_seen``
                    and the FedBuff buffer's staleness tags.
    participation:  (R, C)  bool — which cohort slots train (and pay
                    transport) this round.
    local_steps:    (R, C)  i32  — per-client local step budget k_i, in
                    the strategy's own units (stream samples / epochs).
    weights:        (R, C)  f32  — aggregation weights, normalized per
                    round (0 for non-participants).
    cohort:         (R, C)  i32 or None — WHICH persistent pool client
                    occupies each cohort slot this round (indices into a
                    ``repro.core.pool.ClientPool``; unique per round).
                    The block runner gathers/scatters the pool's
                    cross-round state by these indices inside the scan.
                    None on legacy (pool-free) runs, where cohort slots
                    are anonymous and resampled every round.
    """
    valid: object
    alpha: object
    round_index: object
    participation: object
    local_steps: object
    weights: object
    cohort: object = None

    _FIELDS = ("valid", "alpha", "round_index", "participation",
               "local_steps", "weights", "cohort")


jax.tree_util.register_pytree_node(
    ClientSchedule,
    lambda s: (tuple(getattr(s, f) for f in ClientSchedule._FIELDS), None),
    lambda _, children: ClientSchedule(*children))


class SamplingPolicy:
    """Decides which client tasks feed each round of a block AND what the
    round's heterogeneity schedule is (who shows up, how many local steps
    each client runs, how the server weights their results).

    Both hooks must consume ``rng`` deterministically (the prefetch
    pipeline replays them strictly in block order): the engine calls
    ``plan_schedule`` first, then ``sample_block`` with the resulting
    participation mask.

    ``schedule_kind`` is a STATIC descriptor baked into the block
    runner's cache key: "uniform" keeps the legacy unweighted scan body
    (bit-for-bit identical to the pre-schedule engine), anything else
    selects the schedule-aware body (weighted aggregation + per-client
    step masking). It must be decidable at policy-construction time — the
    runner compiles once per (strategy, beta, channel, schedule_kind).
    """

    schedule_kind = "scheduled"
    #: the host data path ("vectorized" block call or the "reference"
    #: per-task loop); dataclass subclasses expose it as a field and
    #: validate it through ``__post_init__``
    sampler = DEFAULT_SAMPLER

    def __post_init__(self):
        check_sampler(self.sampler)

    def plan_schedule(self, rng, start: int, end: int, clients: int,
                      budget: int) -> Dict[str, np.ndarray]:
        """Schedule rows for rounds [start, end): a dict of NumPy arrays
        ``participation`` (blk, clients) bool, ``local_steps`` (blk,
        clients) int32, and per-round-normalized ``weights`` (blk,
        clients) float32. ``budget`` is the strategy's full per-client
        workload (``FedStrategy.local_step_budget``). The default is the
        homogeneous fleet: everyone participates, full budget, uniform
        weights — and consumes NO rng."""
        blk = end - start
        return {
            "participation": np.ones((blk, clients), bool),
            "local_steps": np.full((blk, clients), budget, np.int32),
            "weights": np.full((blk, clients), 1.0 / clients, np.float32),
        }

    def plan_pool_schedule(self, rng, start: int, end: int, clients: int,
                           budget: int,
                           pool_size: int) -> Dict[str, np.ndarray]:
        """Pooled-run schedule: ``plan_schedule``'s rows plus a
        ``cohort`` array ((blk, clients) int32) naming WHICH of the
        ``pool_size`` persistent clients occupies each cohort slot that
        round (indices must be unique within a round — the engine
        scatters per-client state by them). The default seats a uniform
        without-replacement draw each round, then delegates the
        heterogeneity rows to ``plan_schedule`` — so every existing
        policy (uniform, partial participation, stragglers) composes
        with a pool unchanged. RNG order: cohort draws first, then the
        ``plan_schedule`` draws; deterministic, block-ordered (the
        prefetch-parity contract). Availability processes
        (repro.core.pool) override this wholesale: who is in the cohort
        IS the schedule there."""
        blk = end - start
        if pool_size < clients:
            raise ValueError(f"pool_size={pool_size} is smaller than the "
                             f"cohort ({clients} slots): persistent "
                             f"clients cannot repeat within a round")
        if not blk:
            cohort = np.zeros((0, clients), np.int64)
        elif self.sampler == "vectorized":
            cohort = seat_cohorts(rng, pool_size, clients, blk)
        else:
            cohort = np.stack([
                rng.choice(pool_size, size=clients, replace=False)
                for _ in range(blk)])
        plan = self.plan_schedule(rng, start, end, clients, budget)
        plan["cohort"] = cohort.astype(np.int32)
        return plan

    def sample_block(self, task_dist, rng, rounds: int, clients: int,
                     support: int, data_mode: str,
                     participation: Optional[np.ndarray] = None) -> Dict:
        """Default data path shared by every shipped policy: dispatch to
        the distribution's ``sampler`` flavour ("reference" replays the
        legacy per-task RNG order; "vectorized" is the one-allocation
        fast path), schedule-driven by the participation mask."""
        if self.sampler == "vectorized":
            return task_dist.sample_support_block(
                rng, rounds, clients, support, data_mode,
                participation=participation)
        return task_dist.sample_support_block_reference(
            rng, rounds, clients, support, data_mode,
            participation=participation)

    def state_dict(self) -> Dict:
        """JSON-able cross-block host state, captured into round-state
        checkpoints (repro.checkpoint) so a resumed run continues the
        policy exactly where the interrupted one stopped. Stateless
        policies — every shipped one except
        ``repro.core.pool.MarkovAvailability``, whose two-state chain
        lives outside the rng stream — return {}."""
        return {}

    def load_state_dict(self, state: Dict, rng=None) -> None:
        """Restore a ``state_dict`` snapshot at resume. ``rng`` is the
        run's (already-restored) host generator, for policies whose
        stashed state is keyed by the stream driving it."""


@dataclasses.dataclass(frozen=True)
class UniformSampling(SamplingPolicy):
    """Every round draws ``clients`` fresh tasks i.i.d. — the paper's
    serial (C=1) and batched schema. The trivial schedule (full
    participation, full budget, uniform weights) keeps the engine on its
    legacy fast path: schedule_kind == "uniform" selects the unweighted
    scan body, so runs are bit-for-bit identical to the pre-schedule
    engine.

    The default sampler (``DEFAULT_SAMPLER``, as for every policy) takes
    each block from the distribution's batched ``sample_support_block``
    (block RNG order, one allocation); ``UniformSampling("reference")``
    replays the legacy per-task RNG order bit-for-bit (seeded parity
    with the pre-engine loops).
    """
    sampler: str = DEFAULT_SAMPLER

    schedule_kind = "uniform"


@dataclasses.dataclass(frozen=True)
class PartialParticipation(SamplingPolicy):
    """TinyMetaFed-style partial participation: each round only
    ``max(1, round(fraction * clients))`` cohort slots check in, train,
    and pay transport; the server averages over exactly the participants
    (weights 1/m on participants, 0 elsewhere).

    The default sampler samples the full block in one allocation and
    zeroes the scheduled-out slots afterwards. Under
    ``sampler="reference"`` scheduled-out slots draw NO task data from
    the host rng — the host-side sampling work scales with the fraction,
    which is where TinyMetaFed's savings come from.
    """
    fraction: float = 0.5
    sampler: str = DEFAULT_SAMPLER

    def __post_init__(self):
        if not 0.0 < self.fraction <= 1.0:
            raise ValueError(f"fraction must be in (0, 1], got "
                             f"{self.fraction!r}")
        super().__post_init__()

    def cohort(self, clients: int) -> int:
        """Participants per round."""
        return max(1, int(round(self.fraction * clients)))

    def plan_schedule(self, rng, start, end, clients, budget):
        blk, m = end - start, self.cohort(clients)
        part = np.zeros((blk, clients), bool)
        for r in range(blk):                 # one small choice per round
            part[r, rng.choice(clients, size=m, replace=False)] = True
        return {
            "participation": part,
            "local_steps": np.where(part, budget, 0).astype(np.int32),
            "weights": (part.astype(np.float32) / m),
        }


@dataclasses.dataclass(frozen=True)
class StragglerSampling(SamplingPolicy):
    """Heterogeneous-device straggler simulation: every client shows up,
    but each draws an i.i.d. local step budget k_i uniformly from
    ``[ceil(min_steps_frac * budget), budget]`` (slow MCUs deliver fewer
    local steps by the server's deadline). Aggregation is
    arrival-weighted: w_i = k_i / sum_j k_j, so a client that completed
    twice the local work moves the server twice as far. Everyone still
    ships a full (fraction-scaled, if the channel is partial) payload.

    The per-step masking rides the engine's existing lax.cond/validity
    machinery (steps >= k_i are runtime no-ops inside the client scan),
    so blocks stay fixed-shape and the runner still traces exactly once.
    """
    min_steps_frac: float = 0.25
    sampler: str = DEFAULT_SAMPLER

    def __post_init__(self):
        if not 0.0 < self.min_steps_frac <= 1.0:
            raise ValueError(f"min_steps_frac must be in (0, 1], got "
                             f"{self.min_steps_frac!r}")
        super().__post_init__()

    def plan_schedule(self, rng, start, end, clients, budget):
        blk = end - start
        lo = max(1, int(np.ceil(self.min_steps_frac * budget)))
        steps = rng.integers(lo, budget + 1,
                             size=(blk, clients)).astype(np.int32)
        weights = steps / steps.sum(axis=1, keepdims=True)
        return {
            "participation": np.ones((blk, clients), bool),
            "local_steps": steps,
            "weights": weights.astype(np.float32),
        }
