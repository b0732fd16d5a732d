"""Continuous-batching adaptation server over a meta-learned init.

The paper's deployment story: a NEW device checks in with a few support
samples, fine-tunes the broadcast phi for k steps, and is scored (or
scores itself) on its own query data. At fleet scale those check-ins
arrive as a ragged stream — every request has its own k — so the server
keeps a fixed set of B padded SLOTS on device and advances all of them
a few steps per jitted TICK (the engine's validity-mask idiom): retired
slots are refilled from a host FIFO between ticks by scattering fresh
rows with an out-of-range-drop index, never changing any shape, so the
whole serve loop is ONE jit trace per (adapter, slot-count, shapes)
config (`AdaptationServer.trace_count`, same observable as
`_BlockRunner.trace_count`).

phi rides the tick as a traced argument: swapping the init (say, a
`checkpoint.load_params` snapshot, or a newer phi mid-stream) reuses
the existing trace and executable.

Numerics: `offline_adapt` is the independently-jitted one-shot
reference — each request's served params/query loss are bit-for-bit
equal to the offline call at the same slot width (tests/test_serving.py
pins fp32 and int8; the int8 route is additionally exactly equal to the
engine's scalar TifedStrategy epochs).
"""
from __future__ import annotations

import collections
import dataclasses
import time
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.profiler import TraceAnnotation

#: a request's float32 fields, in the order a refill row holds them
_FIELDS = ("sx", "sy", "qx", "qy")
#: a refill row's leading int32 columns: the slot it fills and its k
_HEAD = 2


@dataclasses.dataclass
class AdaptResult:
    """One retired request: its id, adapted-params query loss, how many
    adaptation steps it ran, and submit->retire wall latency. ``params``
    is the adapted fp32 pytree when the server runs with
    ``return_params=True`` (off by default: shipping params home every
    tick costs a device sync per slot row)."""
    rid: int
    query_loss: float
    steps: int
    latency_s: float
    params: Optional[Dict] = None


class _Pending:
    """A queued request: ``row`` is its refill row, packed at submit
    (int32: the slot, filled at admission, then k, then the float32 bits
    of sx | sy | qx | qy flattened)."""
    __slots__ = ("rid", "k", "row", "t_submit")

    def __init__(self, rid, k, row, t_submit):
        self.rid, self.k, self.row = rid, k, row
        self.t_submit = t_submit


def _bcast(mask, like):
    return mask.reshape(mask.shape + (1,) * (like.ndim - 1))


class AdaptationServer:
    """Serve a ragged stream of client-adaptation requests against one
    meta-learned init.

    - ``adapter``: a `serving.adapters` adapter (Fp32Adapter /
      TifedAdapter) — defines prepare / unit-step / query-loss math.
    - ``slots``: continuous-batching width B (vmap width of every tick).
    - ``k_max``: static per-request step budget bound (requests ask for
      any ``1 <= k <= k_max``).
    - ``steps_per_tick``: adaptation steps advanced per jitted tick —
      the batching/latency knob (small = fresher admission, large =
      fewer host round-trips).
    - ``metrics``: optional `metering.MetricsTracker`; admission,
      retirement latency/steps, and tick counts flow into it.

    Usage::

        server = AdaptationServer(phi, adapter, slots=64, k_max=10)
        server.submit(sx, sy, qx, qy, k=7)
        results = server.drain()       # list of AdaptResult

    Request/query shapes are fixed by the FIRST submitted request; later
    requests must match. Each tick moves one packed array each way: the
    admitted requests' rows in, and ``finished``, the step counts and the
    query losses out.
    """

    def __init__(self, phi, adapter, *, slots: int, k_max: int,
                 steps_per_tick: int = 4, metrics=None,
                 return_params: bool = False):
        if slots < 1:
            raise ValueError(f"slots must be >= 1, got {slots}")
        if k_max < 1:
            raise ValueError(f"k_max must be >= 1, got {k_max}")
        if steps_per_tick < 1:
            raise ValueError(
                f"steps_per_tick must be >= 1, got {steps_per_tick}")
        self.adapter = adapter
        self.B = int(slots)
        self.k_max = int(k_max)
        self.steps_per_tick = int(steps_per_tick)
        self.metrics = metrics
        self.return_params = bool(return_params)
        self.trace_count = 0
        self.ticks = 0
        self._pack = adapter.pack_phi(phi)
        self._queue: collections.deque = collections.deque()
        self._inflight: Dict[int, _Pending] = {}
        self._free = list(range(self.B))      # ascending slot ids
        # adaptation steps each slot has still to run: the host's exact
        # count of the slot-steps a tick does real work on
        self._left = np.zeros(self.B, np.int64)
        self._next_rid = 0
        self._state = None                    # allocated on first step
        self._shapes = None                   # fixed by the first submit
        self._width = None                    # int32 columns of a row
        self._jit_tick = jax.jit(self._tick_fn, donate_argnums=(1,))

    # -- device program ----------------------------------------------------
    def _tick_fn(self, pack, state, refill):
        self.trace_count += 1                 # runs at trace time only
        B = self.B
        ad = self.adapter
        idx = refill[:, 0]                    # (B,) int32; idx == B drops
        with jax.named_scope("refill_scatter"):
            data = lax.bitcast_convert_type(refill[:, _HEAD:], jnp.float32)
            fields, lo = {}, 0
            for name in _FIELDS:
                shape = self._shapes[name]
                hi = lo + int(np.prod(shape))
                fields[name] = data[:, lo:hi].reshape((B,) + shape)
                lo = hi
            fresh = jax.vmap(lambda sx, sy: ad.prepare(pack, sx, sy))(
                fields["sx"], fields["sy"])
            slots = jax.tree.map(
                lambda s, f: s.at[idx].set(f, mode="drop"),
                state["slots"], fresh)
            qx = state["qx"].at[idx].set(fields["qx"], mode="drop")
            qy = state["qy"].at[idx].set(fields["qy"], mode="drop")
            k = state["k"].at[idx].set(refill[:, 1], mode="drop")
            step = state["step"].at[idx].set(0, mode="drop")
            active = state["active"].at[idx].set(True, mode="drop")
            qloss = state["qloss"].at[idx].set(0.0, mode="drop")

        with jax.named_scope("advance"):
            unit = jax.vmap(lambda s, t: ad.unit_step(pack, s, t))
            for _ in range(self.steps_per_tick):
                live = active & (step < k)
                new_slots, _ = unit(slots, step)
                slots = jax.tree.map(
                    lambda n, o: jnp.where(_bcast(live, n), n, o),
                    new_slots, slots)
                step = step + live.astype(jnp.int32)

        with jax.named_scope("query"):
            finished = active & (step >= k)
            ql = jax.vmap(lambda s, x, y: ad.query_loss(pack, s, x, y))(
                slots, qx, qy)
            qloss = jnp.where(finished, ql, qloss)
            active = active & ~finished
            params = (jax.vmap(lambda s: ad.finish(pack, s))(slots)
                      if self.return_params else ())
            # one (3, B) int32 read for the host: finished, step, and the
            # query losses' float32 bits
            out = jnp.stack([finished.astype(jnp.int32), step,
                             lax.bitcast_convert_type(qloss, jnp.int32)])
        new_state = {"slots": slots, "qx": qx, "qy": qy, "k": k,
                     "step": step, "active": active, "qloss": qloss}
        return new_state, out, params

    def _alloc_state(self):
        B = self.B
        sh = self._shapes
        sx0 = jnp.zeros((B,) + sh["sx"], jnp.float32)
        sy0 = jnp.zeros((B,) + sh["sy"], jnp.float32)
        slot_shapes = jax.eval_shape(
            jax.vmap(lambda sx, sy: self.adapter.prepare(
                self._pack, sx, sy)), sx0, sy0)
        self._state = {
            "slots": jax.tree.map(
                lambda a: jnp.zeros(a.shape, a.dtype), slot_shapes),
            "qx": jnp.zeros((B,) + sh["qx"], jnp.float32),
            "qy": jnp.zeros((B,) + sh["qy"], jnp.float32),
            "k": jnp.zeros((B,), jnp.int32),
            "step": jnp.zeros((B,), jnp.int32),
            "active": jnp.zeros((B,), bool),
            "qloss": jnp.zeros((B,), jnp.float32),
        }

    # -- host control loop -------------------------------------------------
    def submit(self, sx, sy, qx, qy, k: int) -> int:
        """Enqueue one adaptation request (FIFO). Returns its id."""
        sx = np.asarray(sx, np.float32)
        sy = np.asarray(sy, np.float32)
        qx = np.asarray(qx, np.float32)
        qy = np.asarray(qy, np.float32)
        k = int(k)
        if not 1 <= k <= self.k_max:
            raise ValueError(f"k={k} outside [1, {self.k_max}]")
        if k > sx.shape[0] and self.adapter.name == "fp32":
            raise ValueError(f"k={k} online steps need >= k support "
                             f"samples, got {sx.shape[0]}")
        arrays = (sx, sy, qx, qy)
        if self._shapes is None:
            self._shapes = {name: arr.shape
                            for name, arr in zip(_FIELDS, arrays)}
            self._width = _HEAD + sum(a.size for a in arrays)
        for name, arr in zip(_FIELDS, arrays):
            if arr.shape != self._shapes[name]:
                raise ValueError(
                    f"{name} shape {arr.shape} != server shape "
                    f"{self._shapes[name]} (fixed by first request)")
        row = np.empty(self._width, np.int32)
        row[1] = k
        np.concatenate([a.ravel() for a in arrays],
                       out=row[_HEAD:].view(np.float32))
        rid = self._next_rid
        self._next_rid += 1
        self._queue.append(_Pending(rid, k, row, time.monotonic()))
        if self.metrics is not None:
            self.metrics.on_admit(
                sx.nbytes + sy.nbytes + qx.nbytes + qy.nbytes)
        return rid

    def _build_refill(self):
        """The tick's refill, one (B, W) int32 host array with a row per
        admitted request (the queue's head first) and rows that fill slot
        B, which the tick drops, after them; and the admitted count."""
        B = self.B
        refill = np.zeros((B, self._width), np.int32)
        refill[:, 0] = B
        n = 0
        while self._queue and self._free:
            req = self._queue.popleft()
            slot = self._free.pop(0)          # lowest free slot first
            refill[n] = req.row
            refill[n, 0] = slot
            self._left[slot] = req.k
            self._inflight[slot] = req
            n += 1
        return refill, n

    def step(self) -> List[AdaptResult]:
        """Admit waiting requests into free slots, run ONE tick, retire
        finished slots. Returns this tick's retired results."""
        if not self._queue and not self._inflight:
            return []
        with TraceAnnotation("serve.tick", tick=self.ticks,
                             computed=self.B * self.steps_per_tick) as span:
            if self._state is None:
                self._alloc_state()
            # admission is FIFO, so a tick admits consecutive ids
            rid_lo = self._queue[0].rid if self._queue else self._next_rid
            with TraceAnnotation("serve.refill") as refill_span:
                refill, admitted = self._build_refill()
                refill_span.set_metadata(rid_lo=rid_lo,
                                         rid_hi=rid_lo + admitted)
            live = np.minimum(self._left, self.steps_per_tick)
            self._left -= live
            # one explicit transfer each way; the refill is fresh each
            # tick, so the runtime may alias it without a copy
            with TraceAnnotation("serve.dispatch", bytes=refill.nbytes):
                self._state, out, params = self._jit_tick(
                    self._pack, self._state, jax.device_put(refill))
                out.copy_to_host_async()
            self.ticks += 1
            if self.metrics is not None:
                self.metrics.on_tick()
            with TraceAnnotation("serve.readback", bytes=out.nbytes):
                out = np.asarray(out)
                done = np.flatnonzero(out[0])
                st, ql = out[1], out[2].view(np.float32)
            results: List[AdaptResult] = []
            with TraceAnnotation("serve.retire"):
                now = time.monotonic()
                for slot in done.tolist():
                    req = self._inflight.pop(slot)
                    self._free.append(slot)
                    p = None
                    if self.return_params:
                        p = jax.tree.map(lambda a: np.asarray(a[slot]),
                                         params)
                    res = AdaptResult(rid=req.rid,
                                      query_loss=float(ql[slot]),
                                      steps=int(st[slot]),
                                      latency_s=now - req.t_submit,
                                      params=p)
                    results.append(res)
                    if self.metrics is not None:
                        self.metrics.on_retire(res.latency_s, res.steps)
                if done.size:
                    self._free.sort()
            span.set_metadata(admitted=admitted, retired=len(results),
                              live=int(live.sum()))
        return results

    def drain(self) -> List[AdaptResult]:
        """Tick until the queue and every slot are empty."""
        results: List[AdaptResult] = []
        while self._queue or self._inflight:
            results.extend(self.step())
        return results

    @property
    def idle(self) -> bool:
        return not self._queue and not self._inflight

    def set_params(self, phi) -> None:
        """Swap the served init. phi is a tick ARGUMENT, so this reuses
        the existing trace (trace_count stays put). Requires an idle
        server — in-flight requests must finish against their phi."""
        if not self.idle:
            raise RuntimeError("cannot swap phi with requests in flight")
        self._pack = self.adapter.pack_phi(phi)

    def reset(self) -> None:
        """Drop all queued work and re-zero the slot state (the jit
        trace and phi pack survive)."""
        self._queue.clear()
        self._inflight.clear()
        self._free = list(range(self.B))
        self._left[:] = 0
        self.ticks = 0
        if self._state is not None:
            self._state = jax.tree.map(
                lambda a: jnp.zeros(a.shape, a.dtype), self._state)


def offline_adapt(phi, adapter, requests, *, slots: int,
                  k_max: int) -> List[Dict]:
    """One-shot reference adaptation: pack ``requests`` (dicts with
    sx/sy/qx/qy/k) FIFO into width-``slots`` groups and run each group's
    full k_max-step masked scan under ONE separately-jitted vmap. This
    is the parity oracle for `AdaptationServer` — same unit-step math,
    same slot width, independent trace — and the cheapest way to adapt
    a request set you already hold in memory.

    Returns one {"params", "query_loss", "steps"} dict per request, in
    submission order.
    """
    if not requests:
        return []
    pack = adapter.pack_phi(phi)
    B = int(slots)

    @jax.jit
    def run(pack, sx, sy, qx, qy, k, active):
        fresh = jax.vmap(lambda x, y: adapter.prepare(pack, x, y))(sx, sy)
        step = jnp.zeros((B,), jnp.int32)
        unit = jax.vmap(lambda s, t: adapter.unit_step(pack, s, t))
        slots_ = fresh
        for _ in range(k_max):
            live = active & (step < k)
            new_slots, _ = unit(slots_, step)
            slots_ = jax.tree.map(
                lambda n, o: jnp.where(_bcast(live, n), n, o),
                new_slots, slots_)
            step = step + live.astype(jnp.int32)
        ql = jax.vmap(lambda s, x, y: adapter.query_loss(pack, s, x, y))(
            slots_, qx, qy)
        params = jax.vmap(lambda s: adapter.finish(pack, s))(slots_)
        return params, ql, step

    out: List[Dict] = []
    for g0 in range(0, len(requests), B):
        group = requests[g0:g0 + B]
        pad = B - len(group)
        stack = {f: np.stack([np.asarray(r[f], np.float32)
                              for r in group] +
                             [np.zeros_like(np.asarray(group[0][f],
                                                       np.float32))] * pad)
                 for f in ("sx", "sy", "qx", "qy")}
        kv = np.asarray([r["k"] for r in group] + [0] * pad, np.int32)
        active = np.asarray([True] * len(group) + [False] * pad)
        params, ql, step = run(pack, stack["sx"], stack["sy"],
                               stack["qx"], stack["qy"], kv, active)
        ql = np.asarray(ql)
        step = np.asarray(step)
        for i in range(len(group)):
            out.append({
                "params": jax.tree.map(lambda a, i=i: np.asarray(a[i]),
                                       params),
                "query_loss": float(ql[i]),
                "steps": int(step[i])})
    return out
