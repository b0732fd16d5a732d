"""Pluggable run metrics: counters, gauges, series, and latency
distributions, plus the engine/serving hooks that feed them.

One `MetricsTracker` instance follows one run (a ``run_federated`` call
or an ``AdaptationServer`` lifetime). It is pure host-side bookkeeping:
every hook takes already-materialized Python/NumPy values, so attaching
a tracker never changes what the device computes — ``run_federated``
with ``tracker=None`` and with a tracker produce bit-for-bit identical
params/history (pinned in tests/test_metrics.py).

Timings live in the profiler's trace, not here. The engine and the
server open ``jax.profiler.TraceAnnotation`` spans, which a running
profiler session (``jax.profiler.trace(dir)``) records on the device
trace's clock; with no session they cost about a microsecond each.
Counts ride on the spans as arguments. Nothing opens a span per client,
sample or request.

- ``engine.run``: one ``run_federated`` call; ``rounds``, ``clients``,
  ``seed``, ``blocks``, ``clients_per_pass`` (how many clients' working
  copies one pass of the client phase holds; ``clients`` where the whole
  cohort fits) and ``tokens`` (the support tokens the call streamed; 0
  where the client data are not token sequences).
- Per block, each with ``block`` (its index in the call) and ``rounds``
  (the block's rounds), which pair the prefetch thread's spans with the
  consumer's:
  ``engine.plan`` (the sampling policy's schedule), ``engine.sample``
  (its client data; ``sampler``: "vectorized" for one block call of
  the distribution, "reference" for the per-task loop, "pool" for a
  pooled run's check-ins), ``engine.stage`` (padding, the ``ClientSchedule``
  and the device transfer; ``bytes`` staged; a host-resident pool's
  identity window is a second one, on the consumer), ``engine.wait``
  (the consumer waiting for the block; with no prefetch thread it holds
  the block's plan, sample and stage), ``engine.dispatch`` (the block
  runner call; ``clients_per_pass``, and ``tokens``, the block's share
  of the run's), ``engine.fetch`` (the tracker's loss fetch and the
  host-resident pool's write-back), ``engine.eval``, ``engine.ckpt``.
- ``serve.tick``: one ``AdaptationServer.step`` that runs a tick;
  ``tick`` (its index), ``admitted``, ``retired``, ``live`` (the
  slot-steps that do real work: per slot in flight, the least of the
  tick's steps and the steps it has left) and ``computed`` (slots x
  steps per tick). Its leaves: ``serve.refill`` (admission;
  ``rid_lo``, ``rid_hi``: the half-open range of the request ids it
  admits), ``serve.dispatch`` (the refill's one transfer and the jitted
  tick; ``bytes``: the packed refill's size), ``serve.readback`` (the
  one read of the packed ``finished``, step counts and query losses;
  ``bytes``: its size) and ``serve.retire``.

An attached tracker gets the gauge ``engine.clients_per_pass``.

The device programs name their parts with ``jax.named_scope``: the
block runner's ``client_phase`` and ``server_update``, the tick's
``refill_scatter``, ``advance`` and ``query``.
"""
from __future__ import annotations

import collections
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np


class MetricsTracker:
    """Counters + gauges + per-round series + observation distributions.

    Vocabulary (all names are free-form dotted strings):

    - ``inc(name, v)``        monotonic counter (transport bytes, retires)
    - ``gauge(name, v)``      last-value-wins (trace counts, cache sizes)
    - ``record(name, step, v)`` per-step series (round -> loss)
    - ``observe(name, v)``    distribution sample (latencies, steps)

    ``percentiles`` summarizes observations; ``summary()`` returns one
    JSON-able dict of everything.
    """

    def __init__(self):
        self.counters: Dict[str, float] = collections.defaultdict(float)
        self.gauges: Dict[str, float] = {}
        self.series: Dict[str, List[Tuple[int, float]]] = (
            collections.defaultdict(list))
        self.observations: Dict[str, List[float]] = (
            collections.defaultdict(list))

    # -- primitives --------------------------------------------------------
    def inc(self, name: str, value: float = 1.0) -> None:
        self.counters[name] += value

    def gauge(self, name: str, value: float) -> None:
        self.gauges[name] = value

    def record(self, name: str, step: int, value: float) -> None:
        self.series[name].append((int(step), float(value)))

    def observe(self, name: str, value: float) -> None:
        self.observations[name].append(float(value))

    # -- summaries ---------------------------------------------------------
    def percentiles(self, name: str,
                    qs: Sequence[float] = (50.0, 95.0, 99.0)) -> Dict:
        """{"p50": ..., "p95": ..., ...} over the observations of
        ``name`` (empty dict when nothing was observed)."""
        vals = self.observations.get(name)
        if not vals:
            return {}
        pct = np.percentile(np.asarray(vals, np.float64), qs)
        return {f"p{q:g}": float(p) for q, p in zip(qs, pct)}

    def series_values(self, name: str) -> List[float]:
        return [v for _, v in self.series.get(name, [])]

    def summary(self) -> Dict:
        out = {"counters": dict(self.counters), "gauges": dict(self.gauges),
               "series": {k: list(v) for k, v in self.series.items()},
               "distributions": {}}
        for name, vals in self.observations.items():
            out["distributions"][name] = {
                "count": len(vals),
                "mean": float(np.mean(vals)),
                **self.percentiles(name)}
        return out

    # -- engine hooks (run_federated) --------------------------------------
    # All hooks receive host values the engine already has (or fetches
    # only when a tracker is attached); none of them feed anything back,
    # so the training trajectory is tracker-independent by construction.
    def on_block(self, start: int, end: int, losses) -> None:
        """Per-round inner losses of one executed scan block
        (``losses[i]`` is round ``start + i``'s cohort-weighted loss)."""
        losses = np.asarray(losses)
        for i, lo in enumerate(losses):
            self.record("round.inner_loss", start + i, float(lo))
        self.inc("engine.rounds", end - start)
        self.inc("engine.blocks")

    def on_transport(self, round_end: int, delta_bytes: int,
                     total_bytes: int) -> None:
        self.inc("transport.bytes", delta_bytes)
        self.record("transport.cum_bytes", round_end, float(total_bytes))

    def on_eval(self, ev: Dict) -> None:
        self.record("eval.query_loss", ev["round"],
                    float(ev["query_loss"]))
        self.inc("engine.evals")

    def on_run_end(self, runner_stats: Optional[Dict] = None,
                   staleness=None) -> None:
        if runner_stats:
            for k, v in runner_stats.items():
                self.gauge(f"runner_cache.{k}", float(v))
        if staleness is not None:
            for s in np.asarray(staleness).ravel():
                self.observe("pool.staleness", float(s))

    # -- serving hooks (AdaptationServer) ----------------------------------
    def on_admit(self, request_bytes: int) -> None:
        self.inc("serve.admitted")
        self.inc("serve.request_bytes", request_bytes)

    def on_retire(self, latency_s: float, steps: int) -> None:
        self.inc("serve.retired")
        self.observe("serve.latency_ms", 1e3 * latency_s)
        self.observe("serve.steps", steps)

    def on_tick(self) -> None:
        self.inc("serve.ticks")
