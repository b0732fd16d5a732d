"""The pluggable MetricsTracker (metering.tracker).

Contracts:

- INERT: attaching a tracker to ``run_federated`` changes NOTHING the
  run computes — params and history are bit-for-bit identical with and
  without it (the per-block loss fetch only happens when a tracker is
  present, so tracker=None also stays fetch-free).
- FAITHFUL: everything the tracker reports is cross-checkable against
  the run's own outputs — per-round inner-loss series vs history rows,
  transport counters vs comm_bytes, staleness observations vs
  pool_state, eval series vs history.
- The summary math (percentiles) matches NumPy.
- The serving hooks agree with the AdaptationServer's own ledger.
"""
import functools

import jax
import numpy as np
import pytest

from repro.configs.paper_models import SINE_MLP
from repro.core import ClientPool, CommChannel, run_federated
from repro.core.strategies import TinyReptileStrategy
from repro.data import SineTasks
from repro.metering import MetricsTracker
from repro.models.paper_nets import init_paper_model, paper_model_loss

LOSS = functools.partial(paper_model_loss, SINE_MLP)
ROUNDS, EVERY = 12, 4
KW = dict(rounds=ROUNDS, clients_per_round=2, support=6, seed=3,
          eval_every=EVERY,
          eval_kwargs=dict(num_tasks=2, support=4, k_steps=2, lr=0.02,
                           query=8))


def _run(tracker=None):
    phi = init_paper_model(SINE_MLP, jax.random.PRNGKey(0))
    return run_federated(phi, SineTasks(), TinyReptileStrategy(LOSS),
                         channel=CommChannel("float32"),
                         pool=ClientPool(SineTasks(), 5),
                         tracker=tracker, **KW)


@pytest.fixture(scope="module")
def tracked():
    tracker = MetricsTracker()
    return _run(tracker), tracker


def test_tracker_is_bitwise_inert(tracked):
    """tracker=None and tracker=MetricsTracker() produce identical runs:
    params bit-for-bit, history row-for-row."""
    out_t, _ = tracked
    out = _run(tracker=None)
    for a, b in zip(jax.tree.leaves(out["params"]),
                    jax.tree.leaves(out_t["params"])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert len(out["history"]) == len(out_t["history"])
    for ra, rb in zip(out["history"], out_t["history"]):
        assert ra.keys() == rb.keys()
        for k in ra:
            assert ra[k] == rb[k], k
    assert out["comm_bytes"] == out_t["comm_bytes"]


def test_round_loss_series_matches_history(tracked):
    """"round.inner_loss" covers every round exactly once, and at each
    eval round equals the history row's inner_loss."""
    out, tr = tracked
    series = tr.series["round.inner_loss"]
    assert [s for s, _ in series] == list(range(ROUNDS))
    by_round = dict(series)
    for row in out["history"]:
        assert by_round[row["round"] - 1] == row["inner_loss"]
    assert tr.counters["engine.rounds"] == ROUNDS
    assert tr.counters["engine.blocks"] >= 1


def test_eval_series_matches_history(tracked):
    out, tr = tracked
    assert tr.series["eval.query_loss"] == [
        (row["round"], float(row["query_loss"])) for row in out["history"]]
    assert tr.counters["engine.evals"] == len(out["history"])
    assert len(out["history"]) == ROUNDS // EVERY


def test_transport_counters_match_comm_bytes(tracked):
    out, tr = tracked
    assert tr.counters["transport.bytes"] == out["comm_bytes"]
    cum = tr.series_values("transport.cum_bytes")
    assert cum[-1] == out["comm_bytes"]
    assert cum == sorted(cum)                       # monotone bill


def test_staleness_observations_match_pool_state(tracked):
    out, tr = tracked
    np.testing.assert_array_equal(
        np.sort(tr.observations["pool.staleness"]),
        np.sort(np.asarray(out["pool_state"]["staleness"], np.float64)))


def test_run_end_gauges(tracked):
    _, tr = tracked
    assert any(k.startswith("runner_cache.") for k in tr.gauges)


def test_percentiles_match_numpy():
    tr = MetricsTracker()
    vals = np.random.default_rng(0).normal(size=257)
    for v in vals:
        tr.observe("x", v)
    got = tr.percentiles("x", qs=(50.0, 95.0, 99.0))
    want = np.percentile(vals, [50.0, 95.0, 99.0])
    assert got == {"p50": want[0], "p95": want[1], "p99": want[2]}
    assert tr.percentiles("missing") == {}
    summ = tr.summary()
    assert summ["distributions"]["x"]["count"] == 257


def test_serving_hooks_match_server_ledger():
    from repro.serving import AdaptationServer, Fp32Adapter
    phi = init_paper_model(SINE_MLP, jax.random.PRNGKey(0))
    tr = MetricsTracker()
    server = AdaptationServer(phi, Fp32Adapter(loss_fn=LOSS),
                              slots=4, k_max=5, steps_per_tick=2,
                              metrics=tr)
    rng = np.random.default_rng(0)
    n = 9
    for i in range(n):
        sx = rng.uniform(-5, 5, (6, 1)).astype(np.float32)
        qx = rng.uniform(-5, 5, (4, 1)).astype(np.float32)
        server.submit(sx, np.sin(sx, dtype=np.float32),
                      qx, np.sin(qx, dtype=np.float32), 1 + i % 5)
    results = server.drain()
    assert tr.counters["serve.admitted"] == n
    assert tr.counters["serve.retired"] == len(results) == n
    assert tr.counters["serve.ticks"] == server.ticks
    assert sorted(tr.observations["serve.steps"]) == sorted(
        float(r.steps) for r in results)
    pcts = tr.percentiles("serve.latency_ms")
    assert set(pcts) == {"p50", "p95", "p99"}
    assert all(v > 0 for v in pcts.values())
