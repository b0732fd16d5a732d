"""The ClientPool persistent-identity layer (PR 4).

Covers the tentpole contracts:
- stable identities: materialize_client determinism, per-client data
  streams that depend only on the client's own check-in count;
- pool state round-trip through the scan: the device-side gather/scatter
  of last_seen/staleness/checkins by cohort indices reproduces a host
  replay of the planned schedule exactly, and the billing cross-checks
  (per_client_bytes == 2 * payload * checkins);
- staleness counters under PartialParticipation over a pool;
- BufferedAggregation (FedBuff) flush semantics: flush cadence, phi
  frozen between flushes, flush-every-round degenerating to the
  unbuffered pooled run, staleness discounts favoring fresh updates;
- Markov / diurnal availability statistics + the no-show-round no-op;
- one default host data path: every shipped policy takes ClientPool's
  and, with everyone participating, draws UniformSampling()'s block;
- the legacy fast path with pool=None stays bit-for-bit (pinned), and
  pooled runs trace exactly once per config.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.paper_models import SINE_MLP
from repro.core import (BufferedAggregation, ClientPool, CommChannel,
                        DiurnalAvailability, MarkovAvailability,
                        PartialParticipation, StragglerSampling,
                        UniformSampling,
                        clear_runner_cache, plan_blocks, reptile_train,
                        run_federated, tinyreptile_train)
from repro.core.engine import _block_runner
from repro.core.pool import PoolState, default_staleness_weight
from repro.core.strategies import TinyReptileStrategy
from repro.data import SineTasks
from repro.models.paper_nets import init_paper_model, paper_model_loss

LOSS = functools.partial(paper_model_loss, SINE_MLP)
EVAL = dict(num_tasks=2, support=4, k_steps=2, lr=0.02, query=8)


@pytest.fixture(scope="module")
def setup():
    return init_paper_model(SINE_MLP, jax.random.PRNGKey(0)), SineTasks()


def _assert_trees_equal(a, b):
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def _assert_trees_close(a, b, tol=1e-5):
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                   rtol=tol, atol=tol)


# ---------------------------------------------------------------------------
# stable identities
# ---------------------------------------------------------------------------

def test_materialize_client_is_stable():
    dist = SineTasks()
    a = dist.materialize_client(3, seed=7)
    b = dist.materialize_client(3, seed=7)
    r1, r2 = np.random.default_rng(0), np.random.default_rng(0)
    xa, ya = a.make_sample(r1)
    xb, yb = b.make_sample(r2)
    np.testing.assert_array_equal(xa, xb)
    np.testing.assert_array_equal(ya, yb)          # same task both times
    c = dist.materialize_client(4, seed=7)
    xc, yc = c.make_sample(np.random.default_rng(0))
    assert not np.array_equal(ya, yc)              # different client


def test_pool_data_depends_only_on_own_checkins():
    """Client 2's k-th check-in draws the same data whether or not other
    clients were scheduled around it."""
    part_a = np.array([[True, True], [True, True]])
    cohort_a = np.array([[2, 5], [2, 1]], np.int32)
    pool = functools.partial(ClientPool, SineTasks(), 8, seed=0,
                             sampler="reference")
    got_a = pool().sample_cohort_block(cohort_a, part_a, support=4)
    part_b = np.array([[True, False], [True, False]])
    cohort_b = np.array([[2, 0], [2, 0]], np.int32)
    got_b = pool().sample_cohort_block(cohort_b, part_b, support=4)
    np.testing.assert_array_equal(got_a["x"][0, 0], got_b["x"][0, 0])
    np.testing.assert_array_equal(got_a["x"][1, 0], got_b["x"][1, 0])
    # consecutive check-ins advance the client's private stream
    assert not np.array_equal(got_a["x"][0, 0], got_a["x"][1, 0])
    # scheduled-out slots stay zero
    assert (got_b["x"][:, 1] == 0).all() and (got_b["y"][:, 1] == 0).all()


def test_pool_validation():
    with pytest.raises(ValueError):
        ClientPool(SineTasks(), 0)
    with pytest.raises(IndexError):
        ClientPool(SineTasks(), 4).client_task(4)
    with pytest.raises(ValueError, match="buffer_size"):
        BufferedAggregation(0)
    with pytest.raises(ValueError, match="pool_size"):
        UniformSampling().plan_pool_schedule(
            np.random.default_rng(0), 0, 4, clients=8, budget=2,
            pool_size=4)


@pytest.mark.parametrize("policy", [
    UniformSampling(), PartialParticipation(1.0), StragglerSampling(),
    DiurnalAvailability(), MarkovAvailability(),
], ids=lambda p: type(p).__name__)
def test_every_policy_takes_the_pool_default(policy):
    """Every shipped policy's default sampler is ClientPool's, and with
    full participation it draws the same block as UniformSampling()
    from the same generator state."""
    dist = SineTasks()
    assert policy.sampler == ClientPool(dist, 4).sampler
    part = np.ones((3, 4), bool)
    got, want = (p.sample_block(dist, np.random.default_rng(5), 3, 4, 6,
                                "batch", participation=part)
                 for p in (policy, UniformSampling()))
    assert got.keys() == want.keys()
    for k in want:
        np.testing.assert_array_equal(got[k], want[k], err_msg=k)


# ---------------------------------------------------------------------------
# pool state round-trip through the scan (gather/scatter parity)
# ---------------------------------------------------------------------------

def _replay_pool_state(policy, seed, rounds, eval_every, max_block,
                       clients, budget, pool_size):
    """Host-side replay of the engine's schedule planning: the expected
    last_seen/staleness/checkins the device scan must reproduce."""
    rng = np.random.default_rng(seed)
    last_seen = np.full(pool_size, -1, np.int64)
    staleness = np.zeros(pool_size, np.int64)
    checkins = np.zeros(pool_size, np.int64)
    for start, end in plan_blocks(rounds, eval_every, max_block)[0]:
        plan = policy.plan_pool_schedule(rng, start, end, clients, budget,
                                         pool_size)
        for j, r in enumerate(range(start, end)):
            for c in range(clients):
                if plan["participation"][j, c]:
                    m = plan["cohort"][j, c]
                    staleness[m] = r - last_seen[m]
                    last_seen[m] = r
                    checkins[m] += 1
    return last_seen, staleness, checkins


@pytest.mark.parametrize("policy", [
    UniformSampling(),
    PartialParticipation(0.5),
    DiurnalAvailability(period=5),
])
def test_pool_state_scan_matches_host_replay(setup, policy):
    """The in-scan gather/scatter of per-client state by cohort indices
    is exact: a pure-host replay of the same planned schedule produces
    identical last_seen/staleness/checkins — across uneven eval blocks
    and both prefetch modes."""
    params, dist = setup
    kw = dict(rounds=13, beta=0.02, support=4, seed=6, eval_every=5,
              eval_kwargs=EVAL, clients_per_round=3)
    out = tinyreptile_train(LOSS, params, dist, pool=ClientPool(dist, 7),
                            sampling=policy, **kw)
    want = _replay_pool_state(policy, seed=6, rounds=13, eval_every=5,
                              max_block=512, clients=3,
                              budget=4, pool_size=7)
    got = out["pool_state"]
    np.testing.assert_array_equal(got["last_seen"], want[0])
    np.testing.assert_array_equal(got["staleness"], want[1])
    np.testing.assert_array_equal(got["checkins"], want[2])
    # billing cross-check: every client pays exactly per check-in
    payload = CommChannel().payload_bytes(params)
    np.testing.assert_array_equal(out["per_client_bytes"],
                                  2 * payload * want[2])
    assert out["comm_bytes"] == sum(out["per_client_bytes"])


def test_pooled_prefetch_parity(setup):
    params, dist = setup
    kw = dict(rounds=11, beta=0.02, support=4, seed=2, eval_every=4,
              eval_kwargs=EVAL, clients_per_round=3, epochs=2,
              sampling=PartialParticipation(0.5))
    sync = reptile_train(LOSS, params, dist, prefetch=0,
                         pool=ClientPool(dist, 6), **kw)
    piped = reptile_train(LOSS, params, dist, prefetch=2,
                          pool=ClientPool(dist, 6), **kw)
    _assert_trees_equal(sync["params"], piped["params"])
    assert sync["history"] == piped["history"]
    for k in ("last_seen", "staleness", "checkins"):
        np.testing.assert_array_equal(sync["pool_state"][k],
                                      piped["pool_state"][k])
    assert sync["per_client_bytes"] == piped["per_client_bytes"]


def test_staleness_under_partial_participation(setup):
    """With a 50% check-in fraction over a pool twice the cohort size,
    clients skip rounds: staleness counters exceed 1 and check-ins sum
    to exactly participants-per-round x rounds."""
    params, dist = setup
    policy = PartialParticipation(0.5)
    out = tinyreptile_train(LOSS, params, dist, rounds=16, beta=0.02,
                            support=4, seed=3, clients_per_round=4,
                            sampling=policy, pool=ClientPool(dist, 8))
    ps = out["pool_state"]
    assert ps["checkins"].sum() == 16 * policy.cohort(4)
    assert (ps["last_seen"] < 16).all()
    seen = ps["checkins"] > 0
    assert (ps["staleness"][seen] >= 1).all()
    assert ps["staleness"].max() > 1               # somebody skipped rounds


# ---------------------------------------------------------------------------
# BufferedAggregation (FedBuff) flush semantics
# ---------------------------------------------------------------------------

def test_fedbuff_flush_cadence(setup):
    """Full participation, cohort C, threshold K: arrivals accumulate C
    per round and the buffer flushes every ceil(K/C) rounds."""
    params, dist = setup
    out = tinyreptile_train(LOSS, params, dist, rounds=10, beta=0.02,
                            support=4, seed=0, clients_per_round=3,
                            pool=ClientPool(dist, 6),
                            buffered=BufferedAggregation(4))
    # counts: 3, 6 -> flush, 3, 6 -> flush ... = one flush per 2 rounds
    assert out["pool_state"]["flushes"] == 5
    assert out["pool_state"]["buffered_pending"] == 0
    for l in jax.tree.leaves(out["params"]):
        assert np.isfinite(np.asarray(l)).all()


def test_fedbuff_phi_frozen_until_first_flush(setup):
    """A threshold larger than the run's total arrivals never flushes:
    phi must come back bit-identical to the init (async aggregation
    really is the only write path)."""
    params, dist = setup
    out = tinyreptile_train(LOSS, params, dist, rounds=4, beta=0.02,
                            support=4, seed=0, clients_per_round=2,
                            pool=ClientPool(dist, 4),
                            buffered=BufferedAggregation(100))
    assert out["pool_state"]["flushes"] == 0
    assert out["pool_state"]["buffered_pending"] == 8     # 4 rounds x 2
    _assert_trees_equal(out["params"], params)
    # ... but identity state still advanced (check-ins happened)
    assert out["pool_state"]["checkins"].sum() == 8


def test_fedbuff_flush_every_round_matches_unbuffered(setup):
    """buffer_size == cohort makes every round flush its own arrivals
    with zero staleness -> uniform weights: identical to the unbuffered
    pooled run (the degeneracy criterion for the async path)."""
    params, dist = setup
    kw = dict(rounds=8, beta=0.02, support=4, seed=5, clients_per_round=3,
              eval_every=8, eval_kwargs=EVAL)
    plain = tinyreptile_train(LOSS, params, dist,
                              pool=ClientPool(dist, 6), **kw)
    buff = tinyreptile_train(LOSS, params, dist,
                             pool=ClientPool(dist, 6),
                             buffered=BufferedAggregation(3), **kw)
    assert buff["pool_state"]["flushes"] == 8
    _assert_trees_close(plain["params"], buff["params"])
    np.testing.assert_allclose(plain["history"][-1]["query_loss"],
                               buff["history"][-1]["query_loss"],
                               rtol=1e-4, atol=1e-5)


def test_fedbuff_staleness_discount_weights():
    """The flush's staleness weighting: updates buffered longer ago get
    discounted by staleness_fn and the weights renormalize."""
    w = np.asarray(default_staleness_weight(jnp.asarray([0.0, 3.0])))
    np.testing.assert_allclose(w, [1.0, 0.5])
    # direct scan-level check: two buffered updates, one fresh, one
    # 3 rounds stale -> flush folds them 2/3 : 1/3
    phi = {"w": jnp.zeros((2,), jnp.float32)}
    strat = TinyReptileStrategy(LOSS, use_pallas=False)
    buf = {"w": jnp.asarray([[3.0, 3.0], [6.0, 6.0], [0.0, 0.0]])}
    buf_round = jnp.asarray([4, 1, 0], jnp.int32)   # taus at r=4: 0, 3
    tau = (4 - buf_round).astype(jnp.float32)
    w = default_staleness_weight(tau) * (jnp.arange(3) < 2)
    w = w / w.sum()
    got = strat.server_aggregate_weighted(
        phi, buf, jnp.float32(1.0), jnp.float32(0.01), w)
    np.testing.assert_allclose(np.asarray(got["w"]),
                               [4.0, 4.0], rtol=1e-6)  # 2/3*3 + 1/3*6


def test_fedbuff_flush_staleness_deadline_of_one_degenerates(setup):
    """Availability-aware FedBuff: flush_staleness=1 means no buffered
    update may ever reach staleness 1, i.e. the buffer flushes every
    round that has arrivals (tau = 0 at every flush) — identical to the
    count-based flush-every-round run even with a huge count
    threshold."""
    params, dist = setup
    kw = dict(rounds=8, beta=0.02, support=4, seed=5, clients_per_round=3,
              eval_every=8, eval_kwargs=EVAL)
    by_count = tinyreptile_train(LOSS, params, dist,
                                 pool=ClientPool(dist, 6),
                                 buffered=BufferedAggregation(3), **kw)
    by_deadline = tinyreptile_train(
        LOSS, params, dist, pool=ClientPool(dist, 6),
        buffered=BufferedAggregation(100, flush_staleness=1), **kw)
    assert by_deadline["pool_state"]["flushes"] == 8
    assert by_deadline["pool_state"]["buffered_pending"] == 0
    _assert_trees_close(by_count["params"], by_deadline["params"])
    np.testing.assert_allclose(
        by_count["history"][-1]["query_loss"],
        by_deadline["history"][-1]["query_loss"], rtol=1e-4, atol=1e-5)


def test_fedbuff_flush_staleness_bounds_buffer_age(setup):
    """A count threshold the sparse fleet never reaches still flushes
    under the staleness deadline: with a cohort of 1 and deadline 3,
    the single arrival of round r is held through rounds r+1, r+2 and
    applied before it would turn 3 rounds stale — one flush per 3
    rounds, nothing pending at a multiple-of-3 horizon."""
    params, dist = setup
    out = tinyreptile_train(LOSS, params, dist, rounds=9, beta=0.02,
                            support=4, seed=1, clients_per_round=1,
                            pool=ClientPool(dist, 4),
                            buffered=BufferedAggregation(
                                100, flush_staleness=3))
    assert out["pool_state"]["flushes"] == 3
    assert out["pool_state"]["buffered_pending"] == 0
    # the count-only control never flushes at all
    held = tinyreptile_train(LOSS, params, dist, rounds=9, beta=0.02,
                             support=4, seed=1, clients_per_round=1,
                             pool=ClientPool(dist, 4),
                             buffered=BufferedAggregation(100))
    assert held["pool_state"]["flushes"] == 0
    assert held["pool_state"]["buffered_pending"] == 9
    _assert_trees_equal(held["params"], params)   # phi frozen, no flush


def test_fedbuff_flush_staleness_validation():
    with pytest.raises(ValueError, match="flush_staleness"):
        BufferedAggregation(4, flush_staleness=0)
    with pytest.raises(ValueError, match="flush_staleness"):
        BufferedAggregation(4, flush_staleness=1.5)
    assert BufferedAggregation(4, flush_staleness=2).flush_staleness == 2


def test_fedbuff_validation(setup):
    params, dist = setup
    with pytest.raises(ValueError, match="pool="):
        tinyreptile_train(LOSS, params, dist, rounds=2,
                          buffered=BufferedAggregation(2))
    with pytest.raises(ValueError, match="uplink"):
        from repro.core.strategies import TransferStrategy
        run_federated(params, dist, TransferStrategy(LOSS), rounds=2,
                      clients_per_round=2, pool=ClientPool(dist, 4),
                      buffered=BufferedAggregation(2))
    with pytest.raises(ValueError, match="cohort"):
        tinyreptile_train(LOSS, params, dist, rounds=2,
                          clients_per_round=8, pool=ClientPool(dist, 4))


# ---------------------------------------------------------------------------
# availability processes
# ---------------------------------------------------------------------------

def test_diurnal_availability_statistics():
    proc = DiurnalAvailability(period=10, base=0.5, amplitude=0.45)
    avail = proc.availability(np.random.default_rng(0), 0, 400,
                              pool_size=32)
    rate = avail.mean(axis=1)                       # per-round rate
    peaks = rate[np.arange(400) % 10 == 2]          # sin ~ +0.95 here
    troughs = rate[np.arange(400) % 10 == 7]        # sin ~ -0.95 here
    assert peaks.mean() > 0.8
    assert troughs.mean() < 0.15
    # fleet-wide phase (spread=0): all clients share the same sine
    spread = DiurnalAvailability(period=10, phase_spread=1.0)
    rate_s = spread.availability(np.random.default_rng(0), 0, 400,
                                 pool_size=32).mean(axis=1)
    assert rate_s.std() < rate.std()                # staggered -> flat
    with pytest.raises(ValueError):
        DiurnalAvailability(period=0)


def test_markov_availability_statistics():
    proc = MarkovAvailability(p_on=0.3, p_off=0.15)
    rng = np.random.default_rng(1)
    # called in contiguous blocks, like the engine's producer
    rows = np.concatenate([proc.availability(rng, 0, 300, 16),
                           proc.availability(rng, 300, 600, 16)])
    stationary = 0.3 / 0.45
    np.testing.assert_allclose(rows.mean(), stationary, atol=0.05)
    # sticky chains: consecutive rounds agree far more often than i.i.d.
    agree = (rows[1:] == rows[:-1]).mean()
    iid_agree = stationary ** 2 + (1 - stationary) ** 2
    assert agree > iid_agree + 0.2
    # out-of-order blocks are rejected, fresh runs reset at round 0
    with pytest.raises(RuntimeError, match="contiguous"):
        proc.availability(rng, 900, 920, 16)
    assert proc.availability(np.random.default_rng(9), 0, 5, 16).shape \
        == (5, 16)
    with pytest.raises(ValueError):
        MarkovAvailability(p_on=0.0)


def test_availability_requires_pool(setup):
    params, dist = setup
    with pytest.raises(ValueError, match="PERSISTENT"):
        tinyreptile_train(LOSS, params, dist, rounds=2,
                          sampling=DiurnalAvailability())


def test_no_show_rounds_are_noops(setup):
    """A trough round where nobody checks in: phi and the pool state
    pass through and no transport is billed — without retracing."""
    params, dist = setup

    class NightOnly(DiurnalAvailability):
        def availability(self, rng, start, end, pool_size):
            rows = np.zeros((end - start, pool_size), bool)
            for r, rnd in enumerate(range(start, end)):
                if rnd % 2 == 0:                 # every other round: empty
                    rows[r] = rng.uniform(size=pool_size) < 0.9
            return rows

    out = tinyreptile_train(LOSS, params, dist, rounds=6, beta=0.02,
                            support=4, seed=0, clients_per_round=2,
                            sampling=NightOnly(period=2),
                            pool=ClientPool(dist, 4))
    ps = out["pool_state"]
    assert set(ps["last_seen"]) <= {-1, 0, 2, 4}    # odd rounds idle
    payload = CommChannel().payload_bytes(params)
    assert out["comm_bytes"] == 2 * payload * ps["checkins"].sum()
    for l in jax.tree.leaves(out["params"]):
        assert np.isfinite(np.asarray(l)).all()


# ---------------------------------------------------------------------------
# legacy fast path + single-trace contract
# ---------------------------------------------------------------------------

def test_pool_none_keeps_legacy_fast_path(setup):
    """pool=None runs are byte-identical to the pre-pool engine: the
    uniform policy still routes through the UNSCHEDULED runner (cohort
    threading is dead code XLA drops), and prefetch parity holds."""
    params, dist = setup
    clear_runner_cache()
    beta = 0.0807                       # unique config -> fresh runner
    kw = dict(rounds=9, beta=beta, support=4, seed=4, eval_every=9,
              eval_kwargs=EVAL)
    a = tinyreptile_train(LOSS, params, dist, prefetch=0, **kw)
    b = tinyreptile_train(LOSS, params, dist, prefetch=2, **kw)
    _assert_trees_equal(a["params"], b["params"])
    assert a["history"] == b["history"]
    assert "pool_state" not in a
    runner = _block_runner(TinyReptileStrategy(LOSS, use_pallas=None),
                           beta, CommChannel(), scheduled=False)
    assert runner.trace_count == 1
    clear_runner_cache()


def test_pooled_runs_trace_once(setup):
    """Pooled runs across uneven eval blocks compile exactly once per
    (strategy, beta, channel, schedule-shape, pool-shape) config, and
    the pooled runner is cached separately from the flat scheduled
    runner."""
    params, dist = setup
    clear_runner_cache()
    beta = 0.0909                       # unique config -> fresh runner
    kw = dict(rounds=13, beta=beta, support=4, seed=3, eval_every=5,
              eval_kwargs=EVAL, clients_per_round=3)
    tinyreptile_train(LOSS, params, dist, pool=ClientPool(dist, 6), **kw)
    strat = TinyReptileStrategy(LOSS, use_pallas=None)
    pooled = _block_runner(strat, beta, CommChannel(), scheduled=True,
                           pooled=True, masked=False)
    assert pooled.trace_count == 1
    # buffered configs are their own cached runner, also single-trace
    tinyreptile_train(LOSS, params, dist, pool=ClientPool(dist, 6),
                      buffered=BufferedAggregation(4), **kw)
    buffed = _block_runner(strat, beta, CommChannel(), scheduled=True,
                           pooled=True, buffered=BufferedAggregation(4),
                           masked=False)
    assert buffed is not pooled
    assert buffed.trace_count == 1
    assert pooled.trace_count == 1       # untouched by the buffered run
    flat = _block_runner(strat, beta, CommChannel(), scheduled=True)
    assert flat is not pooled
    clear_runner_cache()


def test_pool_state_is_a_pytree():
    ps = PoolState(last_seen=np.full(4, -1, np.int32),
                   staleness=np.zeros(4, np.int32),
                   checkins=np.zeros(4, np.int32))
    staged = jax.device_put(ps)
    assert isinstance(staged, PoolState)
    leaves = jax.tree.leaves(staged)
    assert len(leaves) == 3              # buffer fields are empty (None)
    rt = jax.tree.unflatten(jax.tree.structure(staged), leaves)
    assert rt.buf_updates is None and rt.flushes is None
