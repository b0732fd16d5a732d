"""Fleet-scale ClientPool (PR 8): counter-derived identity, bounded
host caches, host-resident identity slabs, and the O(cohort) samplers.

Everything here runs in-process (no forced device topology): the
mesh-sharded and cross-host variants of the same contracts live in
tests/test_mesh_engine.py and tests/test_distributed.py.
"""
import functools
import json

import jax
import numpy as np
import pytest

from repro.configs.paper_models import SINE_MLP
from repro.core import (BufferedAggregation, ClientPool,
                        DiurnalAvailability, MarkovAvailability,
                        run_federated)
from repro.core.pipeline import seat_cohorts
from repro.core.pool import _MAX_TEMPLATES, AvailabilityProcess
from repro.core.strategies import ReptileStrategy, TinyReptileStrategy
from repro.data import KWSTasks, OmniglotTasks, SineTasks, TaskDistribution
from repro.metering.memory import MemoryMeter
from repro.models.paper_nets import init_paper_model, paper_model_loss

LOSS = functools.partial(paper_model_loss, SINE_MLP)
PARAMS = init_paper_model(SINE_MLP, jax.random.PRNGKey(0))
BIG = 1_000_000


def _rngs(seed, i, k):
    # the counter-sampler stream contract: task from (seed, i), data
    # from (seed, i, k) — mirrors pool.py's stream constants
    return (np.random.default_rng([seed, 0x9E37, i]),
            np.random.default_rng([seed, 0x5EED, i, k]))


# ---------------------------------------------------------------- identity

def test_sine_support_override_matches_generic_fallback():
    """SineTasks.sample_client_support (the closed-form fast path) is
    BIT-equal to TaskDistribution's materialize-and-replay fallback for
    both data modes."""
    dist = SineTasks()
    for mode in ("batch", "stream"):
        for i, k in ((0, 0), (3, 2), (BIG - 1, 7)):
            x1, y1 = dist.sample_client_support(*_rngs(5, i, k), 6,
                                                data_mode=mode)
            x2, y2 = TaskDistribution.sample_client_support(
                dist, *_rngs(5, i, k), 6, data_mode=mode)
            np.testing.assert_array_equal(x1, x2)
            np.testing.assert_array_equal(y1, y2)


@pytest.mark.parametrize("dist", [OmniglotTasks(), KWSTasks()],
                         ids=["omniglot", "kws"])
def test_classification_support_overrides(dist):
    """The classification block overrides draw deterministic,
    well-shaped support sets whose labels match the generic fallback's
    task (same class subset from the same task stream)."""
    xa, ya = dist.sample_client_support(*_rngs(1, 4, 2), 5)
    xb, yb = dist.sample_client_support(*_rngs(1, 4, 2), 5)
    np.testing.assert_array_equal(xa, xb)
    np.testing.assert_array_equal(ya, yb)
    xg, yg = TaskDistribution.sample_client_support(dist, *_rngs(1, 4, 2),
                                                    5)
    assert xa.shape == xg.shape and xa.dtype == xg.dtype
    assert ya.shape == yg.shape and ya.dtype == yg.dtype
    assert set(np.unique(ya)) <= set(range(dist.ways))
    xc, _ = dist.sample_client_support(*_rngs(1, 4, 3), 5)
    assert not np.array_equal(xa, xc)        # fresh draw per check-in


def test_counter_sampler_advances_per_checkin():
    """Block sampling under sampler='vectorized': participating slots
    draw client-and-check-in-keyed data (repeat check-ins differ,
    replays are exact), scheduled-out slots stay zero, and NO per-client
    host objects accrete."""
    dist = SineTasks()
    pool = ClientPool(dist, 50, seed=2, sampler="vectorized")
    cohort = np.array([[3, 7], [3, 9]])
    part = np.array([[True, False], [True, True]])
    b1 = pool.sample_cohort_block(cohort, part, 4)
    assert np.all(b1["x"][0, 1] == 0) and np.all(b1["y"][0, 1] == 0)
    assert not np.array_equal(b1["x"][0, 0], b1["x"][1, 0])  # k=0 vs k=1
    np.testing.assert_array_equal(pool._checkins[[3, 7, 9]], [2, 0, 1])
    assert len(pool._rngs) == 0
    # the draws are pure functions of (seed, client, k)
    x, y = dist.sample_client_support(*_rngs(2, 3, 1), 4)
    np.testing.assert_array_equal(b1["x"][1, 0], x)
    np.testing.assert_array_equal(b1["y"][1, 0], y)
    fresh = ClientPool(dist, 50, seed=2, sampler="vectorized")
    r1 = fresh.sample_cohort_block(cohort, part, 4)
    np.testing.assert_array_equal(b1["x"], r1["x"])


def test_host_state_roundtrip_at_million_clients():
    """At N=10^6 the vectorized pool's whole mutable host state is the
    nonzero check-in counters: the snapshot is tiny and JSON-able, and
    a fresh pool restored from it reproduces the next block
    bit-for-bit."""
    dist = SineTasks()
    pool = ClientPool(dist, BIG, seed=9, sampler="vectorized")
    rng = np.random.default_rng(0)
    cohort = seat_cohorts(rng, BIG, 256, 4)
    part = np.ones(cohort.shape, bool)
    pool.sample_cohort_block(cohort, part, 2)
    snap = pool.host_state()
    assert set(snap) == {"checkins"}
    assert len(snap["checkins"]) <= 4 * 256          # O(cohort), not O(N)
    assert len(json.dumps(snap)) < 64 * 1024
    fresh = ClientPool(dist, BIG, seed=9, sampler="vectorized")
    fresh.load_host_state(json.loads(json.dumps(snap)))
    nxt = seat_cohorts(rng, BIG, 256, 1)
    a = pool.sample_cohort_block(nxt, np.ones(nxt.shape, bool), 2)
    b = fresh.sample_cohort_block(nxt, np.ones(nxt.shape, bool), 2)
    np.testing.assert_array_equal(a["x"], b["x"])
    np.testing.assert_array_equal(a["y"], b["y"])


def test_host_state_cross_format_rejected():
    dist = SineTasks()
    vec = ClientPool(dist, 4, sampler="vectorized")
    ref = ClientPool(dist, 4, sampler="reference")
    ref.sample_cohort_block(np.array([[1]]), np.array([[True]]), 2)
    vec.sample_cohort_block(np.array([[1]]), np.array([[True]]), 2)
    with pytest.raises(ValueError, match="legacy per-client rng"):
        vec.load_host_state(ref.host_state())
    with pytest.raises(ValueError, match="counter snapshot"):
        ref.load_host_state(vec.host_state())
    with pytest.raises(ValueError, match="out of range"):
        vec.load_host_state({"checkins": {"99": 1}})


def test_constructor_validation():
    dist = SineTasks()
    with pytest.raises(ValueError, match="sampler"):
        ClientPool(dist, 4, sampler="bogus")
    with pytest.raises(ValueError, match="residency"):
        ClientPool(dist, 4, residency="gpu")
    with pytest.raises(ValueError, match="mmap_dir"):
        ClientPool(dist, 4, mmap_dir="/tmp/x")
    with pytest.raises(ValueError, match="max_cached_tasks"):
        ClientPool(dist, 4, max_cached_tasks=0)


def test_host_caches_stay_bounded():
    """A long-lived vectorized pool touching MANY distinct clients keeps
    O(1) host objects: the task LRU respects max_cached_tasks, no
    per-client generators exist, and the shape-template cache is capped
    — the regression gate for the legacy O(N)-dicts liability."""
    dist = SineTasks()
    pool = ClientPool(dist, 100_000, seed=1, sampler="vectorized",
                      max_cached_tasks=32)
    rng = np.random.default_rng(3)
    for blk in range(6):
        cohort = seat_cohorts(rng, 100_000, 64, 4)
        pool.sample_cohort_block(cohort, np.ones(cohort.shape, bool), 2)
        for s in range(blk + 2):
            pool._template(s + 1, "batch")
    assert len(pool._tasks) <= 32
    assert len(pool._rngs) == 0
    assert len(pool._templates) <= _MAX_TEMPLATES
    # the reference pool on the same workload accretes one generator
    # per distinct client ever seated — the liability being removed
    ref = ClientPool(dist, 100_000, seed=1, sampler="reference")
    cohort = seat_cohorts(np.random.default_rng(3), 100_000, 64, 4)
    ref.sample_cohort_block(cohort, np.ones(cohort.shape, bool), 2)
    assert len(ref._rngs) == len(np.unique(cohort))


# ---------------------------------------------------------------- seating

def test_seat_cohorts_sparse_and_dense():
    """seat_cohorts: unique in-range seats per round in both regimes
    (rejection sampling at cohort << pool, plain without-replacement
    choice when dense), deterministic in the rng stream."""
    for pool_size, clients in ((BIG, 256), (40, 11), (8, 8)):
        out = seat_cohorts(np.random.default_rng(7), pool_size, clients,
                           5)
        assert out.shape == (5, clients)
        assert out.min() >= 0 and out.max() < pool_size
        for r in range(5):
            assert len(set(out[r].tolist())) == clients
        again = seat_cohorts(np.random.default_rng(7), pool_size,
                             clients, 5)
        np.testing.assert_array_equal(out, again)


def test_vectorized_availability_seating():
    """The loop-free availability seating keeps the reference LAYOUT:
    sorted ascending cohort ids packed into the leading slots, False
    tail, every seated client actually available, capped at the cohort
    width."""
    rng = np.random.default_rng(4)
    avail = np.random.default_rng(0).uniform(size=(6, 500)) < 0.3
    avail[2] = False                                  # a trough round
    cohort, part = AvailabilityProcess._seat_available_block(rng, avail,
                                                             8)
    assert cohort.shape == part.shape == (6, 8)
    assert not part[2].any() and not cohort[2].any()
    for r in (0, 1, 3, 4, 5):
        m = int(part[r].sum())
        assert m == min(8, int(avail[r].sum()))
        assert part[r, :m].all() and not part[r, m:].any()
        seats = cohort[r, :m]
        assert np.all(np.diff(seats) > 0)             # sorted, unique
        assert avail[r, seats].all()
        assert not cohort[r, m:].any()


def test_diurnal_parameter_validation():
    DiurnalAvailability(base=0.0, amplitude=1.0, phase_spread=1.0)
    with pytest.raises(ValueError, match="base"):
        DiurnalAvailability(base=1.5)
    with pytest.raises(ValueError, match="amplitude"):
        DiurnalAvailability(amplitude=-0.1)
    with pytest.raises(ValueError, match="phase_spread"):
        DiurnalAvailability(phase_spread=2.0)
    with pytest.raises(ValueError, match="sampler"):
        DiurnalAvailability(sampler="bogus")


# -------------------------------------------------------------- residency

def _run(pool, rounds=8, **kw):
    base = dict(rounds=rounds, clients_per_round=3, beta=0.02, support=4,
                seed=5, eval_every=4,
                eval_kwargs=dict(num_tasks=2, support=4, k_steps=2,
                                 lr=0.02, query=8))
    base.update(kw)
    return run_federated(PARAMS, SineTasks(), TinyReptileStrategy(LOSS),
                         pool=pool, **base)


def _assert_same(a, b):
    for x, y in zip(jax.tree.leaves(a["params"]),
                    jax.tree.leaves(b["params"])):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    for k in ("last_seen", "staleness", "checkins"):
        np.testing.assert_array_equal(a["pool_state"][k],
                                      b["pool_state"][k])
    assert a["per_client_bytes"] == b["per_client_bytes"]
    assert [h["query_loss"] for h in a["history"]] == \
        [h["query_loss"] for h in b["history"]]


@pytest.mark.parametrize("sampler", ["reference", "vectorized"])
def test_host_residency_parity(sampler):
    """residency='host' (cohort-windowed identity staged from host
    slabs) is BIT-for-bit the device-resident run — params, identity
    state, bills, eval — for both samplers, with FedBuff buffering."""
    dist = SineTasks()
    kw = dict(buffered=BufferedAggregation(4))
    dev = _run(ClientPool(dist, 9, seed=5, sampler=sampler), **kw)
    hst = _run(ClientPool(dist, 9, seed=5, sampler=sampler,
                          residency="host"), **kw)
    _assert_same(dev, hst)


def test_host_residency_mmap_and_availability(tmp_path):
    """File-backed (np.memmap) slabs and an availability process on the
    vectorized sampler reproduce the in-RAM host-resident run exactly;
    the slab files exist on disk."""
    dist = SineTasks()
    kw = dict(sampling=DiurnalAvailability(period=4,
                                           sampler="vectorized"))
    ram = _run(ClientPool(dist, 9, seed=5, sampler="vectorized",
                          residency="host"), **kw)
    mm = _run(ClientPool(dist, 9, seed=5, sampler="vectorized",
                         residency="host", mmap_dir=str(tmp_path)), **kw)
    _assert_same(ram, mm)
    assert sorted(p.name for p in tmp_path.iterdir()) == [
        "pool_checkins.i32", "pool_last_seen.i32", "pool_staleness.i32"]


def test_resume_parity_across_residencies(tmp_path):
    """A host-resident vectorized run snapshots the FULL identity
    layout: an interrupted run resumes bit-for-bit — even when the
    resuming pool is DEVICE-resident (checkpoints are
    residency-portable) — against the uninterrupted run. anneal=False:
    the alpha schedule is horizon-dependent."""
    dist = SineTasks()

    def pool(residency):
        return ClientPool(dist, 9, seed=5, sampler="vectorized",
                          residency=residency)

    kw = dict(buffered=BufferedAggregation(4), anneal=False)
    full = _run(pool("host"), rounds=12, **kw)
    d = str(tmp_path / "ck")
    _run(pool("host"), rounds=6, ckpt_dir=d, ckpt_every=3, **kw)
    for residency in ("host", "device"):
        resumed = _run(pool(residency), rounds=12, ckpt_dir=d,
                       ckpt_every=3, resume=True, **kw)
        _assert_same(full, resumed)


def test_pool_sampler_resume_mismatch_rejected(tmp_path):
    """The checkpoint fingerprint pins the pool's sampler: resuming a
    vectorized run with a reference pool (a different identity stream)
    is rejected instead of silently diverging."""
    dist = SineTasks()
    d = str(tmp_path / "ck")
    _run(ClientPool(dist, 9, seed=5, sampler="vectorized"), rounds=6,
         ckpt_dir=d, ckpt_every=3, anneal=False)
    with pytest.raises(ValueError, match="pool_sampler"):
        _run(ClientPool(dist, 9, seed=5, sampler="reference"), rounds=12,
             ckpt_dir=d, ckpt_every=3, resume=True, anneal=False)


def test_million_client_pool_end_to_end():
    """The headline contract: a pooled run over N=10^6 persistent
    clients (vectorized sampler, host-resident slabs) trains rounds,
    reports the full-size identity arrays, and keeps per-round host
    work O(cohort): the block draws touch only seated clients and the
    compact snapshot stays cohort-sized."""
    dist = SineTasks()
    pool = ClientPool(dist, BIG, seed=5, sampler="vectorized",
                      residency="host", max_cached_tasks=64)
    meter = MemoryMeter()
    out = _run(pool, rounds=4, clients_per_round=8, eval_every=0)
    rep = meter.report()
    assert rep["host_baseline_bytes"] >= 0        # meter wiring smoke
    st = out["pool_state"]
    assert st["last_seen"].shape == (BIG,)
    seated = np.flatnonzero(st["checkins"])
    assert 0 < len(seated) <= 4 * 8
    np.testing.assert_array_equal(
        np.sort(seated), np.sort(np.flatnonzero(pool._checkins)))
    assert len(pool._tasks) <= 64 and len(pool._rngs) == 0
    snap = pool.host_state()
    assert len(snap["checkins"]) == len(seated)


def test_memory_meter_reports_growth():
    meter = MemoryMeter()
    ballast = np.ones(4 * 1024 * 1024, np.float64)   # 32 MB
    ballast[0] = 2.0
    rep = meter.report()
    assert rep["host_baseline_bytes"] > 0            # /proc available here
    assert rep["host_current_bytes"] >= rep["host_current_growth_bytes"]
    # peak (ru_maxrss) and current (statm) come from different kernel
    # accounting and may disagree by a few pages — assert each alone
    assert rep["host_peak_bytes"] > 0 and rep["host_current_bytes"] > 0
    assert rep["host_peak_growth_bytes"] >= 0
    assert rep["device_peak_bytes"] >= 0             # 0 on CPU backends
    del ballast
