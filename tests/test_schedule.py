"""The ClientSchedule heterogeneity layer (PR 3).

Covers the tentpole contracts:
- SamplingPolicy as a schedule producer: UniformSampling's trivial plan
  (no rng consumed), PartialParticipation cohorts, StragglerSampling
  step draws + arrival weights;
- schedule-driven block sampling (reference loop skips scheduled-out
  rng draws; vectorized overrides zero scheduled-out slots);
- the scheduled scan body: trivial schedules match the uniform fast
  path, one jit trace per schedule-shape config (no per-round host
  dispatches), masked inner loops degenerate op-for-op at k == budget;
- per-participant transport accounting (comm_bytes + per_client_bytes);
- rotating PartialCommChannel masks: disjoint per-round chunks, full
  coverage within ceil(1/fraction) rounds, full-coverage byte
  accounting over one rotation period;
- the 64-entry runner cache: LRU eviction, miss/unhashable counters,
  clear_runner_cache idempotence.
"""
import copy
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.paper_models import SINE_MLP
from repro.core import (CommChannel, PartialCommChannel,
                        PartialParticipation, StragglerSampling,
                        UniformSampling, clear_runner_cache, fedavg_train,
                        fedsgd_train, reptile_train, run_federated,
                        runner_cache_stats, tinyreptile_train,
                        transfer_train)
from repro.core import engine as engine_mod
from repro.core.engine import _block_runner
from repro.core.meta import (finetune_batch, finetune_batch_masked,
                             finetune_online, finetune_online_masked,
                             tree_bytes)
from repro.core.strategies import (FedAvgStrategy, FedSGDStrategy,
                                   ReptileStrategy, TinyReptileStrategy,
                                   TransferStrategy)
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


@dataclasses.dataclass(frozen=True)
class TrivialScheduled(UniformSampling):
    """UniformSampling's data order, but routed through the scheduled
    scan body (weighted aggregation with uniform weights, step-masked
    client loops at full budget) — the degeneracy check."""
    schedule_kind = "scheduled"


# ---------------------------------------------------------------------------
# schedule planning
# ---------------------------------------------------------------------------

def test_uniform_plan_is_trivial_and_consumes_no_rng():
    rng = np.random.default_rng(0)
    state_before = copy.deepcopy(rng.bit_generator.state)
    plan = UniformSampling().plan_schedule(rng, 3, 10, clients=4, budget=6)
    assert rng.bit_generator.state == state_before      # no draws
    assert plan["participation"].shape == (7, 4)
    assert plan["participation"].all()
    assert (plan["local_steps"] == 6).all()
    np.testing.assert_allclose(plan["weights"], 0.25)
    assert UniformSampling.schedule_kind == "uniform"


def test_partial_participation_plan():
    policy = PartialParticipation(0.5)
    assert policy.cohort(8) == 4 and policy.cohort(1) == 1
    plan = policy.plan_schedule(np.random.default_rng(1), 0, 20,
                                clients=8, budget=5)
    part = plan["participation"]
    assert part.shape == (20, 8)
    assert (part.sum(axis=1) == 4).all()                # exactly m per round
    # weights: 1/m on participants, 0 elsewhere, normalized per round
    np.testing.assert_allclose(plan["weights"].sum(axis=1), 1.0, rtol=1e-6)
    assert (plan["weights"][part] == 0.25).all()
    assert (plan["weights"][~part] == 0.0).all()
    # scheduled-out slots get zero local steps
    assert (plan["local_steps"][part] == 5).all()
    assert (plan["local_steps"][~part] == 0).all()
    # the rotation varies across rounds (not the same cohort every time)
    assert len({tuple(r) for r in part}) > 1
    for bad in (0.0, 1.5):
        with pytest.raises(ValueError):
            PartialParticipation(bad)
    with pytest.raises(ValueError):
        PartialParticipation(0.5, sampler="bogus")


def test_straggler_plan():
    policy = StragglerSampling(min_steps_frac=0.25)
    plan = policy.plan_schedule(np.random.default_rng(2), 0, 30,
                                clients=6, budget=8)
    steps = plan["local_steps"]
    assert steps.shape == (30, 6)
    assert steps.min() >= 2 and steps.max() <= 8        # ceil(.25*8)=2
    assert len(np.unique(steps)) > 1                    # heterogeneous
    assert plan["participation"].all()                  # everyone shows up
    # arrival-weighted: w_i = k_i / sum k_j
    np.testing.assert_allclose(
        plan["weights"], steps / steps.sum(axis=1, keepdims=True),
        rtol=1e-6)
    with pytest.raises(ValueError):
        StragglerSampling(min_steps_frac=0.0)


# ---------------------------------------------------------------------------
# schedule-driven block sampling
# ---------------------------------------------------------------------------

def test_reference_sampling_skips_scheduled_out_rng_draws():
    """Scheduled-out slots draw NOTHING: sampling rounds r with a mask
    equals sampling only the participating slots in the same rng order."""
    dist = SineTasks()
    part = np.array([[True, False, True],
                     [False, True, True]])
    got = dist.sample_support_block_reference(
        np.random.default_rng(7), 2, 3, 4, participation=part)
    # replay: same seed, only the participating (round, client) slots
    rng = np.random.default_rng(7)
    want_live = dist.sample_support_block_reference(rng, 1, 1, 4)
    assert got["x"][0, 0].shape == want_live["x"][0, 0].shape
    np.testing.assert_array_equal(got["x"][0, 0], want_live["x"][0, 0])
    # scheduled-out slots are zero
    assert (got["x"][0, 1] == 0).all() and (got["y"][0, 1] == 0).all()
    assert (got["x"][1, 0] == 0).all()
    # an all-True mask consumes the rng identically to no mask
    a = dist.sample_support_block_reference(np.random.default_rng(3), 2, 2, 4)
    b = dist.sample_support_block_reference(
        np.random.default_rng(3), 2, 2, 4,
        participation=np.ones((2, 2), bool))
    np.testing.assert_array_equal(a["x"], b["x"])
    with pytest.raises(ValueError):
        dist.sample_support_block_reference(
            np.random.default_rng(0), 2, 2, 4,
            participation=np.zeros((2, 2), bool))


def test_vectorized_sampling_zeroes_scheduled_out_slots():
    dist = SineTasks()
    part = np.zeros((3, 2), bool)
    part[:, 0] = True
    blk = dist.sample_support_block(np.random.default_rng(5), 3, 2, 4,
                                    participation=part)
    assert (blk["x"][:, 1] == 0).all() and (blk["y"][:, 1] == 0).all()
    assert np.abs(blk["x"][:, 0]).sum() > 0


# ---------------------------------------------------------------------------
# scheduled scan body: trivial-schedule degeneracy + masked inner loops
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("train_fn,kw", [
    (tinyreptile_train, dict(alpha=1.0, support=6)),
    (reptile_train, dict(alpha=1.0, support=6, epochs=3,
                         clients_per_round=3)),
    (fedavg_train, dict(support=6, epochs=3, clients_per_round=3)),
    (fedsgd_train, dict(support=6, clients_per_round=3)),
    (transfer_train, dict(batch_per_round=12, tasks_per_round=3)),
])
def test_trivial_schedule_matches_uniform_path(setup, train_fn, kw):
    """The scheduled body with the trivial schedule (full participation,
    full budget, uniform weights) reproduces the uniform fast path for
    all five strategies — the tentpole's degeneracy criterion."""
    params, dist = setup
    base = dict(rounds=9, beta=0.02, seed=4, eval_every=9, eval_kwargs=EVAL)
    uni = train_fn(LOSS, params, dist, sampling=UniformSampling(), **base,
                   **kw)
    sch = train_fn(LOSS, params, dist, sampling=TrivialScheduled(), **base,
                   **kw)
    _assert_trees_close(uni["params"], sch["params"])
    assert len(uni["history"]) == len(sch["history"])
    for ue, se in zip(uni["history"], sch["history"]):
        assert set(ue) == set(se)
        np.testing.assert_allclose(ue["query_loss"], se["query_loss"],
                                   rtol=1e-4, atol=1e-5)
    if "comm_bytes" in uni:
        assert uni["comm_bytes"] == sch["comm_bytes"]
        assert uni["per_client_bytes"] == sch["per_client_bytes"]


def test_masked_finetune_degenerates_at_full_budget(setup):
    params, dist = setup
    rng = np.random.default_rng(0)
    task = dist.sample_task(rng)
    sup = task.support_batch(rng, 6)
    xs, ys = jnp.asarray(sup["x"]), jnp.asarray(sup["y"])
    lr = jnp.float32(0.02)

    full, full_l = finetune_online(LOSS, params, xs, ys, lr)
    masked, masked_l = finetune_online_masked(LOSS, params, xs, ys, lr,
                                              jnp.int32(6))
    _assert_trees_equal(full, masked)
    np.testing.assert_array_equal(np.asarray(full_l), np.asarray(masked_l))

    fullb, fullb_l = finetune_batch(LOSS, params, sup, 4, lr)
    maskb, maskb_l = finetune_batch_masked(LOSS, params, sup, 4, lr,
                                           jnp.int32(4))
    _assert_trees_equal(fullb, maskb)
    np.testing.assert_array_equal(np.asarray(fullb_l), np.asarray(maskb_l))


def test_masked_finetune_truncates():
    """k < S: params equal the k-step run; dead steps contribute 0 loss.
    k = 0: params pass through untouched."""
    params = init_paper_model(SINE_MLP, jax.random.PRNGKey(1))
    rng = np.random.default_rng(1)
    task = SineTasks().sample_task(rng)
    sup = task.support_batch(rng, 6)
    xs, ys = jnp.asarray(sup["x"]), jnp.asarray(sup["y"])
    lr = jnp.float32(0.02)

    short, short_l = finetune_online(LOSS, params, xs[:2], ys[:2], lr)
    masked, masked_l = finetune_online_masked(LOSS, params, xs, ys, lr,
                                              jnp.int32(2))
    _assert_trees_equal(short, masked)
    np.testing.assert_array_equal(np.asarray(short_l),
                                  np.asarray(masked_l)[:2])
    assert (np.asarray(masked_l)[2:] == 0).all()

    frozen, frozen_l = finetune_online_masked(LOSS, params, xs, ys, lr,
                                              jnp.int32(0))
    _assert_trees_equal(params, frozen)
    assert (np.asarray(frozen_l) == 0).all()


def test_zero_weight_clients_are_inert_even_when_nonfinite():
    """A scheduled-out client whose hook still ran (one-shot strategies
    ignore local_steps) must not poison the round: 0-weight results are
    zeroed before the weighted sum, so even a NaN/inf gradient from a
    zeroed batch leaves phi finite."""
    from repro.core.strategies import weighted_client_mean
    trees = {"w": jnp.asarray([[1.0, 2.0], [jnp.nan, jnp.inf]])}
    got = weighted_client_mean(trees, jnp.asarray([1.0, 0.0]))
    np.testing.assert_array_equal(np.asarray(got["w"]), [1.0, 2.0])


def test_weighted_aggregates_respect_weights(setup):
    params, _ = setup
    C = 3
    models = jax.tree.map(
        lambda p: jnp.stack([p + i for i in range(C)]), params)
    one_hot = jnp.asarray([0.0, 1.0, 0.0])
    picked = FedAvgStrategy(LOSS).server_aggregate_weighted(
        params, models, jnp.float32(1.0), jnp.float32(0.01), one_hot)
    _assert_trees_close(picked, jax.tree.map(lambda p: p + 1, params))
    # Reptile with a one-hot weight interpolates toward that client only
    rep = TinyReptileStrategy(LOSS, use_pallas=False)
    agg = rep.server_aggregate_weighted(
        params, models, jnp.float32(0.5), jnp.float32(0.01), one_hot)
    _assert_trees_close(agg, jax.tree.map(lambda p: p + 0.5, params))
    # FedSGD applies the weighted mean gradient
    g = FedSGDStrategy(LOSS).server_aggregate_weighted(
        params, models, jnp.float32(1.0), jnp.float32(1.0), one_hot)
    _assert_trees_close(g, jax.tree.map(lambda p: p - (p + 1), params),
                        tol=1e-4)


# ---------------------------------------------------------------------------
# per-participant transport accounting
# ---------------------------------------------------------------------------

def test_partial_participation_accounting(setup):
    params, dist = setup
    policy = PartialParticipation(0.5)
    out = reptile_train(LOSS, params, dist, rounds=12, beta=0.02, support=4,
                        epochs=2, clients_per_round=4, seed=0,
                        sampling=policy)
    payload = CommChannel().payload_bytes(params)
    m = policy.cohort(4)
    assert out["comm_bytes"] == 12 * 2 * m * payload    # participants only
    assert sum(out["per_client_bytes"]) == out["comm_bytes"]
    # every slot's bill is a whole number of participated rounds
    for b in out["per_client_bytes"]:
        assert b % (2 * payload) == 0
        assert 0 <= b <= 12 * 2 * payload
    for l in jax.tree.leaves(out["params"]):
        assert np.isfinite(np.asarray(l)).all()


def test_straggler_full_transport_and_training(setup):
    """Stragglers do less local work but still ship full payloads."""
    params, dist = setup
    out = tinyreptile_train(LOSS, params, dist, rounds=10, beta=0.02,
                            support=8, seed=1, clients_per_round=3,
                            sampling=StragglerSampling(0.25),
                            eval_every=10, eval_kwargs=EVAL)
    payload = CommChannel().payload_bytes(params)
    assert out["comm_bytes"] == 10 * 2 * 3 * payload
    assert out["per_client_bytes"] == [10 * 2 * payload] * 3
    assert np.isfinite(out["history"][-1]["query_loss"])


def test_scheduled_runs_trace_once(setup):
    """Straggler/partial runs across uneven eval blocks still compile
    exactly once per (strategy, beta, channel, schedule-shape) config —
    heterogeneity must not reintroduce per-round host dispatches."""
    params, dist = setup
    clear_runner_cache()
    beta = 0.0704                        # unique config -> fresh runner
    kw = dict(rounds=17, beta=beta, support=4, seed=3, eval_every=7,
              eval_kwargs=EVAL, clients_per_round=3)
    tinyreptile_train(LOSS, params, dist,
                      sampling=StragglerSampling(0.25), **kw)
    runner = _block_runner(TinyReptileStrategy(LOSS, use_pallas=None),
                           beta, CommChannel(), scheduled=True)
    assert runner.trace_count == 1
    tinyreptile_train(LOSS, params, dist,
                      sampling=PartialParticipation(0.5), **kw)
    assert runner.trace_count == 1       # same schedule shape: reused
    # the uniform fast path is a DIFFERENT cached runner
    uniform = _block_runner(TinyReptileStrategy(LOSS, use_pallas=None),
                            beta, CommChannel(), scheduled=False)
    assert uniform is not runner


# ---------------------------------------------------------------------------
# rotating partial-communication masks
# ---------------------------------------------------------------------------

def test_rotating_payload_bytes_reports_chunk_not_fraction():
    """For non-reciprocal fractions the rotating wire carries
    1/ceil(1/fraction) of the entries per round, and payload_bytes must
    agree with the mask (round 0's chunk), not the nominal fraction."""
    ch = PartialCommChannel(fraction=0.4, rotate=True)
    tree = {"w": jnp.zeros((100,), jnp.float32)}
    assert ch.rotation_period == 3
    assert ch.kept_entries(100) == 34                   # ceil(100/3), not 40
    assert ch.payload_bytes(tree) == ch.payload_bytes_at(tree, 0) == 34 * 4
    assert int(np.asarray(
        ch.mask_tree(tree, round_index=0)["w"]).sum()) == 34
    # the fixed-mask accounting is unchanged
    assert PartialCommChannel(fraction=0.4).kept_entries(100) == 40


def test_rotation_period_ceil():
    assert PartialCommChannel(fraction=0.5, rotate=True).rotation_period == 2
    assert PartialCommChannel(fraction=0.25, rotate=True).rotation_period == 4
    # float-noise guard: 1/(1/3) is slightly above 3.0
    assert PartialCommChannel(fraction=1 / 3,
                              rotate=True).rotation_period == 3
    assert PartialCommChannel(fraction=1.0, rotate=True).rotation_period == 1


@pytest.mark.parametrize("fraction,n", [(0.5, 128), (0.25, 10), (0.3, 7)])
def test_rotating_masks_cover_everything_once_per_period(fraction, n):
    """Per-round masks are disjoint chunks that tile every entry exactly
    once per rotation period, and the per-round byte accounting matches
    the mask sizes (full coverage = one whole tree per period)."""
    ch = PartialCommChannel(fraction=fraction, rotate=True)
    tree = {"w": jnp.zeros((n,), jnp.float32)}
    period = ch.rotation_period
    assert period == int(np.ceil(1.0 / fraction - 1e-9))
    seen = np.zeros(n, np.int64)
    total_bytes = 0
    for r in range(period):
        m = np.asarray(ch.mask_tree(tree, round_index=r)["w"])
        assert m.sum() == ch.kept_entries_at(n, r)      # mask == accounting
        seen += m
        total_bytes += ch.payload_bytes_at(tree, r)
    assert (seen == 1).all()                            # exact tiling
    assert total_bytes == tree_bytes(tree)              # one full tree
    # mask sequence repeats with the period
    np.testing.assert_array_equal(
        np.asarray(ch.mask_tree(tree, round_index=0)["w"]),
        np.asarray(ch.mask_tree(tree, round_index=period)["w"]))
    # deterministic in mask_seed, different across rounds
    assert not np.array_equal(
        np.asarray(ch.mask_tree(tree, round_index=0)["w"]),
        np.asarray(ch.mask_tree(tree, round_index=1)["w"]))


def test_rotating_uplink_rotates_the_kept_set():
    r = np.random.default_rng(0)
    ref = {"w": jnp.asarray(r.normal(size=(64,)), jnp.float32)}
    sent = {"w": jnp.asarray(r.normal(size=(64,)), jnp.float32)}
    ch = PartialCommChannel(fraction=0.5, rotate=True)
    got0 = np.asarray(ch.transmit(sent, ref=ref, round_index=0)["w"])
    got1 = np.asarray(ch.transmit(sent, ref=ref, round_index=1)["w"])
    from0 = got0 == np.asarray(sent["w"])
    from1 = got1 == np.asarray(sent["w"])
    assert from0.sum() == ch.kept_entries_at(64, 0)
    assert from1.sum() == ch.kept_entries_at(64, 1)
    assert not (from0 & from1).any()                    # disjoint chunks
    assert (from0 | from1).all()                        # full coverage


def test_rotating_channel_trains_and_meters(setup):
    """End-to-end: the in-scan round index drives the mask; accounting
    bills the round-exact fraction-scaled payload per participant."""
    params, dist = setup
    ch = PartialCommChannel(fraction=0.25, rotate=True)
    rounds = 10
    out = tinyreptile_train(LOSS, params, dist, rounds=rounds, beta=0.02,
                            support=4, seed=1, channel=ch, eval_every=5,
                            eval_kwargs=EVAL)
    want = sum(2 * ch.payload_bytes_at(params, r) for r in range(rounds))
    assert out["comm_bytes"] == want
    assert out["per_client_bytes"] == [want]
    # a full-period slice of the per-round payloads meters a whole tree
    per_period = sum(ch.payload_bytes_at(params, r)
                     for r in range(ch.rotation_period))
    assert per_period == tree_bytes(params)
    for l in jax.tree.leaves(out["params"]):
        assert np.isfinite(np.asarray(l)).all()


def test_rotating_channel_composes_with_schedules(setup):
    """Rotating masks + partial participation: bytes are fraction-scaled
    AND billed only to the round's participants."""
    params, dist = setup
    ch = PartialCommChannel(fraction=0.5, rotate=True)
    policy = PartialParticipation(0.5)
    out = reptile_train(LOSS, params, dist, rounds=8, beta=0.02, support=4,
                        epochs=2, clients_per_round=4, seed=2, channel=ch,
                        sampling=policy)
    m = policy.cohort(4)
    want = sum(2 * m * ch.payload_bytes_at(params, r) for r in range(8))
    assert out["comm_bytes"] == want
    assert sum(out["per_client_bytes"]) == want
    for l in jax.tree.leaves(out["params"]):
        assert np.isfinite(np.asarray(l)).all()


# ---------------------------------------------------------------------------
# the 64-entry runner cache (LRU eviction + counters + clear idempotence)
# ---------------------------------------------------------------------------

def test_runner_cache_lru_eviction():
    """Building runners is cheap (the jit trace happens on first CALL),
    so we can walk straight through the real 64-entry cache."""
    clear_runner_cache()
    strategy = TinyReptileStrategy(LOSS, use_pallas=None)
    channel = CommChannel()
    maxsize = runner_cache_stats()["maxsize"]
    assert maxsize == 64
    betas = [0.001 + 1e-5 * i for i in range(maxsize + 1)]
    runners = [_block_runner(strategy, b, channel) for b in betas]
    stats = runner_cache_stats()
    assert stats["misses"] == maxsize + 1
    assert stats["currsize"] == maxsize                 # one got evicted
    # beta[0] was the least recently used -> evicted: a fresh object
    again0 = _block_runner(strategy, betas[0], channel)
    assert again0 is not runners[0]
    assert runner_cache_stats()["misses"] == maxsize + 2
    # the most recent entry is still cached: identity hit
    hits_before = runner_cache_stats()["hits"]
    assert _block_runner(strategy, betas[-1], channel) is runners[-1]
    assert runner_cache_stats()["hits"] == hits_before + 1
    clear_runner_cache()


def test_runner_cache_unhashable_counter_and_clear_idempotence(caplog):
    clear_runner_cache()

    @dataclasses.dataclass(frozen=True)
    class Unhashable(TinyReptileStrategy):
        junk: list = dataclasses.field(default_factory=list)

    with caplog.at_level("WARNING", logger="repro.core.engine"):
        a = _block_runner(Unhashable(LOSS), 0.02, CommChannel())
        b = _block_runner(Unhashable(LOSS), 0.02, CommChannel())
    assert a is not b                                   # never cached
    stats = runner_cache_stats()
    assert stats["unhashable_misses"] == 2
    assert stats["currsize"] == 0                       # lru untouched
    assert sum("unhashable" in r.message for r in caplog.records) == 2
    # clear is idempotent: calling twice lands in the same zero state
    clear_runner_cache()
    clear_runner_cache()
    stats = runner_cache_stats()
    assert stats == {"hits": 0, "misses": 0, "currsize": 0,
                     "maxsize": 64, "unhashable_misses": 0,
                     "mesh_entries": 0}


def test_runner_cache_accounts_mesh_entries():
    """Mesh-keyed runners are their own cache entries (a sharded trace
    must never serve a flat run or vice versa), are counted by
    runner_cache_stats, and are dropped by clear_runner_cache. The
    cross-topology half (a 4-device and an 8-device mesh never share a
    trace) lives in tests/test_mesh_engine.py, which has the forced
    multi-device process."""
    from repro.core import client_mesh
    clear_runner_cache()
    s = TinyReptileStrategy(LOSS, use_pallas=None)
    mesh = client_mesh(1)
    flat = _block_runner(s, 0.06, CommChannel(), scheduled=True)
    sharded = _block_runner(s, 0.06, CommChannel(), scheduled=True,
                            mesh=mesh)
    assert sharded is not flat
    stats = runner_cache_stats()
    assert stats["currsize"] == 2 and stats["mesh_entries"] == 1
    # an equal mesh (same devices, same axis) hits the same entry:
    # Mesh hashes by topology, not object identity
    again = _block_runner(s, 0.06, CommChannel(), scheduled=True,
                          mesh=client_mesh(1))
    assert again is sharded
    assert runner_cache_stats()["hits"] >= 1
    clear_runner_cache()
    assert runner_cache_stats()["mesh_entries"] == 0


def test_mesh_runner_requires_collective_hook():
    """A custom strategy whose server_aggregate_weighted lacks the
    axis_name parameter gets a plugin-author-facing error at runner
    construction, not a TypeError from inside the trace."""
    from repro.core import client_mesh

    @dataclasses.dataclass(frozen=True)
    class OldHook(TinyReptileStrategy):
        def server_aggregate_weighted(self, phi, client_results, alpha_t,
                                      beta, weights):
            return phi

    with pytest.raises(ValueError, match="axis_name"):
        _block_runner(OldHook(LOSS), 0.05, CommChannel(), scheduled=True,
                      mesh=client_mesh(1))


def test_scheduled_and_uniform_runners_cached_separately():
    clear_runner_cache()
    s = TinyReptileStrategy(LOSS, use_pallas=None)
    u = _block_runner(s, 0.05, CommChannel(), scheduled=False)
    sc = _block_runner(s, 0.05, CommChannel(), scheduled=True)
    assert u is not sc
    assert runner_cache_stats()["misses"] == 2
    assert _block_runner(s, 0.05, CommChannel(), scheduled=True) is sc
    clear_runner_cache()


# ---------------------------------------------------------------------------
# prefetch parity for scheduled runs
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("policy", [
    PartialParticipation(0.5, sampler="reference"),
    StragglerSampling(0.25, sampler="reference"),
    PartialParticipation(0.5, sampler="vectorized"),
])
def test_scheduled_prefetch_parity(setup, policy):
    """Pipelined and synchronous scheduled runs are bit-for-bit
    identical: plan_schedule + sample_block consume the host rng
    strictly in block order either way."""
    params, dist = setup
    kw = dict(rounds=13, beta=0.02, support=4, seed=6, eval_every=5,
              eval_kwargs=EVAL, clients_per_round=3, epochs=2,
              sampling=policy)
    sync = reptile_train(LOSS, params, dist, prefetch=0, **kw)
    piped = reptile_train(LOSS, params, dist, prefetch=2, **kw)
    _assert_trees_equal(sync["params"], piped["params"])
    assert sync["history"] == piped["history"]
    assert sync["comm_bytes"] == piped["comm_bytes"]
    assert sync["per_client_bytes"] == piped["per_client_bytes"]
