"""The overlapped host/device round pipeline (PR 2).

Covers the four tentpole contracts:
- block planning + fixed padded shapes (retrace-free: ONE jit trace per
  strategy/channel config across uneven eval/tail blocks);
- bit-for-bit seeded parity of pipelined (background prefetch) vs
  synchronous runs across eval cadences and uneven max_block tails;
- vectorized block sampling == the scalar block-order reference loop for
  the sine distribution, and shape/dtype contracts for all distributions;
- the default run's one block call a block, and a pooled run's block-path
  seating with data drawn by the pool's identity;
- TinyMetaFed-style partial-communication channel (fraction accounting +
  masked uplink) and the block-runner cache counters.
"""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.paper_models import SINE_MLP
from repro.core import (ClientPool, CommChannel, PartialCommChannel,
                        UniformSampling, clear_runner_cache, fedsgd_train,
                        reptile_train, run_federated, runner_cache_stats,
                        tinyreptile_train)
from repro.core.engine import _block_runner
from repro.core.meta import tree_bytes
from repro.core.pipeline import BlockPrefetcher, plan_blocks
from repro.core.strategies import TinyReptileStrategy
from repro.data import SineTasks
from repro.data.tasks import (ClientTask, KWSTasks, OmniglotTasks,
                              TaskDistribution)
from repro.models.paper_nets import init_paper_model, paper_model_loss

LOSS = functools.partial(paper_model_loss, SINE_MLP)
EVAL = dict(num_tasks=2, support=4, k_steps=2, lr=0.02, query=8)


@pytest.fixture(scope="module")
def setup():
    return init_paper_model(SINE_MLP, jax.random.PRNGKey(0)), SineTasks()


def _assert_trees_equal(a, b):
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


# ---------------------------------------------------------------------------
# block planning
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("rounds,eval_every,max_block", [
    (120, 0, 512), (120, 20, 512), (50, 20, 512), (21, 7, 512),
    (21, 0, 8), (17, 7, 5), (1, 0, 512), (20, 30, 512),
])
def test_plan_blocks_covers_run_with_one_pad(rounds, eval_every, max_block):
    blocks, pad = plan_blocks(rounds, eval_every, max_block)
    # contiguous cover of [0, rounds)
    assert blocks[0][0] == 0 and blocks[-1][1] == rounds
    for (_, e0), (s1, _) in zip(blocks, blocks[1:]):
        assert e0 == s1
    # every block fits the single padded shape
    assert all(0 < e - s <= pad for s, e in blocks)
    # blocks never straddle an eval boundary
    if eval_every:
        for s, e in blocks:
            assert s // eval_every == (e - 1) // eval_every
    assert pad <= max_block and pad <= rounds


def test_plan_blocks_empty_run():
    assert plan_blocks(0, 0, 512) == ([], 0)


def test_plan_blocks_rejects_nonpositive_max_block():
    for bad in (0, -3):
        with pytest.raises(ValueError):
            plan_blocks(10, 0, bad)


# ---------------------------------------------------------------------------
# pipelined vs synchronous: bit-for-bit seeded parity
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("eval_every", [0, 7, 21])
@pytest.mark.parametrize("sampler", ["reference", "vectorized"])
def test_prefetch_parity_across_eval_cadence(setup, eval_every, sampler):
    params, dist = setup
    kw = dict(rounds=21, alpha=1.0, beta=0.02, support=4, seed=3,
              eval_every=eval_every, eval_kwargs=EVAL,
              sampling=UniformSampling(sampler))
    sync = tinyreptile_train(LOSS, params, dist, prefetch=0, **kw)
    piped = tinyreptile_train(LOSS, params, dist, prefetch=2, **kw)
    _assert_trees_equal(sync["params"], piped["params"])
    assert sync["history"] == piped["history"]
    assert sync["comm_bytes"] == piped["comm_bytes"]


def test_prefetch_parity_uneven_max_block_tail(setup):
    """rounds % max_block != 0: the short tail block is padded + masked,
    not re-traced — and numerics stay bitwise identical."""
    params, dist = setup
    kw = dict(rounds=21, alpha=1.0, beta=0.02, support=4, seed=5,
              max_block=8, clients_per_round=3, epochs=2)
    sync = reptile_train(LOSS, params, dist, prefetch=0, **kw)
    piped = reptile_train(LOSS, params, dist, prefetch=2, **kw)
    _assert_trees_equal(sync["params"], piped["params"])


def test_sampling_policy_object_param(setup):
    """A run with no policy is the run with ``UniformSampling()``."""
    params, dist = setup
    kw = dict(rounds=9, alpha=1.0, beta=0.02, support=4, seed=2)
    a = run_federated(params, dist, TinyReptileStrategy(LOSS), **kw)
    b = run_federated(params, dist, TinyReptileStrategy(LOSS),
                      sampling=UniformSampling(), **kw)
    _assert_trees_equal(a["params"], b["params"])
    with pytest.raises(ValueError):
        UniformSampling("bogus")


class _CallData(TaskDistribution):
    """A run's client data made in bulk up front and handed out in the
    engine's order, as the chip benchmark's ``CallTasks`` does: task by task
    and sample by sample to the per-task loop, whole rounds to the block
    path. Client c of round r gets the same samples either way. Counts
    both kinds of call."""

    def __init__(self, rounds, clients, support):
        self.clients, self.tasks, self.blocks = clients, 0, 0
        self.data = SineTasks().sample_support_block(
            np.random.default_rng(7), rounds, clients, support)

    def sample_task(self, rng):
        r, c = divmod(self.tasks, self.clients)
        self.tasks += 1
        samples = zip(self.data["x"][r, c], self.data["y"][r, c])
        return ClientTask(make_sample=lambda _rng: next(samples),
                          task_id=self.tasks - 1)

    def sample_support_block(self, rng, rounds, clients, support,
                             data_mode="batch", participation=None):
        r0 = self.blocks * rounds
        self.blocks += 1
        return self._mask_block({k: v[r0:r0 + rounds].copy()
                                 for k, v in self.data.items()},
                                participation)


def test_default_run_takes_one_block_call_a_block(setup):
    """Naming no policy, a run asks the distribution for each block's
    data in one call and never for a task; on data served alike to both
    routes it computes the per-task loop's params bit for bit."""
    params, _ = setup
    kw = dict(rounds=12, clients_per_round=3, alpha=1.0, beta=0.02,
              support=4, seed=5, max_block=4)
    block_dist, loop_dist = _CallData(12, 3, 4), _CallData(12, 3, 4)
    block = run_federated(params, block_dist, TinyReptileStrategy(LOSS),
                          **kw)
    loop = run_federated(params, loop_dist, TinyReptileStrategy(LOSS),
                         sampling=UniformSampling("reference"), **kw)
    assert (block_dist.blocks, block_dist.tasks) == (3, 0)
    assert (loop_dist.blocks, loop_dist.tasks) == (0, 12 * 3)
    _assert_trees_equal(block["params"], loop["params"])


@pytest.mark.parametrize("pool_sampler", ["reference", "vectorized"])
def test_pooled_run_follows_the_pool_sampler(setup, pool_sampler):
    """With a pool and no policy, the run seats cohorts through the block
    path whatever the pool's identity (the same seating as naming
    ``UniformSampling()``, another than the per-round loop: the pool is
    large enough beside the cohort for the two to differ), and the pool
    draws the data by its own identity."""
    params, dist = setup
    other = {"reference": "vectorized", "vectorized": "reference"}
    kw = dict(rounds=6, clients_per_round=3, beta=0.02, support=4, seed=4)

    def run(identity=pool_sampler, **extra):
        pool = ClientPool(dist, 64, seed=1, sampler=identity)
        out = tinyreptile_train(LOSS, params, dist, pool=pool, **extra,
                                **kw)
        return out["pool_state"]["checkins"], out["params"]
    seats, params_default = run()
    np.testing.assert_array_equal(
        seats, run(sampling=UniformSampling())[0])
    assert not np.array_equal(
        seats, run(sampling=UniformSampling("reference"))[0])
    other_seats, params_other = run(other[pool_sampler])
    np.testing.assert_array_equal(seats, other_seats)
    assert not all(np.array_equal(a, b) for a, b in zip(
        jax.tree.leaves(params_default), jax.tree.leaves(params_other)))


# ---------------------------------------------------------------------------
# retrace-free fixed shapes: exactly one compile per config
# ---------------------------------------------------------------------------

def test_single_trace_across_uneven_eval_blocks(setup):
    """17 rounds at eval_every=7 -> blocks 7, 7, 3 all padded to 7: the
    runner traces exactly once (the tentpole's acceptance criterion)."""
    params, dist = setup
    clear_runner_cache()
    beta = 0.0701                        # unique config -> fresh runner
    tinyreptile_train(LOSS, params, dist, rounds=17, alpha=1.0, beta=beta,
                      support=4, seed=3, eval_every=7, eval_kwargs=EVAL)
    runner = _block_runner(TinyReptileStrategy(LOSS, use_pallas=None),
                           beta, CommChannel())
    assert runner.trace_count == 1
    # a second identical run reuses the cached executable: still 1 trace
    tinyreptile_train(LOSS, params, dist, rounds=17, alpha=1.0, beta=beta,
                      support=4, seed=4, eval_every=7, eval_kwargs=EVAL)
    assert runner.trace_count == 1


def test_single_trace_uneven_max_block_tail(setup):
    params, dist = setup
    clear_runner_cache()
    beta = 0.0702
    tinyreptile_train(LOSS, params, dist, rounds=21, alpha=1.0, beta=beta,
                      support=4, seed=3, max_block=8)   # blocks 8, 8, 5
    runner = _block_runner(TinyReptileStrategy(LOSS, use_pallas=None),
                           beta, CommChannel())
    assert runner.trace_count == 1


# ---------------------------------------------------------------------------
# vectorized block sampling
# ---------------------------------------------------------------------------

def test_sine_vectorized_block_matches_scalar_block_order_loop():
    """The vectorized sine sampler is bit-for-bit a scalar loop in the
    documented block RNG order: all (a, b, c) task triples row-by-row,
    then every support input, then the same per-sample math."""
    dist = SineTasks()
    rounds, clients, support = 4, 3, 5
    vec = dist.sample_support_block(np.random.default_rng(9), rounds,
                                    clients, support)
    rng = np.random.default_rng(9)
    n, (lo, hi) = rounds * clients, dist.x_range
    abc = np.array([[rng.uniform(0.1, 5.0), rng.uniform(0.8, 1.2),
                     rng.uniform(0.0, np.pi)] for _ in range(n)])
    x = np.array([[rng.uniform(lo, hi) for _ in range(support)]
                  for _ in range(n)], np.float32)[..., None]
    a, b, c = (abc[:, j, None, None] for j in range(3))
    y = (a * np.sin(b * x + c)).astype(np.float32)
    np.testing.assert_array_equal(vec["x"],
                                  x.reshape(rounds, clients, support, 1))
    np.testing.assert_array_equal(vec["y"],
                                  y.reshape(rounds, clients, support, 1))


@pytest.mark.parametrize("dist,ways", [
    (OmniglotTasks(num_classes=30, ways=5), 5),
    (KWSTasks(num_words=12, ways=4), 4),
])
def test_vectorized_block_matches_reference_contract(dist, ways):
    """Vectorized Omniglot/KWS blocks match the reference loop's shapes,
    dtypes, and label/value ranges (the RNG block order is documented to
    differ, so values are distribution-equal, not bitwise-equal)."""
    rounds, clients, support = 3, 2, 4
    ref = dist.sample_support_block_reference(np.random.default_rng(1),
                                              rounds, clients, support)
    vec = dist.sample_support_block(np.random.default_rng(1), rounds,
                                    clients, support)
    assert vec["x"].shape == ref["x"].shape
    assert vec["y"].shape == ref["y"].shape
    assert vec["x"].dtype == ref["x"].dtype == np.float32
    assert vec["y"].dtype == ref["y"].dtype == np.int32
    assert np.isfinite(vec["x"]).all()
    assert vec["y"].min() >= 0 and vec["y"].max() < ways


def test_omniglot_vectorized_block_matches_scalar_block_order_loop():
    """The fully-vectorized Omniglot sampler (no per-task Python loop
    left) is bit-for-bit a scalar loop in the documented block RNG
    order: one (n, num_classes) uniform draw argsorted per row for the
    class subsets, then labels, roll offsets, and noise as one array
    draw each, then the per-sample np.roll + noise math."""
    from repro.data.tasks import _glyph_prototype
    dist = OmniglotTasks(num_classes=12, ways=4, noise=0.1)
    rounds, clients, support, side = 2, 3, 4, 28
    n = rounds * clients
    vec = dist.sample_support_block(np.random.default_rng(11), rounds,
                                    clients, support)
    rng = np.random.default_rng(11)
    classes = np.argsort(rng.random((n, 12)), axis=1)[:, :4]
    labels = rng.integers(4, size=(n, support))
    shifts = rng.integers(-2, 3, size=(n, support, 2))
    noise = rng.normal(0, 0.1, size=(n, support, side, side)).astype(
        np.float32)
    x = np.zeros((n, support, side, side, 1), np.float32)
    for i in range(n):
        for s in range(support):
            proto = _glyph_prototype(int(classes[i, labels[i, s]]))
            img = np.roll(proto, tuple(shifts[i, s]), axis=(0, 1))
            x[i, s] = (img + noise[i, s])[..., None].astype(np.float32)
    np.testing.assert_array_equal(
        vec["x"], x.reshape(rounds, clients, support, side, side, 1))
    np.testing.assert_array_equal(
        vec["y"], labels.astype(np.int32).reshape(rounds, clients, support))


def test_kws_vectorized_block_matches_scalar_block_order_loop():
    """Same contract for the KWS sampler: one (n, num_words) uniform
    draw for the keyword subsets, then labels / shifts / amplitudes /
    noise as array draws, per-sample roll-scale-noise math bitwise."""
    from repro.data.tasks import _kws_prototype
    dist = KWSTasks(num_words=9, ways=3, noise=0.15)
    rounds, clients, support, t, f = 2, 2, 5, 49, 10
    n = rounds * clients
    vec = dist.sample_support_block(np.random.default_rng(13), rounds,
                                    clients, support)
    rng = np.random.default_rng(13)
    words = np.argsort(rng.random((n, 9)), axis=1)[:, :3]
    labels = rng.integers(3, size=(n, support))
    shifts = rng.integers(-3, 4, size=(n, support))
    amps = rng.uniform(0.8, 1.2, size=(n, support))
    noise = rng.normal(0, 0.15, size=(n, support, t, f)).astype(np.float32)
    x = np.zeros((n, support, t, f, 1), np.float32)
    for i in range(n):
        for s in range(support):
            proto = _kws_prototype(int(words[i, labels[i, s]]))
            m = np.roll(proto, int(shifts[i, s]), axis=0)
            x[i, s] = (m * amps[i, s] + noise[i, s])[..., None].astype(
                np.float32)
    np.testing.assert_array_equal(
        vec["x"], x.reshape(rounds, clients, support, t, f, 1))
    np.testing.assert_array_equal(
        vec["y"], labels.astype(np.int32).reshape(rounds, clients, support))


def test_choice_block_is_without_replacement_and_uniform():
    """The vectorized subset draw: rows are distinct-entry subsets, and
    with k == m every row is a full permutation (the argsort-of-uniform
    construction); k > m is rejected."""
    from repro.data.tasks import TaskDistribution
    got = TaskDistribution._choice_block(np.random.default_rng(0), 64, 10, 4)
    assert got.shape == (64, 4)
    assert all(len(set(row)) == 4 for row in got)
    perms = TaskDistribution._choice_block(np.random.default_rng(1), 32, 5, 5)
    assert (np.sort(perms, axis=1) == np.arange(5)).all()
    with pytest.raises(ValueError):
        TaskDistribution._choice_block(np.random.default_rng(2), 4, 3, 5)


@pytest.mark.parametrize("dist", [
    OmniglotTasks(num_classes=20, ways=5),
    KWSTasks(num_words=10, ways=4),
])
def test_vectorized_block_distribution_matches_reference(dist):
    """Seeded distributional parity with sample_support_block_reference:
    the vectorized block order draws different values for a given seed
    (documented since PR 2) but must sample the SAME distribution —
    pixel moments and label histograms agree over a large block."""
    rounds, clients, support = 16, 4, 8
    ref = dist.sample_support_block_reference(np.random.default_rng(3),
                                              rounds, clients, support)
    vec = dist.sample_support_block(np.random.default_rng(3), rounds,
                                    clients, support)
    np.testing.assert_allclose(vec["x"].mean(), ref["x"].mean(), atol=0.05)
    np.testing.assert_allclose(vec["x"].std(), ref["x"].std(), atol=0.05)
    ways = dist.ways
    href = np.bincount(ref["y"].ravel(), minlength=ways) / ref["y"].size
    hvec = np.bincount(vec["y"].ravel(), minlength=ways) / vec["y"].size
    np.testing.assert_allclose(hvec, href, atol=0.1)


def test_base_distribution_block_falls_back_to_reference():
    dist = SineTasks()
    ref = dist.sample_support_block_reference(np.random.default_rng(4),
                                              2, 2, 3)
    base = super(SineTasks, dist).sample_support_block  # unbound fallback
    got = base(np.random.default_rng(4), 2, 2, 3)
    np.testing.assert_array_equal(ref["x"], got["x"])
    np.testing.assert_array_equal(ref["y"], got["y"])


def test_vectorized_sampler_trains(setup):
    """End-to-end: the vectorized host path learns an adaptable init."""
    params, dist = setup
    out = tinyreptile_train(LOSS, params, dist, rounds=60, alpha=1.0,
                            beta=0.02, support=8, seed=1, eval_every=60,
                            eval_kwargs=EVAL,
                            sampling=UniformSampling("vectorized"))
    assert np.isfinite(out["history"][-1]["query_loss"])
    assert all(np.isfinite(np.asarray(l)).all()
               for l in jax.tree.leaves(out["params"]))


def test_client_schedule_is_a_pytree():
    """ClientSchedule device-stages through the prefetcher and slices
    through lax.scan like any other block input."""
    from repro.core import ClientSchedule
    sched = ClientSchedule(
        valid=np.array([True, False]),
        alpha=np.array([1.0, 0.5], np.float32),
        round_index=np.array([0, 1], np.int32),
        participation=np.ones((2, 3), bool),
        local_steps=np.full((2, 3), 4, np.int32),
        weights=np.full((2, 3), 1 / 3, np.float32))
    staged = jax.device_put(sched)
    assert isinstance(staged, ClientSchedule)
    rows = []

    def body(carry, s):
        rows.append(s)
        return carry, s.round_index

    _, idx = jax.lax.scan(body, 0, staged)
    np.testing.assert_array_equal(np.asarray(idx), [0, 1])
    assert rows[0].participation.shape == (3,)     # per-round row slices
    assert rows[0].valid.shape == ()


# ---------------------------------------------------------------------------
# the prefetcher itself
# ---------------------------------------------------------------------------

def test_prefetcher_yields_in_order_and_closes():
    pf = BlockPrefetcher(lambda i: i * i, 7, depth=2)
    assert [pf.get() for _ in range(7)] == [i * i for i in range(7)]
    # over-consumption raises instead of deadlocking on the empty queue
    with pytest.raises(StopIteration):
        pf.get()
    pf.close()
    pf.close()                                   # idempotent
    with pytest.raises(StopIteration):
        pf.get()                                 # closed -> exhausted


def test_prefetcher_propagates_producer_errors():
    def produce(i):
        if i == 1:
            raise RuntimeError("boom")
        return i
    pf = BlockPrefetcher(produce, 5, depth=2)
    assert pf.get() == 0
    with pytest.raises(RuntimeError, match="boom"):
        pf.get()
    pf.close()


def test_prefetcher_early_close_does_not_deadlock():
    pf = BlockPrefetcher(lambda i: i, 100, depth=1)
    assert pf.get() == 0
    pf.close()                                   # 99 items never consumed


# ---------------------------------------------------------------------------
# TinyMetaFed-style partial communication
# ---------------------------------------------------------------------------

def test_partial_channel_accounting(setup):
    params, _ = setup
    ch = PartialCommChannel(fraction=0.25)
    want = sum(max(1, int(round(0.25 * x.size))) * 4
               for x in jax.tree.leaves(params))
    assert ch.payload_bytes(params) == want
    assert ch.round_bytes(params, 3) == 2 * 3 * want
    assert want < tree_bytes(params) // 3        # genuinely partial
    # fraction=1.0 degenerates to the base fp32 accounting
    assert PartialCommChannel(fraction=1.0).payload_bytes(params) == \
        tree_bytes(params)
    for bad in (0.0, -0.5, 1.5):
        with pytest.raises(ValueError):
            PartialCommChannel(fraction=bad)


def test_partial_channel_masks_uplink_delta():
    r = np.random.default_rng(0)
    ref = {"w": jnp.asarray(r.normal(size=(128,)), jnp.float32)}
    sent = {"w": jnp.asarray(r.normal(size=(128,)), jnp.float32)}
    ch = PartialCommChannel(fraction=0.5)
    got = np.asarray(ch.transmit(sent, ref=ref)["w"])
    from_sent = got == np.asarray(sent["w"])
    from_ref = got == np.asarray(ref["w"])
    assert (from_sent | from_ref).all()
    assert from_sent.sum() == ch.kept_entries(128)
    # deterministic: the mask is fixed by mask_seed
    again = np.asarray(ch.transmit(sent, ref=ref)["w"])
    np.testing.assert_array_equal(got, again)
    # no ref (downlink): value-exact broadcast
    np.testing.assert_array_equal(np.asarray(ch.transmit(sent)["w"]),
                                  np.asarray(sent["w"]))


def test_partial_channel_int8_keeps_server_values_exact():
    """On a quantizing wire, untransmitted entries fall back to the
    server's OWN values bit-exactly — only transmitted entries carry
    quantization noise."""
    r = np.random.default_rng(2)
    ref = {"w": jnp.asarray(r.normal(size=(128,)), jnp.float32)}
    sent = {"w": jnp.asarray(r.normal(size=(128,)), jnp.float32)}
    ch = PartialCommChannel(dtype="int8", fraction=0.5)
    got = np.asarray(ch.transmit(sent, ref=ref)["w"])
    wired = np.asarray(CommChannel("int8").transmit(sent)["w"])
    from_ref = got == np.asarray(ref["w"])
    from_wire = got == wired
    assert (from_ref | from_wire).all()
    assert from_ref.sum() >= 128 - ch.kept_entries(128)


def test_quantize_true_on_fp32_wire_rejected():
    with pytest.raises(ValueError):
        CommChannel("float32", quantize=True)


def test_partial_channel_wire_gating():
    """quantize=False keeps the accounting-only contract (no dtype cast
    anywhere), and quantizing partial downlinks stay value-exact."""
    r = np.random.default_rng(3)
    ref = {"w": jnp.asarray(r.normal(size=(64,)), jnp.float32)}
    sent = {"w": jnp.asarray(r.normal(size=(64,)), jnp.float32)}
    acct = PartialCommChannel(dtype="float16", quantize=False, fraction=0.5)
    np.testing.assert_array_equal(np.asarray(acct.transmit(sent)["w"]),
                                  np.asarray(sent["w"]))
    up = np.asarray(acct.transmit(sent, ref=ref)["w"])
    assert ((up == np.asarray(sent["w"])) | (up == np.asarray(ref["w"]))).all()
    # metering still sees the compressed link: fp16 itemsize, half entries
    assert acct.payload_bytes(ref) == acct.kept_entries(64) * 2
    # quantizing partial downlink: kept entries ride the int8 wire,
    # dropped entries stay exact — converging to the base channel at 1.0
    ch = PartialCommChannel(dtype="int8", fraction=0.5)
    down = np.asarray(ch.transmit(sent)["w"])
    wired = np.asarray(CommChannel("int8").transmit(sent)["w"])
    exact = down == np.asarray(sent["w"])
    assert (exact | (down == wired)).all()
    assert exact.sum() >= 64 - ch.kept_entries(64)
    full = PartialCommChannel(dtype="int8", fraction=1.0)
    np.testing.assert_array_equal(np.asarray(full.transmit(sent)["w"]),
                                  wired)


def test_partial_channel_trains_and_meters(setup):
    params, dist = setup
    ch = PartialCommChannel(fraction=0.5)
    out = tinyreptile_train(LOSS, params, dist, rounds=30, alpha=1.0,
                            beta=0.02, support=8, seed=1, channel=ch)
    assert out["comm_bytes"] == 30 * 2 * ch.payload_bytes(params)
    assert all(np.isfinite(np.asarray(l)).all()
               for l in jax.tree.leaves(out["params"]))


def test_partial_channel_gradient_uplink(setup):
    """FedSGD's uplink reference is zeros: untransmitted gradient entries
    vanish rather than falling back to phi."""
    params, dist = setup
    out = fedsgd_train(LOSS, params, dist, rounds=10, beta=0.02, support=4,
                       clients_per_round=2, seed=0,
                       channel=PartialCommChannel(fraction=0.5))
    assert all(np.isfinite(np.asarray(l)).all()
               for l in jax.tree.leaves(out["params"]))


# ---------------------------------------------------------------------------
# runner cache counters
# ---------------------------------------------------------------------------

def test_runner_cache_stats_and_clear(setup, caplog):
    params, dist = setup
    clear_runner_cache()
    stats = runner_cache_stats()
    assert stats["currsize"] == 0 and stats["unhashable_misses"] == 0

    kw = dict(rounds=5, alpha=1.0, beta=0.0703, support=4, seed=0)
    tinyreptile_train(LOSS, params, dist, **kw)
    tinyreptile_train(LOSS, params, dist, **kw)
    stats = runner_cache_stats()
    assert stats["misses"] == 1 and stats["hits"] == 1

    @dataclasses.dataclass(frozen=True)
    class UnhashableStrategy(TinyReptileStrategy):
        junk: list = dataclasses.field(default_factory=list)

    from repro.core import run_federated
    with caplog.at_level("WARNING", logger="repro.core.engine"):
        run_federated(params, dist, UnhashableStrategy(LOSS), rounds=5,
                      beta=0.0703, support=4, seed=0)
    assert runner_cache_stats()["unhashable_misses"] == 1
    assert any("unhashable" in r.message for r in caplog.records)

    clear_runner_cache()
    stats = runner_cache_stats()
    assert stats["currsize"] == 0 and stats["unhashable_misses"] == 0
