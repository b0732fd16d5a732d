"""Engine refactor parity: the strategy-backed ``*_train`` entry points
must reproduce the pre-engine per-round Python loops — same seed, same
history, same final params (tolerance <= 1e-5) — when they sample client
data in the loops' per-task order (``UniformSampling("reference")``).

The legacy loops live HERE as fixtures (verbatim from the seed
implementations, evals included), not in src/: the engine is the only
production loop.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.paper_models import SINE_MLP
from repro.core import (CommChannel, UniformSampling, fedavg_train,
                        fedsgd_train, reptile_train, tifed_train,
                        tinyreptile_train, transfer_train)
from repro.core.engine import _block_runner
from repro.core.meta import (evaluate_init, finetune_batch, finetune_online,
                             tree_bytes, tree_lerp)
from repro.core.strategies import TifedStrategy
from repro.data import SineTasks
from repro.models.paper_nets import (init_paper_model, paper_model_loss,
                                     relu_mlp_loss)

LOSS = functools.partial(paper_model_loss, SINE_MLP)
EVAL = dict(num_tasks=4, support=8, k_steps=4, lr=0.02, query=16)
# the legacy loops draw each task, then its samples: the engine's
# per-task "reference" sampler, not its default block order
REF = dict(sampling=UniformSampling("reference"))


# ---------------------------------------------------------------------------
# legacy loops (seed implementations, kept as the parity reference)
# ---------------------------------------------------------------------------

def _legacy_tinyreptile(loss_fn, init_params, task_dist, *, rounds, alpha,
                        beta, support, anneal=True, seed=0, eval_every=0,
                        eval_kwargs=None):
    rng = np.random.default_rng(seed)
    phi = init_params
    history = []
    pbytes = tree_bytes(phi)
    comm_bytes = 0
    for rnd in range(rounds):
        alpha_t = alpha * (1 - rnd / rounds) if anneal else alpha
        task = task_dist.sample_task(rng)
        comm_bytes += pbytes
        xs, ys = zip(*task.support_stream(rng, support))
        phi_hat, inner_losses = finetune_online(
            loss_fn, phi, jnp.stack(xs), jnp.stack(ys), jnp.float32(beta))
        comm_bytes += pbytes
        phi = tree_lerp(phi, phi_hat, alpha_t)
        if eval_every and (rnd + 1) % eval_every == 0:
            ev = evaluate_init(loss_fn, phi, task_dist,
                               np.random.default_rng(10_000 + rnd),
                               **(eval_kwargs or {}))
            ev.update(round=rnd + 1, comm_bytes=comm_bytes,
                      inner_loss=float(inner_losses.mean()))
            history.append(ev)
    return {"params": phi, "history": history, "comm_bytes": comm_bytes}


def _legacy_reptile(loss_fn, init_params, task_dist, *, rounds, alpha, beta,
                    support, epochs, clients_per_round=1, anneal=True,
                    seed=0, eval_every=0, eval_kwargs=None):
    rng = np.random.default_rng(seed)
    phi = init_params
    history = []
    pbytes = tree_bytes(phi)
    comm_bytes = 0
    for rnd in range(rounds):
        alpha_t = alpha * (1 - rnd / rounds) if anneal else alpha
        deltas = None
        inner_loss = 0.0
        for _ in range(clients_per_round):
            task = task_dist.sample_task(rng)
            comm_bytes += 2 * pbytes
            sup = task.support_batch(rng, support)
            phi_hat, losses = finetune_batch(loss_fn, phi, sup, epochs,
                                             jnp.float32(beta))
            inner_loss += float(losses.mean()) / clients_per_round
            d = jax.tree.map(lambda q, p: q - p, phi_hat, phi)
            deltas = d if deltas is None else jax.tree.map(
                lambda a, b: a + b, deltas, d)
        phi = jax.tree.map(
            lambda p, d: p + alpha_t * d / clients_per_round, phi, deltas)
        if eval_every and (rnd + 1) % eval_every == 0:
            ev = evaluate_init(loss_fn, phi, task_dist,
                               np.random.default_rng(10_000 + rnd),
                               **(eval_kwargs or {}))
            ev.update(round=rnd + 1, comm_bytes=comm_bytes,
                      inner_loss=inner_loss)
            history.append(ev)
    return {"params": phi, "history": history, "comm_bytes": comm_bytes}


def _legacy_fedavg(loss_fn, init_params, task_dist, *, rounds, beta, support,
                   epochs, clients_per_round, seed=0, eval_every=0,
                   eval_kwargs=None):
    rng = np.random.default_rng(seed)
    phi = init_params
    history = []
    pbytes = tree_bytes(phi)
    comm_bytes = 0
    for rnd in range(rounds):
        acc = None
        for _ in range(clients_per_round):
            task = task_dist.sample_task(rng)
            comm_bytes += 2 * pbytes
            sup = task.support_batch(rng, support)
            phi_c, _ = finetune_batch(loss_fn, phi, sup, epochs,
                                      jnp.float32(beta))
            acc = phi_c if acc is None else jax.tree.map(
                lambda a, b: a + b, acc, phi_c)
        phi = jax.tree.map(lambda a: a / clients_per_round, acc)
        if eval_every and (rnd + 1) % eval_every == 0:
            ev = evaluate_init(loss_fn, phi, task_dist,
                               np.random.default_rng(10_000 + rnd),
                               **(eval_kwargs or {}))
            ev.update(round=rnd + 1, comm_bytes=comm_bytes)
            history.append(ev)
    return {"params": phi, "history": history, "comm_bytes": comm_bytes}


def _legacy_fedsgd(loss_fn, init_params, task_dist, *, rounds, beta, support,
                   clients_per_round, seed=0, eval_every=0, eval_kwargs=None):
    rng = np.random.default_rng(seed)
    phi = init_params
    history = []
    pbytes = tree_bytes(phi)
    comm_bytes = 0
    grad_fn = jax.jit(jax.grad(loss_fn))
    for rnd in range(rounds):
        gacc = None
        for _ in range(clients_per_round):
            task = task_dist.sample_task(rng)
            comm_bytes += 2 * pbytes
            sup = task.support_batch(rng, support)
            g = grad_fn(phi, sup)
            gacc = g if gacc is None else jax.tree.map(
                lambda a, b: a + b, gacc, g)
        phi = jax.tree.map(lambda p, g: p - beta * g / clients_per_round,
                           phi, gacc)
        if eval_every and (rnd + 1) % eval_every == 0:
            ev = evaluate_init(loss_fn, phi, task_dist,
                               np.random.default_rng(10_000 + rnd),
                               **(eval_kwargs or {}))
            ev.update(round=rnd + 1, comm_bytes=comm_bytes)
            history.append(ev)
    return {"params": phi, "history": history, "comm_bytes": comm_bytes}


def _legacy_transfer(loss_fn, init_params, task_dist, *, rounds, beta,
                     batch_per_round=32, tasks_per_round=8, seed=0,
                     eval_every=0, eval_kwargs=None):
    rng = np.random.default_rng(seed)
    phi = init_params
    history = []
    step = jax.jit(lambda p, b, lr: jax.tree.map(
        lambda w, g: w - lr * g, p, jax.grad(loss_fn)(p, b)))
    per_task = max(batch_per_round // tasks_per_round, 1)
    for rnd in range(rounds):
        xs, ys = [], []
        for _ in range(tasks_per_round):
            task = task_dist.sample_task(rng)
            b = task.support_batch(rng, per_task)
            xs.append(b["x"])
            ys.append(b["y"])
        batch = {"x": np.concatenate(xs), "y": np.concatenate(ys)}
        phi = step(phi, batch, jnp.float32(beta))
        if eval_every and (rnd + 1) % eval_every == 0:
            ev = evaluate_init(loss_fn, phi, task_dist,
                               np.random.default_rng(10_000 + rnd),
                               **(eval_kwargs or {}))
            ev.update(round=rnd + 1)
            history.append(ev)
    return {"params": phi, "history": history}


# ---------------------------------------------------------------------------
# parity assertions
# ---------------------------------------------------------------------------

def _assert_parity(got, want, *, check_comm=True):
    for a, b in zip(jax.tree.leaves(got["params"]),
                    jax.tree.leaves(want["params"])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-5)
    if check_comm:
        assert got["comm_bytes"] == want["comm_bytes"]
    assert len(got["history"]) == len(want["history"])
    for ge, we in zip(got["history"], want["history"]):
        assert set(ge) == set(we), (ge, we)
        for k, v in we.items():
            if isinstance(v, (int, np.integer)):
                assert ge[k] == v, (k, ge[k], v)
            else:
                np.testing.assert_allclose(ge[k], v, rtol=1e-5, atol=1e-5,
                                           err_msg=k)


@pytest.fixture(scope="module")
def setup():
    return init_paper_model(SINE_MLP, jax.random.PRNGKey(0)), SineTasks()


def test_tinyreptile_parity(setup):
    params, dist = setup
    kw = dict(rounds=60, alpha=1.0, beta=0.02, support=8, seed=11,
              eval_every=20, eval_kwargs=EVAL)
    _assert_parity(tinyreptile_train(LOSS, params, dist, **REF, **kw),
                   _legacy_tinyreptile(LOSS, params, dist, **kw))


def test_tinyreptile_no_anneal_no_eval_parity(setup):
    params, dist = setup
    kw = dict(rounds=25, alpha=0.7, beta=0.02, support=8, seed=12,
              anneal=False)
    _assert_parity(tinyreptile_train(LOSS, params, dist, **REF, **kw),
                   _legacy_tinyreptile(LOSS, params, dist, **kw))


def test_reptile_serial_parity(setup):
    params, dist = setup
    kw = dict(rounds=40, alpha=1.0, beta=0.02, support=8, epochs=4,
              clients_per_round=1, seed=13, eval_every=20, eval_kwargs=EVAL)
    _assert_parity(reptile_train(LOSS, params, dist, **REF, **kw),
                   _legacy_reptile(LOSS, params, dist, **kw))


def test_reptile_batched_parity(setup):
    params, dist = setup
    kw = dict(rounds=30, alpha=1.0, beta=0.02, support=8, epochs=4,
              clients_per_round=3, seed=14, eval_every=15, eval_kwargs=EVAL)
    _assert_parity(reptile_train(LOSS, params, dist, **REF, **kw),
                   _legacy_reptile(LOSS, params, dist, **kw))


def test_fedavg_parity(setup):
    params, dist = setup
    kw = dict(rounds=20, beta=0.02, support=8, epochs=4,
              clients_per_round=3, seed=15, eval_every=10, eval_kwargs=EVAL)
    _assert_parity(fedavg_train(LOSS, params, dist, **REF, **kw),
                   _legacy_fedavg(LOSS, params, dist, **kw))


def test_fedsgd_parity(setup):
    params, dist = setup
    kw = dict(rounds=30, beta=0.02, support=8, clients_per_round=3,
              seed=16, eval_every=15, eval_kwargs=EVAL)
    _assert_parity(fedsgd_train(LOSS, params, dist, **REF, **kw),
                   _legacy_fedsgd(LOSS, params, dist, **kw))


def test_transfer_parity(setup):
    params, dist = setup
    kw = dict(rounds=40, beta=0.02, batch_per_round=24, tasks_per_round=6,
              seed=17, eval_every=20, eval_kwargs=EVAL)
    got = transfer_train(LOSS, params, dist, **REF, **kw)
    want = _legacy_transfer(LOSS, params, dist, **kw)
    assert "comm_bytes" not in got and "comm_bytes" not in want
    _assert_parity(got, want, check_comm=False)


def test_schedule_threaded_engine_is_bitwise_uniform(setup):
    """PR-3 acceptance: with UniformSampling (full participation,
    uniform local steps) the schedule-threaded engine must be bit-for-bit
    the PR-2 engine for all five strategies. The legacy-loop parity
    tests above pin the numerics to the seed; this pins the explicit
    schedule object to the default path — the ClientSchedule arrays ride
    the scan but the uniform body must not touch them."""
    params, dist = setup
    cases = [
        (tinyreptile_train, dict(rounds=15, alpha=1.0, beta=0.02,
                                 support=6, seed=21)),
        (reptile_train, dict(rounds=10, alpha=1.0, beta=0.02, support=6,
                             epochs=3, clients_per_round=3, seed=22)),
        (fedavg_train, dict(rounds=8, beta=0.02, support=6, epochs=3,
                            clients_per_round=3, seed=23)),
        (fedsgd_train, dict(rounds=10, beta=0.02, support=6,
                            clients_per_round=3, seed=24)),
        (transfer_train, dict(rounds=10, beta=0.02, batch_per_round=12,
                              tasks_per_round=3, seed=25)),
    ]
    for train_fn, kw in cases:
        default = train_fn(LOSS, params, dist, **kw)
        threaded = train_fn(LOSS, params, dist,
                            sampling=UniformSampling(), **kw)
        for a, b in zip(jax.tree.leaves(default["params"]),
                        jax.tree.leaves(threaded["params"])):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        if "comm_bytes" in default:
            assert default["comm_bytes"] == threaded["comm_bytes"]
            assert default["per_client_bytes"] == \
                threaded["per_client_bytes"]


def test_engine_does_not_clobber_init_params(setup):
    """The engine donates its working buffers; the caller's init_params
    must survive (they are reused across algorithm runs in benches)."""
    params, dist = setup
    before = jax.tree.map(lambda x: np.array(x), params)
    tinyreptile_train(LOSS, params, dist, rounds=8, beta=0.02, support=4,
                      seed=0)
    for a, b in zip(jax.tree.leaves(before), jax.tree.leaves(params)):
        np.testing.assert_array_equal(a, np.asarray(b))


TIFED_EVAL = dict(num_tasks=4, support=8, k_steps=4, lr=0.005, query=16)


def test_tifed_seeded_determinism(setup):
    """Same seed -> bitwise-identical params and history (the dither
    planes are baked trace constants, so nothing is run-dependent)."""
    params, dist = setup
    kw = dict(rounds=20, alpha=1.0, support=16, clients_per_round=4,
              seed=31, eval_every=10, eval_kwargs=TIFED_EVAL)
    a = tifed_train(params, dist, **kw)
    b = tifed_train(params, dist, **kw)
    for x, y in zip(jax.tree.leaves(a["params"]),
                    jax.tree.leaves(b["params"])):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    assert a["comm_bytes"] == b["comm_bytes"]
    assert len(a["history"]) == 2
    for ea, eb in zip(a["history"], b["history"]):
        assert set(ea) == set(eb)
        for k in ea:
            np.testing.assert_array_equal(ea[k], eb[k], err_msg=k)


def test_tifed_pipelined_matches_sync_bitwise(setup):
    """Prefetch + block splitting must not change the integer
    trajectory at all (sampler held fixed: the two sampler flavours have
    documentedly different block RNG orders)."""
    params, dist = setup
    kw = dict(rounds=16, alpha=1.0, support=16, clients_per_round=4,
              seed=32, **REF)
    sync = tifed_train(params, dist, prefetch=0, **kw)
    piped = tifed_train(params, dist, prefetch=2, max_block=4, **kw)
    for x, y in zip(jax.tree.leaves(sync["params"]),
                    jax.tree.leaves(piped["params"])):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    assert sync["comm_bytes"] == piped["comm_bytes"]


def test_tifed_single_trace_and_int8_billing(setup):
    """One jit trace per config, and the uplink bills at the int8 rate:
    1 byte/param both directions, 4x under the fp32 bill for the same
    traffic (the 6 exponent scalars ride free)."""
    params, dist = setup
    # lr_shift=5 gives this test its own cached runner (the runner cache
    # keys on the strategy dataclass), so trace_count pins THIS config
    rounds, clients = 12, 4
    out = tifed_train(params, dist, rounds=rounds, alpha=1.0, support=16,
                      clients_per_round=clients, lr_shift=5, seed=33)
    runner = _block_runner(TifedStrategy(relu_mlp_loss, epochs=8,
                                         lr_shift=5), 0.0,
                           CommChannel("int8", quantize=False),
                           scheduled=False)
    assert runner.trace_count == 1
    n_params = sum(int(np.prod(l.shape)) for l in jax.tree.leaves(params))
    assert out["comm_bytes"] == 2 * clients * rounds * n_params
    fp32_bill = 2 * clients * rounds * tree_bytes(params)
    assert out["comm_bytes"] * 4 == fp32_bill


def test_tifed_rejects_incompatible_channels(setup):
    """tifed uplinks NATIVE int8 trees: an fp32 wire or a simulating
    channel would double-quantize or mis-bill, so the engine refuses."""
    params, dist = setup
    for bad in (CommChannel(),                       # fp32 wire
                CommChannel("int8"),                 # simulates int8
                CommChannel("float16", quantize=False)):  # wrong width
        with pytest.raises(ValueError, match="payload_dtype"):
            tifed_train(params, dist, rounds=2, support=4, channel=bad)


def test_tifed_learns_sine(setup):
    """End-to-end sanity: integer training actually reduces query loss
    vs the untrained init under the paper's eval protocol."""
    params, dist = setup
    out = tifed_train(params, dist, rounds=40, alpha=1.0, support=32,
                      clients_per_round=4, seed=34, eval_every=40,
                      eval_kwargs=TIFED_EVAL)
    ev0 = evaluate_init(relu_mlp_loss, params, dist,
                        np.random.default_rng(10_039), **TIFED_EVAL)
    assert np.isfinite(out["history"][-1]["query_loss"])
    assert out["history"][-1]["query_loss"] < ev0["query_loss"]


def test_pallas_server_update_in_scan(setup):
    """The engine's Pallas meta_update route agrees with the XLA route."""
    params, dist = setup
    kw = dict(rounds=12, alpha=0.8, beta=0.02, support=8, seed=18)
    xla = tinyreptile_train(LOSS, params, dist, use_pallas=False, **kw)
    pal = tinyreptile_train(LOSS, params, dist, use_pallas=True, **kw)
    for a, b in zip(jax.tree.leaves(xla["params"]),
                    jax.tree.leaves(pal["params"])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5)
