"""Serving parity: the continuous-batching AdaptationServer must serve
exactly what the offline adaptation math computes.

Contracts pinned here:

- served request == `serving.offline_adapt` (the independently-jitted
  one-shot vmapped reference at the same slot width) BIT-FOR-BIT —
  params, query loss, and step counts — for the fp32 online-SGD route
  and the int8 TIFeD route, including across slot retire/refill waves
  with adversarial ragged k.
- int8 served params are additionally EXACTLY equal to the engine's
  scalar `TifedStrategy._run_epochs` (integer-valued fp32 arithmetic is
  vmap-width invariant); the fp32 route matches the scalar
  `finetune_online` API to ~1e-6 (vmap changes fp reduction lowering —
  the same contract as the engine's 1-vs-N-device parity).
- the whole serve loop is ONE jit trace per (adapter, slots, shapes)
  config, across refills, resets, and phi swaps.
- a `checkpoint.load_params` phi (from a training checkpoint) serves
  bit-for-bit identically to the in-memory phi.
- a tick moves one packed array each way: the refill reaches the device
  by one explicit transfer, and the query losses come back through the
  int32 result with every bit (NaN, infinities, -0.0) kept.
"""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import load_params, save_checkpoint
from repro.configs.paper_models import SINE_MLP
from repro.core import run_federated
from repro.core.meta import finetune_online
from repro.core.strategies import (TifedStrategy, tifed_dequantize,
                                   tifed_requantize, TinyReptileStrategy)
from repro.data import SineTasks
from repro.models.paper_nets import (init_paper_model, paper_model_loss,
                                     relu_mlp_loss)
from repro.serving import (AdaptationServer, Fp32Adapter, TifedAdapter,
                           offline_adapt)

LOSS = functools.partial(paper_model_loss, SINE_MLP)


@pytest.fixture(scope="module")
def phi():
    return init_paper_model(SINE_MLP, jax.random.PRNGKey(0))


def make_requests(n, support, query, ks, seed=0):
    rng = np.random.default_rng(seed)
    reqs = []
    for i in range(n):
        a, b = rng.uniform(0.1, 5.0), rng.uniform(0.0, np.pi)
        sx = rng.uniform(-5, 5, (support, 1)).astype(np.float32)
        qx = rng.uniform(-5, 5, (query, 1)).astype(np.float32)
        reqs.append({"sx": sx, "sy": np.float32(a * np.sin(sx + b)),
                     "qx": qx, "qy": np.float32(a * np.sin(qx + b)),
                     "k": ks[i % len(ks)]})
    return reqs


def serve_all(server, reqs):
    for r in reqs:
        server.submit(r["sx"], r["sy"], r["qx"], r["qy"], r["k"])
    return {res.rid: res for res in server.drain()}


def assert_results_equal(results, offline):
    for i, off in enumerate(offline):
        res = results[i]
        assert res.steps == off["steps"]
        np.testing.assert_array_equal(
            np.float32(res.query_loss), np.float32(off["query_loss"]),
            err_msg=f"request {i}: query loss diverged")
        for leaf in off["params"]:
            np.testing.assert_array_equal(
                res.params[leaf], off["params"][leaf],
                err_msg=f"request {i}: params[{leaf}] diverged")


# -- fp32 route -------------------------------------------------------------

def test_fp32_served_matches_offline_bitwise(phi):
    """Ragged k, 3 refill waves over 4 slots: every request bit-equal
    to the one-shot offline reference at the same width."""
    adapter = Fp32Adapter(loss_fn=LOSS, lr=0.01)
    reqs = make_requests(12, support=10, query=16,
                         ks=(3, 10, 7, 1, 5, 9, 2, 10, 4, 6, 8, 10))
    server = AdaptationServer(phi, adapter, slots=4, k_max=10,
                              steps_per_tick=3, return_params=True)
    results = serve_all(server, reqs)
    offline = offline_adapt(phi, adapter, reqs, slots=4, k_max=10)
    assert len(results) == len(reqs)
    assert_results_equal(results, offline)


def test_fp32_served_matches_scalar_finetune_online(phi):
    """Served adaptation == the paper's scalar finetune_online on the
    request's first k samples, to vmap-lowering tolerance (1e-6)."""
    adapter = Fp32Adapter(loss_fn=LOSS, lr=0.01)
    reqs = make_requests(6, support=10, query=16, ks=(10, 4, 7, 1, 9, 10))
    server = AdaptationServer(phi, adapter, slots=3, k_max=10,
                              steps_per_tick=4, return_params=True)
    results = serve_all(server, reqs)
    for i, r in enumerate(reqs):
        ref, _ = finetune_online(LOSS, phi,
                                 jnp.asarray(r["sx"][:r["k"]]),
                                 jnp.asarray(r["sy"][:r["k"]]),
                                 jnp.float32(0.01))
        for leaf in ref:
            np.testing.assert_allclose(
                results[i].params[leaf], np.asarray(ref[leaf]),
                rtol=1e-6, atol=1e-6,
                err_msg=f"request {i}: params[{leaf}]")


def test_single_trace_across_refills(phi):
    """One jit trace covers admission, ragged advancing, retirement,
    refills, a reset, AND a second full stream."""
    adapter = Fp32Adapter(loss_fn=LOSS, lr=0.01)
    server = AdaptationServer(phi, adapter, slots=4, k_max=8,
                              steps_per_tick=2)
    reqs = make_requests(24, support=8, query=8,
                         ks=(8, 1, 5, 3, 7, 2, 8, 4))
    out1 = serve_all(server, reqs)
    assert len(out1) == 24
    assert server.trace_count == 1
    server.reset()
    out2 = serve_all(server, reqs)
    assert len(out2) == 24
    assert server.trace_count == 1


def test_ckpt_loaded_phi_serves_identically(phi, tmp_path):
    """phi restored via checkpoint.load_params (both a bare params
    snapshot and a run_federated round-state checkpoint) serves
    bit-for-bit like the in-memory tree — and the phi swap reuses the
    jit trace."""
    adapter = Fp32Adapter(loss_fn=LOSS, lr=0.01)
    reqs = make_requests(6, support=8, query=8, ks=(8, 3, 5, 1, 7, 8))

    # bare params snapshot
    save_checkpoint(str(tmp_path / "bare"), phi, step=0)
    loaded = load_params(str(tmp_path / "bare"), phi)
    # round-state checkpoint from a real (tiny) training run
    out = run_federated(
        phi, SineTasks(), TinyReptileStrategy(LOSS, use_pallas=False),
        rounds=4, clients_per_round=2, support=8, seed=0,
        ckpt_dir=str(tmp_path / "round"), ckpt_every=2, ckpt_async=False)
    trained = load_params(str(tmp_path / "round"), phi)
    for leaf in phi:
        np.testing.assert_array_equal(loaded[leaf], np.asarray(phi[leaf]))
        np.testing.assert_array_equal(trained[leaf],
                                      np.asarray(out["params"][leaf]))

    server = AdaptationServer(phi, adapter, slots=3, k_max=8,
                              steps_per_tick=3, return_params=True)
    mem = sorted(serve_all(server, reqs).values(), key=lambda r: r.rid)
    server.set_params(loaded)
    via_ckpt = sorted(serve_all(server, reqs).values(),
                      key=lambda r: r.rid)
    assert server.trace_count == 1          # phi swap reuses the trace
    for res, ck in zip(mem, via_ckpt):
        assert ck.query_loss == res.query_loss
        for leaf in res.params:
            np.testing.assert_array_equal(ck.params[leaf],
                                          res.params[leaf])


# -- int8 (TIFeD) route -----------------------------------------------------

def test_tifed_served_matches_offline_bitwise(phi):
    phi_q = tifed_requantize(phi)
    adapter = TifedAdapter(support=8, k_max=6, use_pallas=False)
    reqs = make_requests(10, support=8, query=16,
                         ks=(2, 6, 4, 1, 3, 6, 5, 2, 6, 1), seed=1)
    server = AdaptationServer(phi_q, adapter, slots=4, k_max=6,
                              steps_per_tick=2, return_params=True)
    results = serve_all(server, reqs)
    offline = offline_adapt(phi_q, adapter, reqs, slots=4, k_max=6)
    assert server.trace_count == 1
    assert_results_equal(results, offline)


def test_tifed_served_matches_scalar_engine_epochs(phi):
    """Served int8 params == the engine's scalar TifedStrategy epochs
    EXACTLY (integer arithmetic is batching-invariant); the fp32 query
    eval on those identical params matches to vmap tolerance."""
    phi_q = tifed_requantize(phi)
    adapter = TifedAdapter(support=8, k_max=6, use_pallas=False)
    strat = TifedStrategy(loss_fn=relu_mlp_loss, epochs=6,
                          use_pallas=False)
    reqs = make_requests(6, support=8, query=16, ks=(6, 2, 4, 1, 5, 3),
                         seed=2)
    server = AdaptationServer(phi_q, adapter, slots=3, k_max=6,
                              steps_per_tick=2, return_params=True)
    results = serve_all(server, reqs)
    for i, r in enumerate(reqs):
        out, _ = strat._run_epochs(
            phi_q, {"x": jnp.asarray(r["sx"]), "y": jnp.asarray(r["sy"])},
            jnp.int32(r["k"]))
        ref = tifed_dequantize(jax.tree.map(np.asarray, out))
        for leaf in ref:
            np.testing.assert_array_equal(
                results[i].params[leaf], np.asarray(ref[leaf]),
                err_msg=f"request {i}: params[{leaf}]")
        ql = float(relu_mlp_loss(jax.tree.map(jnp.asarray, ref),
                                 {"x": jnp.asarray(r["qx"]),
                                  "y": jnp.asarray(r["qy"])}))
        np.testing.assert_allclose(results[i].query_loss, ql,
                                   rtol=1e-6, atol=1e-6)


def test_tifed_no_cross_slot_leakage(phi):
    """A request served alone in a width-B server equals the same
    request served inside a full ragged batch, EXACTLY — padded-slot
    masks cannot leak across requests on the integer route."""
    phi_q = tifed_requantize(phi)
    adapter = TifedAdapter(support=8, k_max=6, use_pallas=False)
    reqs = make_requests(8, support=8, query=16,
                         ks=(4, 6, 1, 3, 6, 2, 5, 4), seed=3)
    probe = reqs[0]
    together = AdaptationServer(phi_q, adapter, slots=4, k_max=6,
                                steps_per_tick=2, return_params=True)
    got = serve_all(together, reqs)[0]
    alone = AdaptationServer(phi_q, adapter, slots=4, k_max=6,
                             steps_per_tick=2, return_params=True)
    solo = serve_all(alone, [probe])[0]
    assert solo.query_loss == got.query_loss
    for leaf in solo.params:
        np.testing.assert_array_equal(solo.params[leaf], got.params[leaf])


# -- the tick's packed transfers -------------------------------------------

def test_tick_makes_no_implicit_host_to_device_transfer(phi):
    """A warmed tick hands the jitted program no host array: its refill
    goes to the device by one explicit `jax.device_put`."""
    adapter = Fp32Adapter(loss_fn=LOSS, lr=0.01)
    server = AdaptationServer(phi, adapter, slots=4, k_max=5,
                              steps_per_tick=2)
    reqs = make_requests(10, support=6, query=4, ks=(5, 1, 3, 2, 4))
    serve_all(server, reqs[:4])                 # compiles the tick
    for r in reqs[4:]:
        server.submit(r["sx"], r["sy"], r["qx"], r["qy"], r["k"])
    with jax.transfer_guard_host_to_device("disallow"):
        results = server.drain()
    assert sorted(res.rid for res in results) == list(range(4, 10))
    assert server.trace_count == 1


def _planted(adapter):
    """``adapter`` with a request's query loss set to its ``qy[0, 0]``,
    so a test can plant any float32 bit pattern there."""
    class Planted(type(adapter)):
        def query_loss(self, pack, slot, qx, qy):
            return qy[0, 0]
    return Planted(**{f.name: getattr(adapter, f.name)
                      for f in dataclasses.fields(adapter)})


@pytest.mark.parametrize("route", ["fp32", "int8"])
def test_packed_readback_keeps_query_loss_bits(phi, route):
    """NaNs (a payload and a sign included), infinities and -0.0 come
    back bit for bit through the packed int32 result, and the step
    counts with them, equal to the offline reference's."""
    if route == "fp32":
        served_phi = phi
        adapter = _planted(Fp32Adapter(loss_fn=LOSS, lr=0.01))
    else:
        served_phi = tifed_requantize(phi)
        adapter = _planted(TifedAdapter(support=8, k_max=6,
                                        use_pallas=False))
    bits = np.array([0x7FC00000, 0x7FC01234, 0xFFC00001,     # NaNs
                     0x7F800000, 0xFF800000,                 # +inf, -inf
                     0x80000000, 0x00000000,                 # -0.0, 0.0
                     0x3FC00000, 0xC0100000],                # 1.5, -2.25
                    np.uint32).view(np.int32)
    planted = bits.view(np.float32)
    reqs = make_requests(len(bits), support=8, query=4,
                         ks=(6, 1, 3, 5, 2, 4), seed=4)
    for r, v in zip(reqs, planted):
        r["qy"][0, 0] = v
    server = AdaptationServer(served_phi, adapter, slots=4, k_max=6,
                              steps_per_tick=2)
    results = serve_all(server, reqs)
    offline = offline_adapt(served_phi, adapter, reqs, slots=4, k_max=6)
    for i, r in enumerate(reqs):
        got = np.float32(results[i].query_loss).view(np.int32)
        want = np.float32(offline[i]["query_loss"]).view(np.int32)
        assert got == want == bits[i], (i, hex(got), hex(want))
        assert results[i].steps == offline[i]["steps"] == r["k"]


# -- request validation -----------------------------------------------------

def test_submit_validation(phi):
    adapter = Fp32Adapter(loss_fn=LOSS, lr=0.01)
    server = AdaptationServer(phi, adapter, slots=2, k_max=5,
                              steps_per_tick=2)
    r = make_requests(1, support=5, query=4, ks=(5,))[0]
    with pytest.raises(ValueError, match="outside"):
        server.submit(r["sx"], r["sy"], r["qx"], r["qy"], k=6)
    with pytest.raises(ValueError, match="outside"):
        server.submit(r["sx"], r["sy"], r["qx"], r["qy"], k=0)
    server.submit(r["sx"], r["sy"], r["qx"], r["qy"], k=5)
    server.drain()
    bad = make_requests(1, support=7, query=4, ks=(5,))[0]
    with pytest.raises(ValueError, match="shape"):
        server.submit(bad["sx"], bad["sy"], bad["qx"], bad["qy"], k=5)
    with pytest.raises(RuntimeError, match="in flight"):
        server.submit(r["sx"], r["sy"], r["qx"], r["qy"], k=5)
        server.set_params(phi)


def test_constructor_validation(phi):
    adapter = Fp32Adapter(loss_fn=LOSS, lr=0.01)
    with pytest.raises(ValueError, match="slots"):
        AdaptationServer(phi, adapter, slots=0, k_max=5)
    with pytest.raises(ValueError, match="k_max"):
        AdaptationServer(phi, adapter, slots=2, k_max=0)
    with pytest.raises(ValueError, match="steps_per_tick"):
        AdaptationServer(phi, adapter, slots=2, k_max=5, steps_per_tick=0)
