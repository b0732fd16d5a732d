"""Smoke test of the main path on one TPU chip (or the meshes on four).

Drives the system through the entry points a user calls, with random
seeded weights, and checks what comes out:

- training: ``run_federated`` with TinyReptile and Reptile on the paper's
  Omniglot conv (112,709 params) and sine MLP at a 32-client cohort over
  a few scan blocks, checked against the random init's eval, the same
  run on the plain XLA server update, and one jit trace per runner;
- TIFeD int8 training through the compiled ``dfa_epoch_int8`` kernel,
  equal to the oracle route and billed at 0.25x the fp32 bytes;
- serving: ``AdaptationServer`` with the fp32 and int8 adapters over a
  ragged request stream at 64 slots, against ``offline_adapt`` on the
  plain XLA route;
- kernels: each route's compiled program holds a ``tpu_custom_call``.

``--four-chips`` instead runs only the sharded engine: the Omniglot
cohort on a 4-device client mesh against ``mesh=None``, and the reduced
transformer on a 2x2 (clients, model) mesh against the 1-D mesh.

Every failed check raises, so the script exits non-zero; with no TPU it
exits non-zero before any phase. The last line of a passing run is one
JSON object: ``{"ok": true, "device": {...}}``.

    python chip_smoke.py               # one chip
    python chip_smoke.py --four-chips  # one host with four chips
"""
from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs.paper_models import OMNIGLOT_CONV, SINE_MLP  # noqa: E402
from repro.core import CommChannel, client_mesh, run_federated  # noqa: E402
from repro.core.engine import _block_runner, meta_interpolate  # noqa: E402
from repro.core.meta import evaluate_init  # noqa: E402
from repro.core.strategies import (ReptileStrategy, TifedStrategy,  # noqa: E402
                                   TinyReptileStrategy)
from repro.data import OmniglotTasks, SineTasks  # noqa: E402
from repro.models.paper_nets import (init_paper_model,  # noqa: E402
                                     paper_model_loss, param_count,
                                     relu_mlp_loss)
from repro.runtime.compile_cache import enable_compile_cache  # noqa: E402
from repro.serving import (AdaptationServer, Fp32Adapter,  # noqa: E402
                           TifedAdapter, offline_adapt)

COHORT = 32
SLOTS = 64


def say(phase, msg):
    print(f"[{phase}] {msg}", flush=True)


def check(ok, what):
    if not ok:
        raise AssertionError(what)


def assert_trees_close(a, b, rtol, atol, what):
    for (path, x), y in zip(jax.tree_util.tree_leaves_with_path(a),
                            jax.tree.leaves(b)):
        np.testing.assert_allclose(
            np.asarray(x), np.asarray(y), rtol=rtol, atol=atol,
            err_msg=f"{what}: {jax.tree_util.keystr(path)}")


def max_abs_diff(a, b):
    return max(float(np.max(np.abs(np.asarray(x, np.float64)
                                   - np.asarray(y, np.float64))))
               for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))


def device_check(four_chips):
    devs = jax.devices()
    dev = devs[0]
    info = {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devs)}
    say("device", f"platform={info['platform']} kind={info['kind']} "
                  f"count={info['count']}")
    if dev.platform != "tpu":
        print(f"chip_smoke needs a TPU; JAX found {dev.platform}",
              file=sys.stderr)
        sys.exit(2)
    need = 4 if four_chips else 1
    if len(devs) < need:
        print(f"chip_smoke{' --four-chips' if four_chips else ''} needs "
              f"{need} chips; JAX found {len(devs)}", file=sys.stderr)
        sys.exit(2)
    say("device", f"compile cache: {enable_compile_cache()}")
    return info


# -- training ----------------------------------------------------------------

def train_and_check(name, cfg, dist, strategies, *, rounds, max_block,
                    support, beta, eval_kwargs, seed=0):
    """Each (label, make_strategy) pair runs twice, on the default route
    (Pallas server update on TPU) and with use_pallas=False, from one
    seeded init; returns the default-route outputs by label."""
    loss = functools.partial(paper_model_loss, cfg)
    params = init_paper_model(cfg, jax.random.PRNGKey(seed))
    kw = dict(rounds=rounds, clients_per_round=COHORT, alpha=1.0, beta=beta,
              support=support, seed=seed, eval_every=rounds,
              eval_kwargs=eval_kwargs, max_block=max_block)
    base = evaluate_init(loss, params, dist,
                         np.random.default_rng(10_000 + rounds - 1),
                         **eval_kwargs)["query_loss"]
    outs = {}
    for label, make in strategies:
        t0 = time.perf_counter()
        strat = make(loss, None)
        out = run_federated(params, dist, strat, **kw)
        ref = run_federated(params, dist, make(loss, False), **kw)
        jax.block_until_ready(out["params"])
        q = out["history"][-1]["query_loss"]
        check(np.isfinite(q) and q < base,
              f"{name}/{label}: query loss {q} not below random init {base}")
        assert_trees_close(out["params"], ref["params"], 1e-4, 1e-5,
                           f"{name}/{label} Pallas vs XLA server update")
        traces = _block_runner(strat, beta, CommChannel()).trace_count
        check(traces == 1, f"{name}/{label}: trace_count {traces} != 1")
        say("train", f"{name}/{label}: {param_count(params)} params, cohort "
                     f"{COHORT}, support {support}, {rounds} rounds in "
                     f"blocks of {max_block}; query loss {q:.4f} < random "
                     f"init {base:.4f}; params vs use_pallas=False max|d| "
                     f"{max_abs_diff(out['params'], ref['params']):.3g}; "
                     f"trace_count 1; {time.perf_counter() - t0:.1f}s wall "
                     f"with compile - PASS")
        outs[label] = out
    return outs


def tinyreptile(loss, use_pallas):
    return TinyReptileStrategy(loss, use_pallas=use_pallas)


def reptile(loss, use_pallas):
    return ReptileStrategy(loss, epochs=8, use_pallas=use_pallas)


def train_omniglot():
    return train_and_check(
        "omniglot_conv", OMNIGLOT_CONV, OmniglotTasks(),
        [("tinyreptile", tinyreptile), ("reptile", reptile)],
        rounds=24, max_block=8, support=16, beta=0.01,
        eval_kwargs=dict(num_tasks=6, support=16, k_steps=8, lr=0.01,
                         query=32))


def train_sine():
    return train_and_check(
        "sine_mlp", SINE_MLP, SineTasks(),
        [("tinyreptile", tinyreptile), ("reptile", reptile)],
        rounds=48, max_block=16, support=32, beta=0.02,
        eval_kwargs=dict(num_tasks=5, support=10, k_steps=16, lr=0.02,
                         query=20))


def train_tifed(fp32_reptile):
    """--strategy tifed semantics: int8 DFA client epochs and native int8
    uplinks; the compiled kernel route must equal the oracle route."""
    t0 = time.perf_counter()
    params = init_paper_model(SINE_MLP, jax.random.PRNGKey(0))
    channel = CommChannel("int8", quantize=False)
    kw = dict(rounds=48, clients_per_round=COHORT, alpha=1.0, beta=0.02,
              support=32, seed=0, eval_every=48, max_block=16,
              eval_kwargs=dict(num_tasks=5, support=10, k_steps=16,
                               lr=0.005, query=20), channel=channel)
    strat = TifedStrategy(relu_mlp_loss, epochs=8)
    out = run_federated(params, SineTasks(), strat, **kw)
    ref = run_federated(params, SineTasks(),
                        TifedStrategy(relu_mlp_loss, epochs=8,
                                      use_pallas=False), **kw)
    for (path, x), y in zip(jax.tree_util.tree_leaves_with_path(
            out["params"]), jax.tree.leaves(ref["params"])):
        np.testing.assert_array_equal(
            np.asarray(x), np.asarray(y),
            err_msg=f"tifed kernel vs oracle: {jax.tree_util.keystr(path)}")
    q = out["history"][-1]["query_loss"]
    check(np.isfinite(q), f"tifed query loss {q} is not finite")
    ratio = out["comm_bytes"] / fp32_reptile["comm_bytes"]
    check(ratio == 0.25, f"tifed bytes are {ratio}x the fp32 run, not 0.25x")
    traces = _block_runner(strat, 0.02, channel).trace_count
    check(traces == 1, f"tifed trace_count {traces} != 1")
    say("tifed", f"sine_mlp int8: cohort {COHORT}, support 32, 48 rounds, 8 "
                 f"epochs; params == use_pallas=False exactly; comm "
                 f"{out['comm_bytes']} B = 0.25x fp32 "
                 f"{fp32_reptile['comm_bytes']} B; query loss {q:.4f}; "
                 f"trace_count 1; {time.perf_counter() - t0:.1f}s wall with "
                 f"compile - PASS")
    return out


# -- serving -----------------------------------------------------------------

def sine_requests(n, support, query, k_max, seed):
    rng = np.random.default_rng(seed)
    reqs = []
    for _ in range(n):
        a, b = rng.uniform(0.1, 5.0), rng.uniform(0.0, np.pi)
        sx = rng.uniform(-5, 5, (support, 1)).astype(np.float32)
        qx = rng.uniform(-5, 5, (query, 1)).astype(np.float32)
        reqs.append({"sx": sx, "sy": np.float32(a * np.sin(sx + b)),
                     "qx": qx, "qy": np.float32(a * np.sin(qx + b)),
                     "k": int(rng.integers(1, k_max + 1))})
    return reqs


def serve_and_check(name, phi, adapter, ref_adapter, *, support, k_max,
                    exact, n=320):
    t0 = time.perf_counter()
    reqs = sine_requests(n, support, 20, k_max, seed=7)
    server = AdaptationServer(phi, adapter, slots=SLOTS, k_max=k_max,
                              steps_per_tick=5, return_params=True)
    for r in reqs:
        server.submit(r["sx"], r["sy"], r["qx"], r["qy"], r["k"])
    served = sorted(server.drain(), key=lambda r: r.rid)
    want = offline_adapt(phi, ref_adapter, reqs, slots=SLOTS, k_max=k_max)
    check(len(served) == n, f"{name}: served {len(served)} of {n}")
    for got, w, r in zip(served, want, reqs):
        check(got.steps == w["steps"] == r["k"],
              f"{name} request {got.rid}: {got.steps} steps, want {r['k']}")
        if exact:
            for k in w["params"]:
                np.testing.assert_array_equal(
                    got.params[k], w["params"][k],
                    err_msg=f"{name} request {got.rid} {k}")
        else:
            assert_trees_close(got.params, w["params"], 1e-4, 1e-5,
                               f"{name} request {got.rid}")
        np.testing.assert_allclose(got.query_loss, w["query_loss"],
                                   rtol=1e-4, atol=1e-5)
        check(np.isfinite(got.query_loss), f"{name}: non-finite loss")
    check(server.trace_count == 1,
          f"{name}: trace_count {server.trace_count} != 1")
    say("serve", f"{name}: {n} ragged requests (k in 1..{k_max}, support "
                 f"{support}) at {SLOTS} slots in {server.ticks} ticks; "
                 f"params {'==' if exact else 'allclose to'} offline_adapt"
                 f"(use_pallas=False); trace_count 1; "
                 f"{time.perf_counter() - t0:.1f}s wall with compile - PASS")


def serve(phi_fp32, phi_tifed):
    loss = functools.partial(paper_model_loss, SINE_MLP)
    serve_and_check("fp32", phi_fp32, Fp32Adapter(loss),
                    Fp32Adapter(loss, use_pallas=False),
                    support=16, k_max=10, exact=False)
    serve_and_check("tifed", phi_tifed, TifedAdapter(support=16, k_max=6),
                    TifedAdapter(support=16, k_max=6, use_pallas=False),
                    support=16, k_max=6, exact=True)


# -- kernels -----------------------------------------------------------------

def dfa_oracle_parity():
    """The compiled int8 epoch against its oracle on random integer
    inputs: weights and biases exactly equal for both dims classes
    (dout == 1, and din, dout > 1, which splits the DFA delta) and for
    each trained layer; the fp32 loss to rtol 1e-6."""
    from repro.kernels import ops, ref
    f32 = jnp.float32
    scales = {"f0": f32(2.0 ** -7), "f1": f32(2.0 ** -7),
              "fe": f32(2.0 ** -9), "floss": f32(2.0 ** -9),
              "ftw": (f32(2.0 ** -8), f32(2.0 ** -9), f32(2.0 ** -10)),
              "ftb": (f32(2.0 ** -6), f32(2.0 ** -7), f32(2.0 ** -8))}
    for dims in ((1, 32, 32, 1), (5, 16, 12, 3)):
        din, h1, h2, dout = dims
        w_shapes = ((din, h1), (h1, h2), (h2, dout))
        for layer in range(3):
            rng = np.random.default_rng(layer + 10)

            def ints(lim, shape):
                return jnp.asarray(rng.integers(-lim, lim + 1, shape), f32)
            ws = tuple(ints(127, sh) for sh in w_shapes)
            bs = tuple(ints(2 ** 15, (h,)) for h in (h1, h2, dout))
            xq, yal = ints(127, (32, din)), ints(2 ** 15, (32, dout))
            fb = tuple(ints(127, (dout, h)) for h in (h1, h2))
            dither = tuple(jnp.asarray(rng.random(sh), f32)
                           for sh in w_shapes)
            args = (ws, bs, xq, yal, layer, fb, dither, scales)
            gw, gb, gl = ops.dfa_epoch_int8(*args)
            ww, wb, wl = ref.dfa_int8_epoch(*args)
            for i in range(3):
                np.testing.assert_array_equal(
                    np.asarray(gw[i], np.float32), np.asarray(ww[i]),
                    err_msg=f"dims {dims} layer {layer} w{i}")
                np.testing.assert_array_equal(
                    np.asarray(gb[i], np.float32), np.asarray(wb[i]),
                    err_msg=f"dims {dims} layer {layer} b{i}")
            np.testing.assert_allclose(float(gl), float(wl), rtol=1e-6)
        say("kernels", f"dfa_epoch_int8 dims {dims}, S=32, layers 0-2: "
                       f"weights and biases == ref.dfa_int8_epoch, loss "
                       f"to rtol 1e-6 - PASS")


def kernel_routes(phi_omni, phi_sine, phi_tifed):
    """Compile each route as the engine and server call it and look for
    the Mosaic kernel in the compiled program."""
    loss = functools.partial(paper_model_loss, SINE_MLP)
    cohort = jax.tree.map(lambda x: jnp.stack([x] * COHORT), phi_sine)
    batch = {"x": jnp.zeros((COHORT, 32, 1)), "y": jnp.zeros((COHORT, 32, 1))}
    tifed = TifedStrategy(relu_mlp_loss, epochs=8)
    fp32 = Fp32Adapter(loss)
    fp32_slots = jax.vmap(lambda sx, sy: fp32.prepare(phi_sine, sx, sy))(
        jnp.zeros((SLOTS, 16, 1)), jnp.zeros((SLOTS, 16, 1)))
    int8 = TifedAdapter(support=16, k_max=6)
    pack = int8.pack_phi(phi_tifed)
    int8_slots = jax.vmap(lambda sx, sy: int8.prepare(pack, sx, sy))(
        jnp.zeros((SLOTS, 16, 1)), jnp.zeros((SLOTS, 16, 1)))
    steps = jnp.zeros((SLOTS,), jnp.int32)
    routes = [
        ("meta_update", "omniglot server update",
         jax.jit(lambda a, b: meta_interpolate(a, b, jnp.float32(0.5))),
         (phi_omni, phi_omni)),
        ("meta_update", "sine cohort server update",
         jax.jit(lambda p, c: TinyReptileStrategy(loss).server_aggregate(
             p, c, jnp.float32(0.5), 0.02)), (phi_sine, cohort)),
        ("online_sgd", "fp32 serving unit step x64 slots",
         jax.jit(jax.vmap(lambda s, t: fp32.unit_step(phi_sine, s, t))),
         (fp32_slots, steps)),
        ("dfa_epoch_int8", "tifed client update x32 cohort",
         jax.jit(jax.vmap(lambda b: tifed.client_update(phi_sine, b, 0.0))),
         (batch,)),
        ("dfa_epoch_int8", "tifed serving unit step x64 slots",
         jax.jit(jax.vmap(lambda s, t: int8.unit_step(pack, s, t))),
         (int8_slots, steps)),
    ]
    for kernel, what, fn, args in routes:
        hlo = fn.lower(*args).compile().as_text()
        n = hlo.count("tpu_custom_call")
        say("kernels", f"{kernel} ({what}): tpu_custom_call x{n}"
                       f"{' - PASS' if n else ' - MISSING'}")
        check(n > 0, f"{kernel} ({what}) compiled without its kernel")


# -- four chips --------------------------------------------------------------

def print_placement(name, tree):
    for path, leaf in jax.tree_util.tree_leaves_with_path(tree):
        per_dev = ", ".join(f"d{s.device.id}:{tuple(s.data.shape)}"
                            for s in leaf.addressable_shards)
        say("placement", f"{name} {jax.tree_util.keystr(path)} "
                         f"{tuple(leaf.shape)} -> {per_dev}")


def four_chip_omniglot():
    t0 = time.perf_counter()
    loss = functools.partial(paper_model_loss, OMNIGLOT_CONV)
    params = init_paper_model(OMNIGLOT_CONV, jax.random.PRNGKey(0))
    strat = TinyReptileStrategy(loss)
    kw = dict(rounds=4, clients_per_round=COHORT, alpha=1.0, beta=0.01,
              support=16, seed=0, max_block=2)
    flat = run_federated(params, OmniglotTasks(), strat, **kw)
    mesh = client_mesh(4)
    sharded = run_federated(params, OmniglotTasks(), strat, mesh=mesh, **kw)
    assert_trees_close(flat["params"], sharded["params"], 1e-3, 2e-4,
                       "omniglot client_mesh(4) vs mesh=None")
    traces = _block_runner(strat, 0.01, CommChannel(), scheduled=True,
                           mesh=mesh, masked=False).trace_count
    check(traces == 1, f"omniglot mesh trace_count {traces} != 1")
    print_placement("omniglot 1-D", sharded["params"])
    say("mesh", f"omniglot_conv tinyreptile cohort {COHORT} on client_mesh(4)"
                f" vs mesh=None, 4 rounds, HIGHEST matmul precision: "
                f"params allclose, max|d| "
                f"{max_abs_diff(flat['params'], sharded['params']):.3g} "
                f"(training moved them by up to "
                f"{max_abs_diff(flat['params'], params):.3g}); "
                f"trace_count 1; {time.perf_counter() - t0:.1f}s - PASS")


def four_chip_transformer():
    """The --arch transformer engine run: the reduced tinyllama family
    on heterogeneous LM clients, 2x2 (clients, model) vs 1-D clients."""
    from repro.configs import get_arch
    from repro.data import LmTaskDistribution, lm_loss
    from repro.launch.train import ARCH_FAMILIES
    from repro.models import build_model
    from repro.runtime.sharding import client_model_mesh, partitioner_for

    t0 = time.perf_counter()
    cfg = get_arch(ARCH_FAMILIES["transformer"]).reduced()
    model = build_model(cfg)
    dist = LmTaskDistribution(cfg.vocab_size, 64)
    phi = model.init(jax.random.PRNGKey(0))
    strat = ReptileStrategy(lm_loss(model), epochs=8)
    kw = dict(rounds=4, clients_per_round=8, alpha=1.0, beta=0.02,
              support=8, seed=0, max_block=2)
    one_d = run_federated(phi, dist, strat, mesh=client_mesh(4), **kw)
    mesh2d = client_model_mesh(2, 2)
    part = partitioner_for("transformer")
    two_d = run_federated(phi, dist, strat, mesh=mesh2d, partitioner=part,
                          **kw)
    assert_trees_close(one_d["params"], two_d["params"], 1e-3, 1e-3,
                       "transformer 2x2 vs 1-D")
    check(one_d["comm_bytes"] == two_d["comm_bytes"],
          "transformer 2x2 and 1-D bills differ")
    traces = _block_runner(strat, 0.02, CommChannel(), scheduled=True,
                           mesh=mesh2d, masked=False,
                           partitioner=part).trace_count
    check(traces == 1, f"transformer 2-D trace_count {traces} != 1")
    print_placement("transformer 2x2", two_d["params"])
    say("mesh", f"{cfg.name} ({param_count(phi)} params) reptile cohort 8 on "
                f"client_model_mesh(2, 2) with partitioner_for('transformer')"
                f" vs client_mesh(4), HIGHEST matmul precision: params "
                f"allclose, max|d| "
                f"{max_abs_diff(one_d['params'], two_d['params']):.3g} "
                f"(training moved them by up to "
                f"{max_abs_diff(one_d['params'], phi):.3g}); "
                f"trace_count 1; {time.perf_counter() - t0:.1f}s - PASS")


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded-engine parity checks on "
                         "four chips")
    args = ap.parse_args()
    info = device_check(args.four_chips)
    if args.four_chips:
        # the mesh parity checks compare placements, not matmul passes:
        # at the TPU's default one-pass bf16 precision two programs that
        # batch the clients differently round differently, and training
        # amplifies that past any tolerance a placement check could use
        with jax.default_matmul_precision("highest"):
            four_chip_omniglot()
            four_chip_transformer()
    else:
        omni = train_omniglot()
        sine = train_sine()
        tifed = train_tifed(sine["reptile"])
        serve(sine["tinyreptile"]["params"], tifed["params"])
        dfa_oracle_parity()
        kernel_routes(omni["tinyreptile"]["params"],
                      sine["tinyreptile"]["params"], tifed["params"])
    print(json.dumps({"ok": True, "device": info}), flush=True)


if __name__ == "__main__":
    main()
