"""Engine speedup tracking: rounds/sec for (1) the pre-refactor per-client
Python loops, (2) the PR-1 synchronous engine (prefetch=0, reference
per-task sampling), and (3) the pipelined engine (vectorized block
sampling + double-buffered background prefetch), on the paper's sine
task. Acceptance floors: engine >= 3x the Python loops (PR 1) and
pipelined >= 1.5x the synchronous engine (PR 2) for batched-client
Reptile (clients_per_round=8) on CPU.

A "heterogeneity" section (PR 3) benchmarks the ClientSchedule layer on
the same batched-Reptile cohort: full participation vs 50% partial
participation vs a straggler cohort — rounds/sec plus the transport
bill (total and per-client min/max), showing that scenario plugins ride
the fixed-shape scan at full speed while partial participation halves
the bytes.

A "pool_async" section (PR 4) benchmarks persistent client identities:
the same cohort seated from a 32-client ClientPool — uniform seating
(floor: >= 0.9x the anonymous-cohort legacy path), diurnal-availability
check-ins, and FedBuff buffered aggregation (flush every 16 arrivals)
— with the block runner's trace counters recorded to pin the
one-jit-trace-per-config contract.

A "ckpt_overhead" section (PR 7) times the preemption-safety layer:
the pipelined cohort on the wide fleet-simulation MLP (support 128)
with the async round-state snapshotter armed at --ckpt-every 10 vs the
same run without a checkpoint directory, plus the fixed per-snapshot
cost in ms. Floor: < 5% rounds/sec cost (the writer thread keeps
device->host transfer and npz serialization off the scan's critical
path).

An "int8_training" section (PR 6) benchmarks TIFeD integer-only local
training (tifed_train: int8 DFA client epochs, native int8 uplinks,
quantization-aware aggregation) against the fp32 batched-Reptile
baseline at the SAME cohort/model/support/epochs. Floors: tifed
pipelined rounds/sec >= 1.5x fp32 reptile pipelined, uplink bytes at
the int8 rate (0.25x the fp32 bill), trace_count 1.

A "pool_scale" section (PR 8) sweeps the persistent-fleet size N in
{256, 10^4, 10^6} at a fixed cohort of 256 (vectorized counter-derived
identity, host-resident slabs): rounds/sec per N plus a live
host-memory meter (repro.metering.memory.MemoryMeter) and the size of
the pool's compact host snapshot. Floor: the N=10^6 run stays within
1.2x of the N=256 run's rounds/sec — per-round host work is O(cohort),
and the only O(N) residual is the int32 identity (16 bytes/client:
check-in counter + 3 slab fields).

A "mesh_scaling" section (PR 5) sweeps cohort size x device count for
the client-sharded engine (run_federated(mesh=...)) on a wider sine
MLP with a longer support stream, demonstrated on CPU CI under
XLA_FLAGS=--xla_force_host_platform_device_count=8 (bench() spawns the
forced-device subprocess itself when the parent is single-device).
Floors: >= 2x rounds/sec at cohort 64 on 8 host devices vs 1 device,
>= 1.5x at cohort 32 on 4, trace_count 1 for every sharded config.

An "lm_mesh" section (PR 10) benchmarks federated meta-learning over a
LARGE client model: the reduced transformer on heterogeneous LM-domain
clients (cohort 8), 1-D client mesh (phi replicated) vs the 2-D
(clients x model) mesh (phi's weight matrices split over the model
axis per its ModelPartitioner, GSPMD-scheduled collectives) —
rounds/sec plus the analytic per-device parameter bytes of each
layout. Floor: 2-D phi bytes <= 0.6x the replicated 1-D layout
(armed under --smoke; the mesh2d CI job runs --lm-mesh-only --smoke
on 4 forced host devices).

A "serving" section (PR 9) benchmarks the continuous-batching
`serving.AdaptationServer` on the meta-learned sine-MLP init: sustained
client-adaptation requests/sec plus p50/p95/p99 submit->retire latency
for the fp32 online-SGD route and the int8 TIFeD route, each under a
uniform-k and an adversarial ragged-k stream. Floors: >= 500 req/s at
k=10 for fp32 on CPU smoke, exactly 1 jit trace per server config.

Every section runs under a per-section wall-clock budget in --smoke
mode (`_SectionBudget`): a section that overruns raises loudly with its
elapsed time instead of silently eating the CI job's timeout, and each
section's seconds land in the payload as ``section_seconds``.

Writes BENCH_engine.json next to the repo root (same spirit as the
results/dryrun JSON cells consumed by benchmarks/report.py) so the
speedup is tracked across future PRs.

  PYTHONPATH=src python -m benchmarks.engine_bench            # full run
  PYTHONPATH=src python -m benchmarks.engine_bench --json     # JSON out
  PYTHONPATH=src python -m benchmarks.engine_bench --rounds 8 --smoke
                       # tier-1-budget smoke: pipeline on/off +
                       # heterogeneity only (no legacy Python loops)
"""
from __future__ import annotations

import argparse
import dataclasses
import functools
import json
import os
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.paper_models import SINE_MLP
from repro.core import (BufferedAggregation, ClientPool, CommChannel,
                        DiurnalAvailability, PartialParticipation,
                        StragglerSampling, UniformSampling, client_mesh,
                        reptile_train, tifed_train, tinyreptile_train)
from repro.core.engine import _block_runner
from repro.core.meta import finetune_batch, finetune_online, tree_lerp
from repro.core.strategies import (ReptileStrategy, TifedStrategy,
                                   TinyReptileStrategy)
from repro.data import SineTasks
from repro.models.paper_nets import (init_paper_model, paper_model_loss,
                                     relu_mlp_loss)
from repro.runtime.compile_cache import enable_compile_cache

LOSS = functools.partial(paper_model_loss, SINE_MLP)
ROUNDS = 120
SUPPORT = 32
OUT_PATH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "BENCH_engine.json")

# -- mesh-scaling workload (PR 5) -------------------------------------------
# The sharded client axis is demonstrated on a WIDER sine MLP (96x96
# hidden, ~9.6k params) with a longer support stream: a vmapped cohort
# carries every client's inner-loop parameter state across every scan
# step (cohort x params x fp32 — ~1.2 MB at cohort 32, ~2.5 MB at 64),
# which falls out of a single CPU device's cache, while each mesh
# shard's slice stays cache-resident — exactly the fleet-simulation
# regime sharding the client axis targets. The paper-faithful 32x32
# net stays the workload for every other section.
MESH_MLP = dataclasses.replace(SINE_MLP, name="sine_mlp_wide",
                               hidden=(96, 96))
MESH_LOSS = functools.partial(paper_model_loss, MESH_MLP)
MESH_SUPPORT = 128
MESH_DEVICES = (1, 4, 8)
MESH_COHORTS = (32, 64)


# -- pre-refactor loops (one host->device dispatch per client per round) ----

def _python_loop_tinyreptile(params, dist, rounds):
    rng = np.random.default_rng(0)
    phi = params
    for rnd in range(rounds):
        alpha_t = 1.0 * (1 - rnd / rounds)
        task = dist.sample_task(rng)
        xs, ys = zip(*task.support_stream(rng, SUPPORT))
        phi_hat, _ = finetune_online(LOSS, phi, jnp.stack(xs), jnp.stack(ys),
                                     jnp.float32(0.02))
        phi = tree_lerp(phi, phi_hat, alpha_t)
    return jax.block_until_ready(jax.tree.leaves(phi)[0])


def _python_loop_reptile(params, dist, rounds, clients, epochs=8):
    rng = np.random.default_rng(0)
    phi = params
    for rnd in range(rounds):
        alpha_t = 1.0 * (1 - rnd / rounds)
        deltas = None
        for _ in range(clients):
            task = dist.sample_task(rng)
            sup = task.support_batch(rng, SUPPORT)
            phi_hat, _ = finetune_batch(LOSS, phi, sup, epochs,
                                        jnp.float32(0.02))
            d = jax.tree.map(lambda q, p: q - p, phi_hat, phi)
            deltas = d if deltas is None else jax.tree.map(
                lambda a, b: a + b, deltas, d)
        phi = jax.tree.map(lambda p, d: p + alpha_t * d / clients,
                           phi, deltas)
    return jax.block_until_ready(jax.tree.leaves(phi)[0])


class _SectionBudget:
    """Per-section wall-clock guard for --smoke runs. ``check(name)``
    closes the section that just ran, records its elapsed seconds, and
    (when armed) raises RuntimeError past the budget — so a section
    that regresses from seconds to minutes fails the CI smoke loudly
    with a name and a number instead of burning the job's 45-minute
    timeout. Full runs record seconds but never raise (the canonical
    120-round numbers are allowed to be slow)."""

    def __init__(self, enabled: bool, per_section_s: float = 300.0):
        self.enabled = enabled
        self.limit = per_section_s
        self.seconds = {}
        self._t0 = time.perf_counter()

    def check(self, name: str) -> None:
        now = time.perf_counter()
        dt = now - self._t0
        self._t0 = now
        self.seconds[name] = round(dt, 2)
        if self.enabled and dt > self.limit:
            raise RuntimeError(
                f"--smoke section {name!r} took {dt:.1f}s, over its "
                f"{self.limit:.0f}s budget — smoke sections must stay "
                f"CI-cheap; profile the regression or move the workload "
                f"to the full bench")


def _rounds_per_sec(fn, rounds, reps: int = 3, warm: bool = True):
    """Warmup once (compile + caches; skipped when the caller already
    ran ``fn`` for its output), then best of ``reps`` timed runs (the
    timeit convention: min elapsed suppresses host load jitter — one
    120-round pass is a fraction of a second, far too short for a
    single sample to be a stable ratio on a shared machine)."""
    if warm:
        fn()                              # warmup: compile + caches
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    return rounds / best


def mesh_scaling(rounds: int = ROUNDS, smoke: bool = False):
    """The mesh_scaling section: rounds/sec for cohort size x device
    count, sharding the client axis over the devices THIS process has
    (run under XLA_FLAGS=--xla_force_host_platform_device_count=8 on
    CPU; ``bench`` spawns that subprocess automatically when a CPU parent
    has a single device). devices=1 is the legacy mesh=None engine —
    the strongest single-device baseline. Acceptance floors (see
    docs/BENCHMARKS.md): >= 2x rounds/sec at cohort 64 on 8 host
    devices vs 1, >= 1.5x at cohort 32 on 4, every sharded config at
    trace_count 1.

    Returns (rows, section).
    """
    ndev = len(jax.devices())
    if ndev < 2:
        raise RuntimeError(
            "mesh_scaling needs multiple devices; run under "
            "XLA_FLAGS=--xla_force_host_platform_device_count=8")
    if smoke:
        devices = tuple(dict.fromkeys((1, min(4, ndev))))
        cohorts = (32,)
    else:
        devices = tuple(d for d in MESH_DEVICES if d <= ndev)
        if len(devices) < 2:
            # a 2-3-device host: none of the canonical sharded device
            # counts fit, but the host's own width still demonstrates
            # the sweep (better than silently recording baselines only)
            devices = (1, ndev)
        cohorts = MESH_COHORTS
    params = init_paper_model(MESH_MLP, jax.random.PRNGKey(0))
    dist = SineTasks()
    # 16-round scan blocks: long enough that per-block dispatch +
    # collective warm-up amortizes on every device count, short enough
    # that prefetch still overlaps host sampling
    pipe = dict(prefetch=2, max_block=16)
    section = {"devices_available": ndev, "model": MESH_MLP.name,
               "support": MESH_SUPPORT, "devices": list(devices),
               "cohorts": list(cohorts)}
    rows = []
    for ci, cohort in enumerate(cohorts):
        # a distinct beta per cohort keeps every (cohort, device) pair on
        # its OWN cached runner, so trace_count == 1 really pins one jit
        # trace per config (cohort size changes the block shape)
        beta = 0.02 + 1e-4 * ci
        for d in devices:
            mesh = None if d == 1 else client_mesh(d)

            def run(mesh=mesh, cohort=cohort, beta=beta):
                out = tinyreptile_train(
                    MESH_LOSS, params, dist, rounds=rounds, alpha=1.0,
                    beta=beta, support=MESH_SUPPORT, seed=0,
                    clients_per_round=cohort, sampler="vectorized",
                    mesh=mesh, **pipe)
                jax.block_until_ready(jax.tree.leaves(out["params"])[0])
            rps = _rounds_per_sec(run, rounds)
            row = {"rounds_per_sec": round(rps, 2)}
            if mesh is not None:
                runner = _block_runner(
                    TinyReptileStrategy(MESH_LOSS, use_pallas=None),
                    beta, CommChannel(), scheduled=True, mesh=mesh,
                    masked=False)
                row["trace_count"] = runner.trace_count
            section[f"c{cohort}_d{d}"] = row
            rows.append((f"engine/mesh_c{cohort}_d{d}", 1e6 / rps,
                         f"rounds_per_sec={rps:.1f}"))
    for cohort in cohorts:
        base = section[f"c{cohort}_d1"]["rounds_per_sec"]
        for d in devices[1:]:
            section[f"c{cohort}_d{d}"]["speedup_vs_1dev"] = round(
                section[f"c{cohort}_d{d}"]["rounds_per_sec"] / base, 2)
    return rows, section


def _forced_device_child(flag: str, rounds: int, devices: int):
    """Run one multi-device section (``--mesh-only`` / ``--lm-mesh-only``)
    in a child process with ``devices`` forced host devices (the device
    count is fixed at backend init, so the parent cannot grow its own)
    and return the section dict. CPU parents only: a chip belongs to the
    process that holds it, so off the CPU a child could never reach it.
    A failed child raises, failing the whole run."""
    if jax.default_backend() != "cpu":
        raise RuntimeError(f"{flag} child processes are for CPU parents; "
                           f"on {jax.default_backend()} run the section "
                           f"in-process")
    env = dict(os.environ)
    flags = [f for f in env.get("XLA_FLAGS", "").split()
             if "host_platform_device_count" not in f]
    env["XLA_FLAGS"] = " ".join(
        flags + [f"--xla_force_host_platform_device_count={devices}"])
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run(
        [sys.executable, "-m", "benchmarks.engine_bench", flag,
         "--rounds", str(rounds)],
        capture_output=True, text=True, env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    if r.returncode != 0:
        raise RuntimeError(f"{flag} child failed:\n{r.stderr[-2000:]}")
    # the section object is the last thing printed, from its brace on
    return json.loads(r.stdout[r.stdout.index("{"):])


def lm_mesh_bench(rounds: int = ROUNDS, smoke: bool = False):
    """The lm_mesh section (PR 10): federated meta-learning over a
    LARGE client model — a reduced transformer whose clients are
    heterogeneous LM domains (LmTaskDistribution) — comparing the 1-D
    client mesh (phi fully replicated on every device) against the 2-D
    (clients, model) mesh (phi's weight matrices split over the model
    axis per the transformer ModelPartitioner, GSPMD route). Records
    rounds/sec for both layouts, the live host-memory meter, and the
    ANALYTIC per-device parameter bytes of each placed phi
    (leaf.sharding.shard_shape — device memory meters read 0 on forced
    host devices). Acceptance floor (docs/BENCHMARKS.md): 2-D
    per-device parameter bytes <= 0.6x the replicated 1-D layout —
    enforced here under --smoke (the mesh2d CI job's contract).

    Needs >= 4 devices for the 2x2 mesh; on CPU run under
    XLA_FLAGS=--xla_force_host_platform_device_count=4.

    Returns (rows, section).
    """
    from jax.sharding import NamedSharding, PartitionSpec
    from repro.configs import get_arch
    from repro.core import run_federated
    from repro.data import LmTaskDistribution, lm_loss
    from repro.metering.memory import MemoryMeter
    from repro.runtime.sharding import (DEFAULT_PARTITIONER,
                                        client_model_mesh,
                                        per_device_param_bytes)

    ndev = len(jax.devices())
    if ndev < 4:
        raise RuntimeError(
            "lm_mesh needs >= 4 devices (a 2x2 clients x model mesh); "
            "run under XLA_FLAGS=--xla_force_host_platform_device_count=4")
    from repro.models import build_model
    cfg = dataclasses.replace(
        get_arch("tinyllama-1.1b").reduced(), name="tinyllama-bench",
        vocab_size=256, d_model=128, d_ff=256, num_heads=4,
        num_kv_heads=4, head_dim=32)
    model = build_model(cfg)
    lm_dist = LmTaskDistribution(cfg.vocab_size, 32)
    phi = model.init(jax.random.PRNGKey(0))
    strategy = ReptileStrategy(lm_loss(model), epochs=2, use_pallas=None)
    lm_rounds = 6 if smoke else min(rounds, 24)
    param_count = sum(int(np.prod(l.shape)) for l in jax.tree.leaves(phi))
    section = {"model": cfg.name, "param_count": param_count,
               "cohort": 8, "seq": 32, "rounds": lm_rounds}
    cases = (("1d_clients4", client_mesh(4)),
             ("2d_clients2_model2", client_model_mesh(2, 2)))
    rows, phi_bytes = [], {}
    for name, mesh in cases:
        model_sharded = "model" in mesh.axis_names
        meter = MemoryMeter()

        def run(mesh=mesh):
            out = run_federated(
                phi, lm_dist, strategy, rounds=lm_rounds,
                clients_per_round=8, alpha=1.0, beta=0.02, support=4,
                seed=0, mesh=mesh, prefetch=2,
                max_block=max(1, lm_rounds // 2))
            jax.block_until_ready(jax.tree.leaves(out["params"])[0])
        rps = _rounds_per_sec(run, lm_rounds, reps=2 if smoke else 3)
        mem = meter.report()
        placed = jax.device_put(
            phi, DEFAULT_PARTITIONER.shardings(phi, mesh) if model_sharded
            else NamedSharding(mesh, PartitionSpec()))
        phi_bytes[name] = per_device_param_bytes(placed)
        section[name] = {
            "rounds_per_sec": round(rps, 2),
            "per_device_param_bytes": phi_bytes[name],
            "host_peak_growth_mb": round(
                mem["host_peak_growth_bytes"] / 2 ** 20, 1),
        }
        rows.append((f"engine/lm_mesh_{name}", 1e6 / rps,
                     f"rounds_per_sec={rps:.2f} "
                     f"per_device_param_bytes={phi_bytes[name]}"))
    ratio = phi_bytes["2d_clients2_model2"] / phi_bytes["1d_clients4"]
    section["param_bytes_2d_over_1d"] = round(ratio, 3)
    if smoke and ratio > 0.6:
        raise RuntimeError(
            f"lm_mesh floor violated: 2-D per-device parameter bytes "
            f"must be <= 0.6x the replicated 1-D layout, got "
            f"{ratio:.3f} ({phi_bytes})")
    return rows, section


def serving_bench(smoke: bool = False):
    """The serving section: sustained requests/sec + p50/p95/p99
    latency for the continuous-batching AdaptationServer, fp32 and int8
    routes, each under a uniform-k stream (every request asks the full
    budget — the paper's k=10 deployment fine-tune) and an adversarial
    ragged-k stream (k cycles pseudo-randomly over [1, k_max], the
    regime continuous batching exists for). Acceptance floors (see
    docs/SERVING.md): fp32 uniform k=10 >= 500 req/s on CPU smoke;
    exactly 1 jit trace per server across warmup + the timed stream.

    Returns (rows, section).
    """
    from repro.core.strategies import tifed_requantize
    from repro.metering import MetricsTracker
    from repro.serving import AdaptationServer, Fp32Adapter, TifedAdapter

    SLOTS, SPT = 64, 5
    phi32 = init_paper_model(SINE_MLP, jax.random.PRNGKey(0))
    configs = [
        ("fp32", Fp32Adapter(loss_fn=LOSS), phi32,
         dict(support=10, query=20, k_max=10,
              requests=512 if smoke else 4096)),
        ("tifed", TifedAdapter(support=8, k_max=6),
         tifed_requantize(phi32),
         dict(support=8, query=20, k_max=6,
              requests=256 if smoke else 2048)),
    ]
    section = {"slots": SLOTS, "steps_per_tick": SPT,
               "model": SINE_MLP.name}
    rows = []
    for name, adapter, phi, cfg in configs:
        rng = np.random.default_rng(0)

        def make_reqs(n, k_fn, cfg=cfg, rng=rng):
            reqs = []
            for i in range(n):
                a = rng.uniform(0.1, 5.0)
                b = rng.uniform(0.0, np.pi)
                sx = rng.uniform(-5, 5,
                                 (cfg["support"], 1)).astype(np.float32)
                qx = rng.uniform(-5, 5,
                                 (cfg["query"], 1)).astype(np.float32)
                reqs.append((sx, np.float32(a * np.sin(sx + b)), qx,
                             np.float32(a * np.sin(qx + b)), k_fn(i)))
            return reqs

        strat_sec = {k: cfg[k] for k in ("support", "query", "k_max",
                                         "requests")}
        for wname, k_fn in (
                ("uniform_k_max", lambda i, c=cfg: c["k_max"]),
                ("ragged", lambda i, c=cfg: 1 + (i * 7919) % c["k_max"])):
            server = AdaptationServer(phi, adapter, slots=SLOTS,
                                      k_max=cfg["k_max"],
                                      steps_per_tick=SPT)
            reqs = make_reqs(cfg["requests"], k_fn)
            for r in reqs[:SLOTS]:        # warm the (single) jit trace
                server.submit(*r)
            server.drain()
            server.reset()
            tracker = MetricsTracker()    # timed-stream latencies only
            server.metrics = tracker
            t0 = time.perf_counter()
            for r in reqs:
                server.submit(*r)
            done = server.drain()
            dt = time.perf_counter() - t0
            rps = len(done) / dt
            pct = tracker.percentiles("serve.latency_ms")
            strat_sec[wname] = {
                "req_per_s": round(rps, 1),
                "p50_ms": round(pct["p50"], 3),
                "p95_ms": round(pct["p95"], 3),
                "p99_ms": round(pct["p99"], 3),
                "ticks": server.ticks,
                "trace_count": server.trace_count,
            }
            rows.append((f"engine/serving_{name}_{wname}", 1e6 / rps,
                         f"req_per_s={rps:.1f} p99_ms={pct['p99']:.2f}"))
            if server.trace_count != 1:
                raise RuntimeError(
                    f"serving {name}/{wname}: {server.trace_count} jit "
                    f"traces across warmup + refills (contract: exactly "
                    f"1 per (adapter, slots, shapes) config)")
            if smoke and name == "fp32" and wname == "uniform_k_max" \
                    and rps < 500:
                raise RuntimeError(
                    f"serving smoke floor: fp32 k=10 sustained only "
                    f"{rps:.0f} req/s < 500 (slots={SLOTS}, "
                    f"steps_per_tick={SPT})")
        section[name] = strat_sec
    return rows, section


def bench(rounds: int = ROUNDS, smoke: bool = False):
    """Returns (rows, payload). ``smoke`` skips the slow legacy Python
    loops and only compares pipeline on vs off (tier-1 time budget)."""
    params = init_paper_model(SINE_MLP, jax.random.PRNGKey(0))
    dist = SineTasks()
    results = {}
    budget = _SectionBudget(enabled=smoke)

    # engine kwargs: PR-1 synchronous baseline vs the pipelined fast path.
    # The pipelined config caps blocks so the run splits into >= 4 blocks
    # and the prefetch thread actually overlaps host sampling of block N+1
    # with device compute on block N (one monolithic block would
    # degenerate to inline staging with nothing to overlap) — also at
    # smoke round counts.
    sync = dict(prefetch=0, sampler="reference")
    piped = dict(prefetch=2, sampler="vectorized",
                 max_block=min(16, max(1, rounds // 4)))

    cases = [
        ("tinyreptile",
         lambda: _python_loop_tinyreptile(params, dist, rounds),
         lambda kw: tinyreptile_train(LOSS, params, dist, rounds=rounds,
                                      alpha=1.0, beta=0.02, support=SUPPORT,
                                      seed=0, **kw)),
        ("reptile_batched_c8",
         lambda: _python_loop_reptile(params, dist, rounds, clients=8),
         lambda kw: reptile_train(LOSS, params, dist, rounds=rounds,
                                  alpha=1.0, beta=0.02, support=SUPPORT,
                                  epochs=8, clients_per_round=8, seed=0,
                                  **kw)),
    ]
    def synced(engine_fn, kw):
        # the engine returns as soon as the last block is dispatched;
        # block on the result so device compute is inside the timing
        out = engine_fn(kw)
        return jax.block_until_ready(jax.tree.leaves(out["params"])[0])

    rows = []
    for name, legacy_fn, engine_fn in cases:
        sync_rps = _rounds_per_sec(lambda: synced(engine_fn, sync), rounds)
        piped_rps = _rounds_per_sec(lambda: synced(engine_fn, piped), rounds)
        pipeline_speedup = piped_rps / sync_rps
        res = {"engine_sync_rounds_per_sec": round(sync_rps, 2),
               "engine_pipelined_rounds_per_sec": round(piped_rps, 2),
               "pipeline_speedup": round(pipeline_speedup, 2)}
        if not smoke:
            legacy_rps = _rounds_per_sec(legacy_fn, rounds)
            res["python_loop_rounds_per_sec"] = round(legacy_rps, 2)
            res["engine_speedup"] = round(sync_rps / legacy_rps, 2)
            res["pipelined_vs_python_loop"] = round(piped_rps / legacy_rps, 2)
            rows.append((f"engine/{name}_python_loop", 1e6 / legacy_rps,
                         f"rounds_per_sec={legacy_rps:.1f}"))
        results[name] = res
        rows.append((f"engine/{name}_engine_sync", 1e6 / sync_rps,
                     f"rounds_per_sec={sync_rps:.1f}"))
        rows.append((f"engine/{name}_engine_pipelined", 1e6 / piped_rps,
                     f"rounds_per_sec={piped_rps:.1f} "
                     f"pipeline_speedup={pipeline_speedup:.2f}x"))
    budget.check("pipeline")

    # -- int8 training: TIFeD integer DFA vs the fp32 reptile baseline --
    # Same cohort (8), model (SINE_MLP shapes), support, and epoch count
    # as reptile_batched_c8 — the matched-workload ratio the PR-6
    # acceptance floor (>= 1.5x) is judged on. The bytes ratio pins the
    # native int8 uplink bill against the analytic fp32 bill for the
    # same traffic (2 * C * rounds * fp32 payload): exactly 0.25.
    int8_ch = CommChannel("int8", quantize=False)

    def tifed_fn(kw):
        return tifed_train(params, dist, rounds=rounds, alpha=1.0,
                           support=SUPPORT, epochs=8, clients_per_round=8,
                           seed=0, channel=int8_ch, **kw)
    # sync and piped use different block shapes, so each config traces
    # once on the shared cached runner; pin the piped config's count as
    # a delta (1 = retrace-free across its repeated timed runs)
    runner = _block_runner(TifedStrategy(relu_mlp_loss, epochs=8), 0.0,
                           int8_ch, scheduled=False)
    t_sync = _rounds_per_sec(lambda: synced(tifed_fn, sync), rounds)
    traces_before = runner.trace_count
    t_piped = _rounds_per_sec(lambda: synced(tifed_fn, piped), rounds)
    out = tifed_fn(piped)
    fp32_rps = results["reptile_batched_c8"]["engine_pipelined_rounds_per_sec"]
    fp32_bytes = 2 * 8 * rounds * CommChannel().payload_bytes(params)
    results["int8_training"] = {
        "engine_sync_rounds_per_sec": round(t_sync, 2),
        "engine_pipelined_rounds_per_sec": round(t_piped, 2),
        "pipeline_speedup": round(t_piped / t_sync, 2),
        "vs_fp32_reptile": round(t_piped / fp32_rps, 2),
        "comm_bytes": out["comm_bytes"],
        "bytes_vs_fp32": round(out["comm_bytes"] / fp32_bytes, 3),
        "trace_count": runner.trace_count - traces_before,
    }
    rows.append(("engine/int8_tifed_pipelined", 1e6 / t_piped,
                 f"rounds_per_sec={t_piped:.1f} "
                 f"vs_fp32_reptile={t_piped / fp32_rps:.2f}x "
                 f"bytes_vs_fp32={out['comm_bytes'] / fp32_bytes:.3f}"))
    budget.check("int8_training")

    # -- heterogeneity: the ClientSchedule layer on the batched cohort --
    cohorts = [
        ("full_participation", UniformSampling("vectorized")),
        ("partial_participation_50", PartialParticipation(
            0.5, sampler="vectorized")),
        ("straggler_cohort_25", StragglerSampling(
            0.25, sampler="vectorized")),
    ]
    het = {}
    # the policies carry their own sampler; pass only the pipeline knobs
    # (run_federated rejects a non-default sampler= next to sampling=)
    pipe_kw = {k: piped[k] for k in ("prefetch", "max_block")}
    for name, policy in cohorts:
        def run_policy(policy=policy):
            out = reptile_train(LOSS, params, dist, rounds=rounds,
                                alpha=1.0, beta=0.02, support=SUPPORT,
                                epochs=8, clients_per_round=8, seed=0,
                                sampling=policy, **pipe_kw)
            jax.block_until_ready(jax.tree.leaves(out["params"])[0])
            return out
        out = run_policy()            # doubles as warmup + accounting
        rps = _rounds_per_sec(run_policy, rounds, warm=False)
        het[name] = {
            "rounds_per_sec": round(rps, 2),
            "comm_bytes": out["comm_bytes"],
            "per_client_bytes_min": min(out["per_client_bytes"]),
            "per_client_bytes_max": max(out["per_client_bytes"]),
        }
        rows.append((f"engine/heterogeneity_{name}", 1e6 / rps,
                     f"rounds_per_sec={rps:.1f} "
                     f"comm_bytes={out['comm_bytes']}"))
    full_rps = het["full_participation"]["rounds_per_sec"]
    for name in ("partial_participation_50", "straggler_cohort_25"):
        het[name]["vs_full_participation"] = round(
            het[name]["rounds_per_sec"] / full_rps, 2)
        het[name]["bytes_vs_full"] = round(
            het[name]["comm_bytes"]
            / het["full_participation"]["comm_bytes"], 3)
    results["heterogeneity"] = het
    budget.check("heterogeneity")

    # -- pool / async: persistent identities over a 32-client pool ------
    # Floor: pooled uniform seating >= 0.9x the legacy anonymous-cohort
    # path at the SAME host sampling style (per-task "reference" draws —
    # the pool samples each check-in from that client's private stream).
    POOL_N = 32
    fedbuff = BufferedAggregation(16)
    pool_cases = [
        ("legacy_uniform", dict(sampling=UniformSampling("reference")),
         None),
        ("pooled_uniform", dict(), None),
        ("pooled_diurnal", dict(sampling=DiurnalAvailability(period=24)),
         None),
        ("pooled_fedbuff_k16", dict(buffered=fedbuff), fedbuff),
    ]
    pool_sec = {}
    for name, case_kw, buffered in pool_cases:
        pooled_case = name != "legacy_uniform"

        def run_case(case_kw=case_kw, pooled_case=pooled_case):
            kw = dict(case_kw)
            if pooled_case:
                kw["pool"] = ClientPool(dist, POOL_N, seed=0)
            out = reptile_train(LOSS, params, dist, rounds=rounds,
                                alpha=1.0, beta=0.02, support=SUPPORT,
                                epochs=8, clients_per_round=8, seed=0,
                                **pipe_kw, **kw)
            jax.block_until_ready(jax.tree.leaves(out["params"])[0])
            return out
        out = run_case()              # doubles as warmup + pool state
        rps = _rounds_per_sec(run_case, rounds, warm=False)
        row = {"rounds_per_sec": round(rps, 2),
               "comm_bytes": out["comm_bytes"]}
        if pooled_case:
            ps = out["pool_state"]
            row["checkins_min"] = int(ps["checkins"].min())
            row["checkins_max"] = int(ps["checkins"].max())
            row["staleness_max"] = int(ps["staleness"].max())
            if buffered is not None:
                row["flushes"] = ps["flushes"]
            masked = name == "pooled_diurnal"    # availability process
            runner = _block_runner(ReptileStrategy(LOSS, epochs=8), 0.02,
                                   CommChannel(), scheduled=True,
                                   pooled=True, buffered=buffered,
                                   masked=masked)
            row["trace_count"] = runner.trace_count   # 1 = retrace-free
        pool_sec[name] = row
        rows.append((f"engine/pool_{name}", 1e6 / rps,
                     f"rounds_per_sec={rps:.1f} "
                     f"comm_bytes={out['comm_bytes']}"))
    for name in ("pooled_uniform", "pooled_diurnal", "pooled_fedbuff_k16"):
        pool_sec[name]["vs_legacy_uniform"] = round(
            pool_sec[name]["rounds_per_sec"]
            / pool_sec["legacy_uniform"]["rounds_per_sec"], 2)
    results["pool_async"] = pool_sec
    budget.check("pool_async")

    # -- pool_scale: the fleet-size sweep (PR 8) ------------------------
    # Fixed cohort (256), fleet size N in {256, 1e4, 1e6}: with the
    # counter-derived identity and host-resident slabs, per-round host
    # work is O(cohort), so rounds/sec must be flat in N (floor: 1e6
    # within 1.2x of 256). TinyReptile keeps the device step light so
    # host-side scaling regressions cannot hide behind client compute.
    from repro.core import run_federated as _rf
    from repro.metering.memory import MemoryMeter
    scale_rounds = 8 if smoke else min(rounds, 24)
    scale_sec = {"cohort": 256, "rounds": scale_rounds}
    scale_rps = {}
    for n in (256, 10_000, 1_000_000):
        pool = ClientPool(dist, n, seed=0, sampler="vectorized",
                          residency="host")
        meter = MemoryMeter()

        def run_scale(pool=pool):
            out = _rf(params, dist,
                      TinyReptileStrategy(LOSS, use_pallas=None),
                      rounds=scale_rounds, clients_per_round=256,
                      alpha=1.0, beta=0.02, support=8, seed=0,
                      **pipe_kw, pool=pool)
            jax.block_until_ready(jax.tree.leaves(out["params"])[0])

        rps = _rounds_per_sec(run_scale, scale_rounds,
                              reps=2 if smoke else 3)
        mem = meter.report()
        snap = pool.host_state()
        scale_rps[n] = rps
        scale_sec[f"n_{n}"] = {
            "rounds_per_sec": round(rps, 2),
            # the analytic O(N) residual: per-client int32 identity
            "identity_int32_mb": round(16 * n / 2 ** 20, 2),
            # measured growth since this size's baseline (upper bound:
            # ru_maxrss is a process-lifetime high-water mark)
            "host_current_growth_mb": round(
                mem["host_current_growth_bytes"] / 2 ** 20, 1),
            "host_peak_growth_mb": round(
                mem["host_peak_growth_bytes"] / 2 ** 20, 1),
            "snapshot_entries": len(snap["checkins"]),
        }
        rows.append((f"engine/pool_scale_n{n}", 1e6 / rps,
                     f"rounds_per_sec={rps:.1f}"))
    scale_sec["n256_over_n1000000"] = round(
        scale_rps[256] / scale_rps[1_000_000], 3)
    results["pool_scale"] = scale_sec
    budget.check("pool_scale")

    # -- checkpoint overhead: async round-state snapshots (PR 7) --------
    # The preemption-safety tentpole must be ~free on the round engine's
    # hot path: the consumer dispatches one fused device-side copy of
    # the carry and hands it to the background writer thread (D2H
    # transfer + in-memory npz + atomic writes off the critical path).
    # Judged on the WIDE fleet-simulation workload (the mesh_scaling
    # MLP, support 128) — the long-run regime checkpointing exists for,
    # where 10 rounds of compute amortize the ~2ms fixed per-snapshot
    # cost (also recorded, as snapshot_cost_ms, so the fixed cost stays
    # visible instead of hidden behind the ratio). Floor (see
    # docs/BENCHMARKS.md): < 5% rounds/sec cost at --ckpt-every 10.
    # Paired interleaved timing: base/ckpt alternate within one loop so
    # host-load drift hits both sides equally.
    import tempfile as _tempfile
    from repro.core import run_federated as _run_federated
    from repro.core.strategies import ReptileStrategy as _Reptile
    ck_params = init_paper_model(MESH_MLP, jax.random.PRNGKey(0))

    def ckpt_case(ckpt_dir):
        kw = {} if ckpt_dir is None else dict(ckpt_dir=ckpt_dir,
                                              ckpt_every=10)
        out = _run_federated(
            ck_params, dist, _Reptile(MESH_LOSS, epochs=8, use_pallas=None),
            rounds=rounds, alpha=1.0, beta=0.02, support=MESH_SUPPORT,
            clients_per_round=8, seed=0, prefetch=2, max_block=16,
            sampling=UniformSampling("vectorized"), **kw)
        jax.block_until_ready(jax.tree.leaves(out["params"])[0])

    with _tempfile.TemporaryDirectory() as ckpt_d:
        ckpt_case(None)
        ckpt_case(ckpt_d)                 # warm both traces
        t_base, t_ck = float("inf"), float("inf")
        for _ in range(2 if smoke else 5):
            t0 = time.perf_counter()
            ckpt_case(None)
            t_base = min(t_base, time.perf_counter() - t0)
            t0 = time.perf_counter()
            ckpt_case(ckpt_d)
            t_ck = min(t_ck, time.perf_counter() - t0)
    base_rps, ck_rps = rounds / t_base, rounds / t_ck
    n_snaps = max(1, rounds // 10)
    overhead_pct = (t_ck / t_base - 1.0) * 100.0
    results["ckpt_overhead"] = {
        "workload": f"{MESH_MLP.name} c8 support{MESH_SUPPORT}",
        "no_ckpt_rounds_per_sec": round(base_rps, 2),
        "ckpt_every_10_rounds_per_sec": round(ck_rps, 2),
        "overhead_pct": round(overhead_pct, 2),
        "snapshot_cost_ms": round((t_ck - t_base) / n_snaps * 1000, 3),
    }
    rows.append(("engine/ckpt_every_10_pipelined", 1e6 / ck_rps,
                 f"rounds_per_sec={ck_rps:.1f} "
                 f"overhead_pct={overhead_pct:.2f}"))
    budget.check("ckpt_overhead")

    # -- mesh scaling: shard the client axis over the devices ------------
    # Multi-device parents and accelerator hosts sweep in-process (on an
    # accelerator a section without enough devices fails the run); a
    # single-device CPU full run spawns the forced 8-device child; a
    # single-device CPU SMOKE run skips the section (tier-1 time budget —
    # the dedicated multi-device CI job covers it).
    on_cpu = jax.default_backend() == "cpu"
    if len(jax.devices()) > 1 or not on_cpu:
        mesh_rows, results["mesh_scaling"] = mesh_scaling(rounds, smoke)
        rows.extend(mesh_rows)
    elif not smoke:
        results["mesh_scaling"] = _forced_device_child("--mesh-only",
                                                       rounds, 8)
    budget.check("mesh_scaling")

    # -- lm_mesh: the 2-D (clients x model) mesh on a transformer (PR 10) --
    # same rule with >= 4 devices (the mesh2d CI job forces 4 on CPU and
    # runs --lm-mesh-only --smoke, which arms the 0.6x bytes floor).
    if len(jax.devices()) >= 4 or not on_cpu:
        lm_rows, results["lm_mesh"] = lm_mesh_bench(rounds, smoke)
        rows.extend(lm_rows)
    elif not smoke:
        results["lm_mesh"] = _forced_device_child("--lm-mesh-only",
                                                  rounds, 4)
    budget.check("lm_mesh")

    # -- serving: the continuous-batching adaptation server (PR 9) ------
    serve_rows, results["serving"] = serving_bench(smoke)
    rows.extend(serve_rows)
    budget.check("serving")

    payload = {"bench": "engine", "status": "OK", "backend":
               jax.default_backend(), "rounds": rounds, "support": SUPPORT,
               "smoke": smoke, "section_seconds": budget.seconds,
               "results": results}
    return rows, payload


def run():
    """benchmarks.run contract: full bench, write BENCH_engine.json,
    return the CSV rows."""
    rows, payload = bench()
    with open(OUT_PATH, "w") as f:
        json.dump(payload, f, indent=2)
        f.write("\n")
    return rows


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--rounds", type=int, default=ROUNDS)
    ap.add_argument("--json", action="store_true",
                    help="print the result payload as JSON on stdout")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny pipeline-on/off check: skips the legacy "
                         "Python-loop baselines and does not overwrite "
                         "BENCH_engine.json")
    ap.add_argument("--mesh-only", action="store_true",
                    help="run ONLY the mesh_scaling sweep and print its "
                         "section as JSON (the multi-device subprocess "
                         "bench() spawns; needs forced host devices)")
    ap.add_argument("--serving-only", action="store_true",
                    help="run ONLY the serving section and print it as "
                         "JSON (the serving CI job's fast path; --smoke "
                         "arms the >= 500 req/s fp32 floor)")
    ap.add_argument("--lm-mesh-only", action="store_true",
                    help="run ONLY the lm_mesh section (2-D clients x "
                         "model mesh on the reduced transformer) and "
                         "print it as JSON; needs >= 4 devices — the "
                         "mesh2d CI job's fast path, where --smoke arms "
                         "the 0.6x per-device parameter bytes floor")
    args = ap.parse_args()
    enable_compile_cache()

    if args.mesh_only:
        _, section = mesh_scaling(rounds=args.rounds)
        print(json.dumps(section, indent=2))
        return
    if args.lm_mesh_only:
        _, section = lm_mesh_bench(rounds=args.rounds, smoke=args.smoke)
        print(json.dumps(section, indent=2))
        return
    if args.serving_only:
        _, section = serving_bench(smoke=args.smoke)
        print(json.dumps(section, indent=2))
        return

    rows, payload = bench(rounds=args.rounds, smoke=args.smoke)
    # only the canonical config may update the tracked record — a quick
    # --rounds 8 iteration must not clobber the 120-round numbers the
    # acceptance thresholds are judged against
    if not args.smoke and args.rounds == ROUNDS:
        with open(OUT_PATH, "w") as f:
            json.dump(payload, f, indent=2)
            f.write("\n")
    if args.json:
        print(json.dumps(payload, indent=2))
    else:
        from benchmarks.common import emit
        emit(rows)


if __name__ == "__main__":
    main()
