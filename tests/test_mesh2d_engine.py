"""2-D (clients x model) mesh execution of the round engine (PR 10).

Runs in SUBPROCESSES with forced host devices (the test_mesh_engine.py
pattern) so the topology never leaks into the rest of the suite. The
2-D route is GSPMD-only: the GLOBAL block bodies compile against the
mesh with phi committed to the run's ModelPartitioner NamedShardings
(weight matrices split on the model axis, norms/biases replicated) and
the schedule/batch rows sharded over "clients" — no manual shard_map.
Covers the tentpole contracts:

- seeded parity of a small-transformer federated run across mesh=None
  vs a 1-D client mesh vs a 2x2 (clients, model) mesh — training
  trajectory, eval history, and the exact integer transport bills —
  at ONE jit trace per config across uneven eval blocks;
- the memory win the 2-D mesh exists for: analytic per-device
  parameter bytes of model-sharded phi <= 0.6x the replicated 1-D
  layout (the BENCHMARKS.md floor);
- composition with the sine workload, pooled identity state, partial
  participation, and FedBuff buffered aggregation (flat pool-state
  layout under GSPMD);
- the mamba2 ssd_scan Pallas kernel on the client-update hot path
  INSIDE a federated 2-D round (REPRO_OPT_SSD_PALLAS routes the
  prefetcher-thread trace; interpret mode on CPU), with parity
  against the oracle einsum route;
- validation: int8 strategies and an explicit Pallas server update
  (use_pallas=True) rejected on model-sharded meshes,
  partitioner= rejected without a 2-D mesh, and partitioner identity
  as part of the runner-cache key.
"""
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_PRELUDE = """
import dataclasses, functools
import jax, numpy as np
from repro.configs import get_arch
from repro.configs.paper_models import SINE_MLP
from repro.core import (BufferedAggregation, ClientPool, CommChannel,
                        PartialParticipation, clear_runner_cache,
                        client_mesh, run_federated, runner_cache_stats)
from repro.core.engine import _block_runner
from repro.core.strategies import (ReptileStrategy, TifedStrategy,
                                   TinyReptileStrategy)
from repro.data import LmTaskDistribution, SineTasks, lm_loss
from repro.models import build_model
from repro.models.paper_nets import (init_paper_model, paper_model_loss,
                                     relu_mlp_loss)
from repro.runtime.sharding import (DEFAULT_PARTITIONER, client_model_mesh,
                                    partitioner_for, per_device_param_bytes)

LOSS = functools.partial(paper_model_loss, SINE_MLP)
EVAL = dict(num_tasks=2, support=4, k_steps=2, lr=0.02, query=8)
params = init_paper_model(SINE_MLP, jax.random.PRNGKey(0))
dist = SineTasks()

def assert_close(a, b, tol=3e-4):
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                   rtol=tol, atol=tol)

def tiny_lm(family):
    base = {"transformer": "tinyllama-1.1b",
            "mamba2": "mamba2-130m"}[family]
    cfg = get_arch(base).reduced()
    small = dict(name="tiny-" + family, vocab_size=128, d_model=64)
    if family == "transformer":
        small.update(d_ff=128, num_heads=2, num_kv_heads=2, head_dim=32)
    else:
        small.update(ssm_state=16, ssm_chunk=8)
    return dataclasses.replace(cfg, **small)
"""


def _run(code: str, devices: int = 8, extra_env=None):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["JAX_PLATFORMS"] = "cpu"
    if extra_env:
        env.update(extra_env)
    r = subprocess.run([sys.executable, "-c", _PRELUDE + code],
                       capture_output=True, text=True, env=env, cwd=REPO,
                       timeout=500)
    assert r.returncode == 0, r.stderr[-3000:]
    return r.stdout


def test_mesh2d_transformer_parity_and_memory():
    """The headline run: a small transformer meta-trained over
    heterogeneous LM clients agrees seeded across mesh=None, a 1-D
    client mesh, and a 2x2 (clients, model) mesh — params, eval
    history, exact bills — traces ONCE per config, and the 2-D layout
    carries <= 0.6x the per-device parameter bytes of the replicated
    1-D run."""
    out = _run("""
cfg = tiny_lm("transformer")
model = build_model(cfg)
lm = LmTaskDistribution(cfg.vocab_size, 16)
phi = model.init(jax.random.PRNGKey(1))
S = ReptileStrategy(lm_loss(model), epochs=2, use_pallas=None)
LM_EVAL = dict(num_tasks=2, support=4, k_steps=2, lr=0.01, query=4)
kw = dict(rounds=5, beta=0.02, support=3, seed=3, eval_every=2,
          eval_kwargs=LM_EVAL, clients_per_round=3)   # uneven: pads to 4
mesh2d = client_model_mesh(2, 2)
clear_runner_cache()
flat = run_federated(phi, lm, S, **kw)
one_d = run_federated(phi, lm, S, mesh=client_mesh(4), **kw)
two_d = run_federated(phi, lm, S, mesh=mesh2d, **kw)
for other in (one_d, two_d):
    assert_close(flat["params"], other["params"], tol=1e-3)
    assert len(flat["history"]) == len(other["history"])
    for fe, se in zip(flat["history"], other["history"]):
        np.testing.assert_allclose(fe["query_loss"], se["query_loss"],
                                   rtol=1e-3, atol=1e-4)
    assert flat["comm_bytes"] == other["comm_bytes"]
    assert flat["per_client_bytes"] == other["per_client_bytes"]
runner = _block_runner(S, 0.02, CommChannel(), scheduled=True,
                       mesh=mesh2d, masked=False,
                       partitioner=DEFAULT_PARTITIONER)
assert runner.trace_count == 1, runner.trace_count

# the memory contract the 2-D mesh exists for (the BENCHMARKS floor):
# phi's weight matrices split over the model axis, so each device
# holds well under the replicated footprint
two_bytes = per_device_param_bytes(jax.device_put(
    phi, DEFAULT_PARTITIONER.shardings(phi, mesh2d)))
one_bytes = per_device_param_bytes(jax.device_put(phi, jax.devices()[0]))
assert two_bytes <= 0.6 * one_bytes, (two_bytes, one_bytes)
print("transformer 2d parity ok", two_bytes / one_bytes)
""", devices=4)
    assert "transformer 2d parity ok" in out


def test_mesh2d_sine_pooled_composition():
    """The 2-D route composes with the engine's fleet plugins exactly
    like a flat run: pooled identity state, partial participation, and
    FedBuff buffered aggregation all agree with mesh=None — including
    integer pool counters and per-client bills."""
    out = _run("""
S = TinyReptileStrategy(LOSS, use_pallas=None)
mesh2d = client_model_mesh(2, 2)
kw = dict(rounds=11, beta=0.02, support=4, seed=6, eval_every=4,
          eval_kwargs=EVAL, clients_per_round=3)
for case_kw in (dict(),
                dict(sampling=PartialParticipation(0.5)),
                dict(buffered=BufferedAggregation(4))):
    pooled = bool(case_kw)
    pool = lambda: ClientPool(dist, 7) if pooled else None
    flat = run_federated(params, dist, S, pool=pool(), **case_kw, **kw)
    shrd = run_federated(params, dist, S, pool=pool(), mesh=mesh2d,
                         **case_kw, **kw)
    assert_close(flat["params"], shrd["params"])
    assert flat["per_client_bytes"] == shrd["per_client_bytes"]
    assert flat["comm_bytes"] == shrd["comm_bytes"]
    if pooled:
        for k in ("last_seen", "staleness", "checkins"):
            np.testing.assert_array_equal(flat["pool_state"][k],
                                          shrd["pool_state"][k])
    if "buffered" in case_kw:
        assert (flat["pool_state"]["flushes"]
                == shrd["pool_state"]["flushes"])
        assert (flat["pool_state"]["buffered_pending"]
                == shrd["pool_state"]["buffered_pending"])
print("2d pooled composition ok")
""", devices=4)
    assert "2d pooled composition ok" in out


def test_mesh2d_mamba2_ssd_pallas_route():
    """The Pallas hot path inside a federated 2-D round: with
    REPRO_OPT_SSD_PALLAS set (env, not feature_scope — the block traces
    on the prefetcher thread) a mamba2 client update routes through
    kernels.ssd_scan, and the run agrees with the oracle einsum route
    traced before the flag flipped."""
    out = _run("""
import os
import repro.kernels.ssd_scan as ssd_mod
calls = {"n": 0}
orig = ssd_mod.ssd_scan
def counting(*a, **k):
    calls["n"] += 1
    return orig(*a, **k)
ssd_mod.ssd_scan = counting

cfg = tiny_lm("mamba2")
model = build_model(cfg)
lm = LmTaskDistribution(cfg.vocab_size, 16)
phi = model.init(jax.random.PRNGKey(2))
S = ReptileStrategy(lm_loss(model), epochs=2, use_pallas=None)
kw = dict(rounds=3, beta=0.02, support=2, seed=4, clients_per_round=2)
oracle = run_federated(phi, lm, S, **kw)
assert calls["n"] == 0                       # flag off: einsum oracle
os.environ["REPRO_OPT_SSD_PALLAS"] = "1"
# the inner finetune jit caches its jaxpr by shape — drop it so the
# 2-D trace re-reads the feature flag and takes the kernel route
jax.clear_caches()
clear_runner_cache()
shrd = run_federated(phi, lm, S, mesh=client_model_mesh(2, 2), **kw)
assert calls["n"] > 0, calls                 # kernel traced on hot path
assert_close(oracle["params"], shrd["params"], tol=2e-3)
print("mamba2 pallas 2d route ok", calls["n"])
""", devices=4)
    assert "mamba2 pallas 2d route ok" in out


def test_mesh2d_validation_and_cache_identity():
    """Guard rails: int8 uplink strategies cannot run with model-sharded
    phi (per-tensor quantization grids need whole tensors), a
    partitioner without a 2-D mesh is rejected, client_model_mesh
    validates its device budget, and the partitioner is part of the
    runner-cache identity (renamed rules can never be served a stale
    trace)."""
    out = _run("""
import dataclasses as dc
mesh2d = client_model_mesh(2, 2)
kw = dict(rounds=2, beta=0.02, support=4, seed=1, clients_per_round=2)
try:
    run_federated(params, dist, TifedStrategy(relu_mlp_loss, epochs=2),
                  channel=CommChannel("int8", quantize=False),
                  mesh=mesh2d, **dict(kw, beta=0.0))
    raise SystemExit("int8 on model-sharded mesh accepted")
except ValueError as e:
    assert "int8" in str(e)
try:
    run_federated(params, dist, TinyReptileStrategy(LOSS, use_pallas=None),
                  partitioner=DEFAULT_PARTITIONER, **kw)
    raise SystemExit("partitioner without 2-D mesh accepted")
except ValueError as e:
    assert "partitioner" in str(e)
try:
    client_model_mesh(64, 64)
    raise SystemExit("oversized mesh accepted")
except ValueError:
    pass
# GSPMD cannot partition a Mosaic kernel: the 2-D route refuses an
# explicit Pallas server update (None resolves to XLA there)
try:
    run_federated(params, dist, TinyReptileStrategy(LOSS, use_pallas=True),
                  mesh=mesh2d, **kw)
    raise SystemExit("Pallas server update on a model-sharded mesh accepted")
except ValueError as e:
    assert "model-sharded" in str(e)

S = TinyReptileStrategy(LOSS, use_pallas=None)
clear_runner_cache()
r_default = _block_runner(S, 0.05, CommChannel(), scheduled=True,
                          mesh=mesh2d, masked=False,
                          partitioner=DEFAULT_PARTITIONER)
r_renamed = _block_runner(S, 0.05, CommChannel(), scheduled=True,
                          mesh=mesh2d, masked=False,
                          partitioner=dc.replace(DEFAULT_PARTITIONER,
                                                 name="other"))
assert r_default is not r_renamed          # identity keyed by name
assert _block_runner(S, 0.05, CommChannel(), scheduled=True,
                     mesh=mesh2d, masked=False,
                     partitioner=DEFAULT_PARTITIONER) is r_default
assert runner_cache_stats()["mesh_entries"] == 2
print("2d validation ok")
""", devices=4)
    assert "2d validation ok" in out
