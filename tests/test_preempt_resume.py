"""Preemption-safe federated runs (PR 7): kill-and-resume BIT-FOR-BIT
parity against an uninterrupted seeded run.

The contract under test: run_federated(ckpt_dir=...) snapshots the full
scan carry (phi, PoolState incl. int8 FedBuff slabs, host RNG / sampling
chains, per-client transport bills, eval history) at block boundaries,
and resume=True restores it so an interrupted run finishes with EXACTLY
the params, history rows, pool identity state, and integer byte bills of
a run that was never killed.

Heavy cases run in SUBPROCESSES (the test_mesh_engine.py pattern) so
forced host-device topologies never leak into the rest of the suite;
the real-SIGKILL case additionally exercises the async writer dying at
an arbitrary execution point and falling back to the newest durable
snapshot.
"""
import functools
import json
import os
import shutil
import subprocess
import sys
import tempfile

import jax
import numpy as np
import pytest

from repro.configs.paper_models import SINE_MLP
from repro.core import UniformSampling, run_federated
from repro.core.strategies import TinyReptileStrategy
from repro.data import SineTasks
from repro.models.paper_nets import init_paper_model, paper_model_loss
from repro.testing import faults

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_PRELUDE = """
import functools, tempfile
import jax, numpy as np
from repro.configs.paper_models import SINE_MLP
from repro.core import (BufferedAggregation, ClientPool, CommChannel,
                        DiurnalAvailability, MarkovAvailability,
                        run_federated, client_mesh)
from repro.core.strategies import (FedAvgStrategy, FedSGDStrategy,
                                   ReptileStrategy, TifedStrategy,
                                   TinyReptileStrategy, TransferStrategy)
from repro.data import SineTasks
from repro.models.paper_nets import (init_paper_model, paper_model_loss,
                                     relu_mlp_loss)
from repro.testing import faults

LOSS = functools.partial(paper_model_loss, SINE_MLP)
EVAL = dict(num_tasks=2, support=4, k_steps=2, lr=0.02, query=8)
params = init_paper_model(SINE_MLP, jax.random.PRNGKey(0))
dist = SineTasks()

def assert_same(ref, res, tag):
    for a, b in zip(jax.tree.leaves(ref["params"]),
                    jax.tree.leaves(res["params"])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=tag)
    assert len(ref["history"]) == len(res["history"]), tag
    for ra, rb in zip(ref["history"], res["history"]):
        assert set(ra) == set(rb), tag
        for k in ra:
            assert float(ra[k]) == float(rb[k]), (tag, k, ra[k], rb[k])
    for k in ("comm_bytes", "per_client_bytes"):
        if k in ref:
            assert ref[k] == res[k], (tag, k)
    if "pool_state" in ref:
        for k in ref["pool_state"]:
            a = np.asarray(ref["pool_state"][k])
            b = np.asarray(res["pool_state"][k])
            assert a.dtype == b.dtype, (tag, k)
            np.testing.assert_array_equal(a, b, err_msg=f"{tag}:{k}")

def crash_resume(make_run, crash_round, tag):
    ref = make_run()
    d = tempfile.mkdtemp()
    ck = dict(ckpt_dir=d, ckpt_every=4)
    try:
        with faults.crash_at_round(crash_round):
            make_run(ckpt_async=False, **ck)
        raise SystemExit(f"{tag}: crash hook never fired")
    except faults.SimulatedPreemption:
        pass
    res = make_run(resume=True, **ck)
    assert_same(ref, res, tag)
"""


def _run(code: str, devices: int = 1, timeout: int = 560):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run([sys.executable, "-c", _PRELUDE + code],
                       capture_output=True, text=True, env=env, cwd=REPO,
                       timeout=timeout)
    assert r.returncode == 0, r.stderr[-3000:]
    return r.stdout


def test_crash_resume_parity_all_six_strategies():
    """Kill after the round-4 snapshot, resume, and land bit-for-bit on
    the uninterrupted run for every strategy — including tifed, whose
    carry holds int8 payloads and an int8 transport bill."""
    out = _run("""
cases = [
    ("tinyreptile", TinyReptileStrategy(LOSS, use_pallas=None), {}),
    ("reptile", ReptileStrategy(LOSS, epochs=2, use_pallas=None), {}),
    ("fedavg", FedAvgStrategy(LOSS, epochs=2), {}),
    ("fedsgd", FedSGDStrategy(LOSS), {}),
    ("transfer", TransferStrategy(LOSS), {}),
    ("tifed", TifedStrategy(relu_mlp_loss, epochs=8),
     dict(beta=0.0, support=16,
          eval_kwargs=dict(num_tasks=2, support=4, k_steps=2, lr=0.01,
                           query=8),
          channel=CommChannel("int8", quantize=False))),
]
for name, strategy, over in cases:
    kw = dict(rounds=8, beta=0.02, support=6, seed=5, clients_per_round=3,
              eval_every=4, eval_kwargs=EVAL)
    kw.update(over)
    def make_run(**extra):
        return run_federated(params, dist, strategy, **kw, **extra)
    crash_resume(make_run, 4, name)
    print("OK", name)
print("six-strategy crash/resume parity ok")
""")
    assert "six-strategy crash/resume parity ok" in out
    for name in ("tinyreptile", "reptile", "fedavg", "fedsgd", "transfer",
                 "tifed"):
        assert f"OK {name}" in out


def test_crash_resume_pool_buffered_availability():
    """Pooled scenarios: the snapshot must carry PoolState (identity
    arrays + FedBuff buffer slab + flush counters), the per-client data
    RNG streams, and the availability chain — Markov's sticky on/off
    state is host-side and would silently diverge if dropped."""
    out = _run("""
strategy = TinyReptileStrategy(LOSS, use_pallas=None)
scenarios = [
    ("pool-buffered-markov", lambda: dict(
        pool=ClientPool(dist, 16, seed=7),
        buffered=BufferedAggregation(buffer_size=3),
        sampling=MarkovAvailability())),
    ("pool-diurnal", lambda: dict(
        pool=ClientPool(dist, 12, seed=11),
        sampling=DiurnalAvailability(period=6))),
    ("pool-plain", lambda: dict(pool=ClientPool(dist, 10, seed=2))),
]
for name, mk in scenarios:
    kw = dict(rounds=12, beta=0.02, support=6, seed=5, clients_per_round=4,
              eval_every=4, eval_kwargs=EVAL)
    def make_run(**extra):
        # fresh pool/policy objects per run: host state must come from
        # the snapshot, never from leftover in-process objects
        return run_federated(params, dist, strategy, **kw, **mk(), **extra)
    crash_resume(make_run, 4, name)
    print("OK", name)
print("pool crash/resume parity ok")
""")
    assert "pool crash/resume parity ok" in out


def test_crash_resume_mesh4():
    """Resume on a 4-device client mesh: the sharded carry (phi
    replicated, pool arrays client-sharded) snapshots and restores to
    the same bits as the uninterrupted mesh run."""
    out = _run("""
strategy = TinyReptileStrategy(LOSS, use_pallas=None)
mesh = client_mesh(4)
kw = dict(rounds=8, beta=0.02, support=6, seed=3, clients_per_round=4,
          eval_every=4, eval_kwargs=EVAL, mesh=mesh,
          pool=None)
def make_run(**extra):
    return run_federated(params, dist, strategy,
                         pool=ClientPool(dist, 8, seed=9),
                         buffered=BufferedAggregation(buffer_size=2),
                         **{k: v for k, v in kw.items() if k != "pool"},
                         **extra)
crash_resume(make_run, 4, "mesh4-pool")
def make_flat(**extra):
    return run_federated(params, dist, strategy,
                         **{k: v for k, v in kw.items() if k != "pool"},
                         **extra)
crash_resume(make_flat, 4, "mesh4-flat")
print("mesh4 crash/resume parity ok")
""", devices=4)
    assert "mesh4 crash/resume parity ok" in out


def test_real_sigkill_resume():
    """A REAL preemption: the child announces each durable snapshot on
    stdout and is SIGKILLed right after the first one — mid-run, async
    writer live, no cleanup. A second process resumes from whatever
    survived on disk and must still land bit-for-bit on the
    uninterrupted run."""
    child = _PRELUDE + """
import sys
d = sys.argv[1]
strategy = TinyReptileStrategy(LOSS, use_pallas=None)
with faults.announce_snapshots():
    run_federated(params, dist, strategy, rounds=16, beta=0.02, support=6,
                  seed=5, clients_per_round=3, eval_every=4, eval_kwargs=EVAL,
                  ckpt_dir=d, ckpt_every=4)
"""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
    env["JAX_PLATFORMS"] = "cpu"
    with tempfile.TemporaryDirectory() as d:
        rc, out = faults.kill_after_snapshot(
            [sys.executable, "-c", child, d], n=1, env=env, cwd=REPO,
            timeout=400)
        assert rc != 0, "child survived the kill"
        assert faults.SNAPSHOT_TAG in out
        finisher = _PRELUDE + """
import sys
d = sys.argv[1]
strategy = TinyReptileStrategy(LOSS, use_pallas=None)
kw = dict(rounds=16, beta=0.02, support=6, seed=5, clients_per_round=3,
          eval_every=4, eval_kwargs=EVAL)
ref = run_federated(params, dist, strategy, **kw)
res = run_federated(params, dist, strategy, ckpt_dir=d, ckpt_every=4,
                    resume=True, **kw)
assert_same(ref, res, "sigkill-resume")
print("sigkill resume parity ok")
"""
        r = subprocess.run([sys.executable, "-c", finisher, d],
                           capture_output=True, text=True, env=env,
                           cwd=REPO, timeout=560)
        assert r.returncode == 0, r.stderr[-3000:]
        assert "sigkill resume parity ok" in r.stdout


# ---------------------------------------------------------------------------
# in-process cases (default backend, no forced topology)

LOSS = functools.partial(paper_model_loss, SINE_MLP)
EVAL = dict(num_tasks=2, support=4, k_steps=2, lr=0.02, query=8)


@pytest.fixture(scope="module")
def sine_setup():
    params = init_paper_model(SINE_MLP, jax.random.PRNGKey(0))
    return params, SineTasks(), TinyReptileStrategy(LOSS, use_pallas=None)


def _exact(a, b):
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_resume_past_original_horizon(sine_setup):
    """--resume with a LARGER --rounds keeps going past the original
    horizon: ckpt at rounds=6, resume to rounds=10, bitwise equal to a
    fresh rounds=10 run (anneal=False — annealed alpha schedules depend
    on the total horizon by design)."""
    params, dist, strategy = sine_setup
    kw = dict(beta=0.02, support=6, seed=5, eval_every=2, eval_kwargs=EVAL,
              anneal=False)
    with tempfile.TemporaryDirectory() as d:
        run_federated(params, dist, strategy, rounds=6, ckpt_dir=d,
                      ckpt_every=2, ckpt_async=False, **kw)
        res = run_federated(params, dist, strategy, rounds=10, ckpt_dir=d,
                            ckpt_every=2, resume=True, **kw)
        fresh = run_federated(params, dist, strategy, rounds=10, **kw)
        _exact(fresh["params"], res["params"])
        assert len(fresh["history"]) == len(res["history"])
        for a, b in zip(fresh["history"], res["history"]):
            assert all(float(a[k]) == float(b[k]) for k in a)


def test_resume_at_horizon_is_noop(sine_setup):
    """Resuming a run that already finished returns the saved terminal
    state without executing any blocks."""
    params, dist, strategy = sine_setup
    kw = dict(rounds=4, beta=0.02, support=6, seed=5, eval_every=2,
              eval_kwargs=EVAL)
    with tempfile.TemporaryDirectory() as d:
        out1 = run_federated(params, dist, strategy, ckpt_dir=d,
                             ckpt_every=2, ckpt_async=False, **kw)
        out2 = run_federated(params, dist, strategy, ckpt_dir=d,
                             ckpt_every=2, resume=True, **kw)
        _exact(out1["params"], out2["params"])
        assert len(out1["history"]) == len(out2["history"])


def test_resume_fingerprint_mismatch_rejected(sine_setup):
    params, dist, strategy = sine_setup
    kw = dict(rounds=4, beta=0.02, support=6, eval_every=2,
              eval_kwargs=EVAL)
    with tempfile.TemporaryDirectory() as d:
        run_federated(params, dist, strategy, seed=5, ckpt_dir=d,
                      ckpt_every=2, ckpt_async=False, **kw)
        with pytest.raises(ValueError, match="different run config"):
            run_federated(params, dist, strategy, seed=99, ckpt_dir=d,
                          ckpt_every=2, resume=True, **kw)


def test_resume_under_another_sampler_rejected(sine_setup):
    """The fingerprint records the policy's sampler: a snapshot of the
    per-task sampler (the default before the block path) is refused
    under a policy naming another, and a run that names no policy
    continues it under its own, bit for bit."""
    params, dist, strategy = sine_setup
    kw = dict(beta=0.02, support=6, seed=5, anneal=False)
    with tempfile.TemporaryDirectory() as d:
        run_federated(params, dist, strategy, rounds=2, ckpt_dir=d,
                      ckpt_every=2, ckpt_async=False,
                      sampling=UniformSampling("reference"), **kw)
        with pytest.raises(ValueError, match="policy_sampler"):
            run_federated(params, dist, strategy, rounds=4, ckpt_dir=d,
                          ckpt_every=2, resume=True,
                          sampling=UniformSampling("vectorized"), **kw)
        resumed = run_federated(params, dist, strategy, rounds=4,
                                ckpt_dir=d, ckpt_every=2, resume=True, **kw)
        fresh = run_federated(params, dist, strategy, rounds=4,
                              sampling=UniformSampling("reference"), **kw)
        _exact(fresh["params"], resumed["params"])


def test_cli_resumes_a_snapshot_of_the_per_task_default(tmp_path,
                                                        monkeypatch, capsys):
    """A launcher run snapshotted under the per-task sampler (the default
    before the block path) continues from the CLI, which names no
    policy, in that order: as a resume that names the policy does."""
    import repro.core
    from repro.launch import train

    def cli(ckpt_dir, *argv):
        train.run_engine_strategy(train.parse_args(
            ["--strategy", "reptile", "--clients", "2", "--ckpt-dir",
             str(ckpt_dir), "--ckpt-every", "2", *argv]))
        row = json.loads(capsys.readouterr().out.splitlines()[-1])
        row.pop("dt_s")
        return row

    def per_task(*args, sampling=None, **kw):
        return run_federated(
            *args, sampling=sampling or UniformSampling("reference"), **kw)

    old, named = tmp_path / "old", tmp_path / "named"
    with monkeypatch.context() as m:
        m.setattr(repro.core, "run_federated", per_task)
        cli(old, "--rounds", "2")
        shutil.copytree(old, named)
        want = cli(named, "--rounds", "4", "--resume")
    got = cli(old, "--rounds", "4", "--resume")
    assert (old / "ckpt_00000004.npz").exists()
    assert got == want


def test_resume_mesh_layout_mismatch_rejected(sine_setup):
    """A snapshot taken under one mesh layout never silently resumes
    into another: the fingerprint pins the full mesh topology (axis
    names + extents) and the ModelPartitioner identity, so a flat (or
    1-D) checkpoint cannot feed a 2-D model-sharded run. A 1x1
    ("clients", "model") mesh makes this checkable on one device."""
    from repro.runtime.sharding import client_model_mesh
    params, dist, strategy = sine_setup
    kw = dict(rounds=4, beta=0.02, support=6, seed=5, eval_every=2,
              eval_kwargs=EVAL)
    with tempfile.TemporaryDirectory() as d:
        run_federated(params, dist, strategy, ckpt_dir=d, ckpt_every=2,
                      ckpt_async=False, **kw)
        with pytest.raises(ValueError, match="different run config"):
            run_federated(params, dist, strategy, ckpt_dir=d,
                          ckpt_every=2, resume=True,
                          mesh=client_model_mesh(1, 1), **kw)


def test_resume_shrunk_horizon_rejected(sine_setup):
    params, dist, strategy = sine_setup
    kw = dict(beta=0.02, support=6, seed=5, eval_every=2, eval_kwargs=EVAL)
    with tempfile.TemporaryDirectory() as d:
        run_federated(params, dist, strategy, rounds=8, ckpt_dir=d,
                      ckpt_every=2, ckpt_async=False, **kw)
        with pytest.raises(ValueError):
            run_federated(params, dist, strategy, rounds=4, ckpt_dir=d,
                          ckpt_every=2, resume=True, **kw)


def test_resume_empty_dir_starts_fresh(sine_setup, caplog):
    """resume=True against a directory with no snapshots is a fresh
    start (logged), not an error — first launch of a preemptible job."""
    import logging
    params, dist, strategy = sine_setup
    kw = dict(rounds=4, beta=0.02, support=6, seed=5, eval_every=2,
              eval_kwargs=EVAL)
    ref = run_federated(params, dist, strategy, **kw)
    with tempfile.TemporaryDirectory() as d:
        with caplog.at_level(logging.INFO, "repro.core.engine"):
            res = run_federated(params, dist, strategy, ckpt_dir=d,
                                ckpt_every=2, ckpt_async=False,
                                resume=True, **kw)
        assert any("fresh" in r.message for r in caplog.records)
    _exact(ref["params"], res["params"])


def test_ckpt_argument_validation(sine_setup):
    params, dist, strategy = sine_setup
    kw = dict(rounds=2, beta=0.02, support=6, seed=5)
    with pytest.raises(ValueError):
        run_federated(params, dist, strategy, ckpt_dir="/tmp/x",
                      ckpt_every=0, **kw)
    with pytest.raises(ValueError):
        run_federated(params, dist, strategy, resume=True, **kw)


def test_corrupt_newest_snapshot_resumes_from_older(sine_setup, caplog):
    """Graceful degradation end-to-end: corrupt the newest snapshot,
    resume falls back to the previous one (warning logged) and still
    reproduces the uninterrupted run bit-for-bit."""
    import logging
    params, dist, strategy = sine_setup
    from repro.checkpoint import list_checkpoints
    kw = dict(rounds=12, beta=0.02, support=6, seed=5, eval_every=4,
              eval_kwargs=EVAL)
    ref = run_federated(params, dist, strategy, **kw)
    with tempfile.TemporaryDirectory() as d:
        try:
            with faults.crash_at_round(8):
                run_federated(params, dist, strategy, ckpt_dir=d,
                              ckpt_every=4, ckpt_async=False, **kw)
        except faults.SimulatedPreemption:
            pass
        faults.flip_bytes(list_checkpoints(d)[-1])
        with caplog.at_level(logging.WARNING, "repro.checkpoint.ckpt"):
            res = run_federated(params, dist, strategy, ckpt_dir=d,
                                ckpt_every=4, resume=True, **kw)
        assert any("falling back" in r.message for r in caplog.records)
    _exact(ref["params"], res["params"])
    assert len(ref["history"]) == len(res["history"])
