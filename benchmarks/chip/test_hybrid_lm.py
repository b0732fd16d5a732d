"""CPU tests of the hybrid language-model cell's yardstick: the kind's
counts at the published widths, its weights and loss against the
program's at a CPU-sized cut, the driver's token data, and whole runs of
the cell at that cut with the device check skipped, sound and with a
fault planted, which must come out not correct.

Run from the repository root with ``PYTHONPATH=src JAX_PLATFORMS=cpu``.
"""
from __future__ import annotations

import dataclasses
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

from chipbench import harness  # noqa: E402

CELL = "granite_h_micro.tinyreptile.c4.s4k"
CFG = harness.load_json(HERE / "configs" / "granite_4_0_h_micro.json")
KIND = harness.load_module("kinds", "hybrid_lm")
DRIVER = harness.load_module("drivers", "train_lm")

#: granite-shaped at a CPU size: the published layer kinds and
#: multipliers, every width cut
SMALL = dict(CFG, hidden_size=64, num_attention_heads=4,
             num_key_value_heads=2, shared_intermediate_size=128,
             intermediate_size=128, mamba_d_state=16, mamba_d_head=16,
             mamba_n_heads=8, mamba_chunk_size=16, vocab_size=256)
SMALL_TRAFFIC = {"seq": 64, "beta": 0.01}


def _small_arch(cfg):
    from repro.configs import get_arch
    return dataclasses.replace(
        get_arch(cfg["registered"]), num_layers=cfg["num_hidden_layers"],
        d_model=cfg["hidden_size"], num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"],
        head_dim=cfg["hidden_size"] // cfg["num_attention_heads"],
        d_ff=cfg["shared_intermediate_size"], vocab_size=cfg["vocab_size"],
        ssm_state=cfg["mamba_d_state"], ssm_head_dim=cfg["mamba_d_head"],
        ssm_chunk=cfg["mamba_chunk_size"],
        residual_multiplier=cfg["residual_multiplier"], dtype="float32")


def test_counts_at_the_published_widths():
    assert KIND.param_count(CFG) == CFG["params"] == 772_160_448
    assert KIND.update_bytes(CFG) == 12 * 772_160_448
    # 6 x matmul weights x tokens (the tied head once), causal attention
    # (one layer, 32 heads of 64) and nine layers of chunked SSD
    s = 4096
    matmul = 9 * (2048 * 8512 + 4096 * 2048) + (2 * 2048 * 64 * 40) \
        + 10 * 3 * 2048 * 8192 + 12_544 * 2048
    attn = 2 * s * s * 32 * 64
    ssd = 9 * (s * 256 * (128 + 64 * 64) + 4 * s * 64 * 64 * 128)
    assert KIND.train_flops(CFG, s) == 3 * (2 * s * matmul + attn + ssd)
    assert 19.4e12 < KIND.train_flops(CFG, s) < 19.7e12


def test_config_holds_the_published_widths():
    arch = DRIVER.arch_config(CFG)
    assert (arch.num_layers, arch.vocab_size) == (10, 12_544)
    assert arch.param_count() == CFG["params"]
    assert CFG["layer_types"][:10] == ["mamba"] * 5 + ["attention"] \
        + ["mamba"] * 4
    with pytest.raises(harness.BenchError, match="hidden_size"):
        DRIVER.arch_config(dict(CFG, hidden_size=1024))


def test_kind_weights_and_loss_are_the_programs():
    from repro.data.lm import lm_loss
    from repro.models.transformer import build_model
    model = build_model(_small_arch(SMALL))
    params = KIND.init(SMALL, jax.random.PRNGKey(3))
    want = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    assert jax.tree.structure(params) == jax.tree.structure(want)
    assert [a.shape for a in jax.tree.leaves(params)] == \
        [a.shape for a in jax.tree.leaves(want)]
    assert KIND.param_count(SMALL) == sum(
        a.size for a in jax.tree.leaves(params))
    data = DRIVER.token_block(7, 1, 1, 1, 64, SMALL["vocab_size"])
    batch = {"x": jnp.asarray(data["x"][0, 0]),
             "y": jnp.asarray(data["y"][0, 0])}
    got = float(jax.jit(lm_loss(model))(params, batch))
    with jax.default_matmul_precision("highest"):
        ref = float(jax.jit(lambda p, b: KIND.loss(SMALL, p, b))(
            params, batch))
    # float32 on the CPU: the same sums in another order
    assert got == pytest.approx(ref, rel=1e-5)


def test_token_block_is_seeded_and_shifted():
    a = DRIVER.token_block(2**31 + 5, 2, 3, 2, 32, 100)
    b = DRIVER.token_block(2**31 + 5, 2, 3, 2, 32, 100)
    c = DRIVER.token_block(2**31 + 6, 2, 3, 2, 32, 100)
    assert a["x"].shape == a["y"].shape == (2, 3, 2, 32)
    assert a["x"].dtype == np.int32
    np.testing.assert_array_equal(a["x"], b["x"])
    assert not np.array_equal(a["x"], c["x"])
    assert a["x"].min() >= 0 and a["x"].max() < 100
    np.testing.assert_array_equal(a["y"][..., :-1], a["x"][..., 1:])
    assert (a["y"][..., -1] == -1).all()


def _cpu_device(chips, peaks):
    d = jax.devices()
    return d[:chips], {"platform": d[0].platform, "kind": d[0].device_kind,
                       "count": len(d)}, peaks["devices"]["TPU v5 lite"]


def _drive(monkeypatch, arch=_small_arch):
    load = harness.load_cell

    def small(name, bench=None):
        c = load(name, bench)
        c.config = SMALL
        c.traffic.update(SMALL_TRAFFIC)
        return c
    monkeypatch.setattr(harness, "load_cell", small)
    monkeypatch.setattr(harness, "enable_compile_cache", lambda: None)
    monkeypatch.setattr(DRIVER, "arch_config", arch)
    args = harness.parse_args(["--workload", CELL, "--seed", str(2**31 + 9),
                               "--seconds", "0.5", "--trace", "0"])
    return harness.measure(args, time.perf_counter(),
                           device_check=_cpu_device)


def test_sound_run_is_correct(monkeypatch):
    res = _drive(monkeypatch)
    assert res["correct"], res["checks"]
    assert set(res["checks"]) == {"loss_gap", "change1_gap", "change2_gap",
                                  "nonfinite_params"}
    assert res["failed"] == 0 and res["attempted"] >= 2
    assert res["metrics"]["rounds_per_s"]["value"] > 0
    # a CPU reports no memory: the whole cohort in one pass
    assert res["other_metrics"]["clients_per_pass"] == 4


def test_residual_multiplier_fault_is_not_correct(monkeypatch):
    def faulty(cfg):
        return dataclasses.replace(_small_arch(cfg), residual_multiplier=1.0)
    res = _drive(monkeypatch, faulty)
    assert not res["correct"]
    assert res["checks"]["change1_gap"]["value"] > \
        res["checks"]["change1_gap"]["limit"]


def test_calibration_names_the_worst_leaves(monkeypatch):
    """``calibrate_faults.py``'s readings at the CPU-sized cut: the
    program's worst leaf of each change gap is the leaf that the
    yardstick's number comes from."""
    calib = _load_calibrate()
    load = harness.load_cell

    def small(name, bench=None):
        c = load(name, bench)
        c.config = SMALL
        c.traffic.update(SMALL_TRAFFIC)
        return c
    monkeypatch.setattr(harness, "load_cell", small)
    monkeypatch.setattr(DRIVER, "arch_config", _small_arch)
    cell = harness.load_cell(CELL)
    run = harness.Run(cell, 2**31 + 9, 1.0, False, time.perf_counter(),
                      {}, jax.devices()[:1])
    row = calib.training(run, DRIVER, faults=False)
    prog = row["program"]
    for k in ("change1", "change2"):
        worst = prog["leaves"][k]
        assert 1 <= len(worst) <= 3
        assert worst[0]["gap"] == pytest.approx(prog[f"{k}_gap"], rel=1e-9)
        assert [w["gap"] for w in worst] == sorted(
            (w["gap"] for w in worst), reverse=True)
        assert all(w["ref"] > 0 and w["ulp"] > 0 and -1 <= w["cos"] <= 1
                   for w in worst)
    assert set(row) == {"program"}


def _load_calibrate():
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "calibrate_faults", HERE / "calibrate_faults.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
