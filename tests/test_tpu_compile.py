"""Compile rehearsals for a TPU v5e that is described, not attached.

The main path's Pallas kernels are compiled by the TPU compiler at the
paper models' widths, with interpret mode off, so a kernel that Mosaic
refuses (unaligned blocks, scalar stores to VMEM, unsupported dot
types) fails here instead of on the chip. The topology is described
inside a module fixture, never at import: only one process at a time may
hold the TPU library.

The file also pins ``chip_smoke.py``'s refusal to run without a TPU.
"""
import os
import shutil
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.paper_models import OMNIGLOT_CONV, SINE_MLP
from repro.kernels import meta_update as _mu
from repro.kernels import online_sgd as _sgd
from repro.kernels import online_sgd_int8 as _int8
from repro.kernels import ops
from repro.models.paper_nets import init_paper_model

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
VMAP = 8


@pytest.fixture(scope="module")
def one_chip():
    """One device of a described v5e:2x2, with the kernels compiled for
    it (interpret mode off) and the persistent cache off: such compiles
    cannot be read back without a chip."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    cache_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    # traced kernels are cached per shape: drop them on the way in and
    # out, so no interpret-mode trace leaks in and no compiled-mode one
    # leaks out to the CPU tests
    jax.clear_caches()
    with pytest.MonkeyPatch.context() as mp:
        for mod in (_mu, _sgd, _int8):
            mp.setattr(mod, "pltpu_interpret", lambda: False)
        yield SingleDeviceSharding(topo.devices[0])
    jax.clear_caches()
    jax.config.update("jax_enable_compilation_cache", cache_on)


def _specs(tree, sharding, batch=None):
    lead = () if batch is None else (batch,)
    return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
        lead + tuple(jnp.shape(x)), jnp.result_type(x), sharding=sharding),
        tree)


def _assert_kernel(fn, *args):
    hlo = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in hlo


MODELS = {"omniglot_conv": OMNIGLOT_CONV, "sine_mlp": SINE_MLP}


@pytest.mark.parametrize("vmapped", [False, True], ids=["plain", "vmap"])
@pytest.mark.parametrize("model", sorted(MODELS))
@pytest.mark.parametrize("kernel", ["meta_update", "online_sgd"])
def test_fp32_kernel_compiles(one_chip, kernel, model, vmapped):
    """The server update and the serving SGD step, over every leaf of
    the paper model; vmapped as the server vmaps its slots."""
    params = jax.eval_shape(lambda: init_paper_model(
        MODELS[model], jax.random.PRNGKey(0)))
    fn = {"meta_update": ops.tree_meta_update,
          "online_sgd": ops.tree_online_sgd}[kernel]
    batch = VMAP if vmapped else None
    if vmapped:
        fn = jax.vmap(fn, in_axes=(0, 0, None))
    p = _specs(params, one_chip, batch)
    _assert_kernel(fn, p, p, jax.ShapeDtypeStruct((), jnp.float32,
                                                  sharding=one_chip))


def _dfa_specs(dims, S, sharding, batch):
    din, h1, h2, dout = dims
    i8, i32, f32 = jnp.int8, jnp.int32, jnp.float32

    def sds(shape, dtype, per_client=True):
        lead = (batch,) if batch is not None and per_client else ()
        return jax.ShapeDtypeStruct(lead + shape, dtype, sharding=sharding)

    w_shapes = ((din, h1), (h1, h2), (h2, dout))
    ws = tuple(sds(s, i8) for s in w_shapes)
    bs = tuple(sds((h,), i32) for h in (h1, h2, dout))
    xq, yal = sds((S, din), i8), sds((S, dout), i32)
    layer = sds((), i32)
    fb = tuple(sds((dout, h), i8, False) for h in (h1, h2))
    dither = tuple(sds(s, f32, False) for s in w_shapes)
    scalar = sds((), f32, False)
    scales = {"f0": scalar, "f1": scalar, "fe": scalar, "floss": scalar,
              "ftw": (scalar,) * 3, "ftb": (scalar,) * 3}
    return ws, bs, xq, yal, layer, fb, dither, scales


@pytest.mark.parametrize("vmapped", [False, True], ids=["plain", "vmap"])
@pytest.mark.parametrize("dims", [(1, 32, 32, 1), (5, 16, 12, 3)],
                         ids=["sine_mlp", "din5_dout3"])
def test_dfa_epoch_int8_compiles(one_chip, dims, vmapped):
    """The TIFeD epoch at S=32: vmapped as the engine vmaps its cohort
    and the server its slots (weights, data and layer per client)."""
    batch = VMAP if vmapped else None
    args = _dfa_specs(dims, 32, one_chip, batch)
    fn = ops.dfa_epoch_int8
    if vmapped:
        fn = jax.vmap(fn, in_axes=(0, 0, 0, 0, 0, None, None, None))
    _assert_kernel(fn, *args)


def _run_smoke(cwd, script):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.run([sys.executable, script], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


@pytest.mark.parametrize("where", ["checkout", "alone"])
def test_chip_smoke_refuses_without_tpu(tmp_path, where):
    """With no TPU, or without the rest of the repo, chip_smoke.py exits
    non-zero and prints no passing result."""
    script = os.path.join(REPO, "chip_smoke.py")
    cwd = REPO
    if where == "alone":
        cwd = str(tmp_path)
        script = shutil.copy(script, tmp_path / "chip_smoke.py")
    r = _run_smoke(cwd, str(script))
    assert r.returncode != 0
    assert '"ok": true' not in r.stdout
