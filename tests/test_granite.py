"""granite-4.0-h-micro (hybrid Mamba-2 + NoPE GQA) against its plain
reference (repro.testing.granite_reference), and TinyReptile over it
through run_federated with the whole cohort in one pass and in passes of
one client.

The model here is granite-shaped at a CPU size: the published layer kinds
(MMMMMAMMMM) and multipliers, d 64, 4 query and 2 KV heads of 16, Mamba
4 heads of 16 with state 16 and chunk 16, vocabulary 256, 64 tokens.
"""
import dataclasses
import functools
import glob

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.configs import get_arch
from repro.configs.paper_models import SINE_MLP
from repro.core import engine, run_federated
from repro.core.strategies import TinyReptileStrategy
from repro.data import SineTasks
from repro.data.lm import lm_loss
from repro.data.tasks import ClientTask, TaskDistribution
from repro.metering import MetricsTracker
from repro.models.paper_nets import init_paper_model, paper_model_loss
from repro.models.transformer import build_model
from repro.testing import granite_reference as ref

GRANITE = get_arch("granite-4.0-h-micro")
SMALL = dataclasses.replace(
    GRANITE, num_layers=10, d_model=64, num_heads=4, num_kv_heads=2,
    head_dim=16, d_ff=128, vocab_size=256, ssm_state=16, ssm_head_dim=16,
    ssm_chunk=16, dtype="float32")
SEQ = 64
MODEL = build_model(SMALL)
STRATEGY = TinyReptileStrategy(lm_loss(MODEL))
ROUNDS, COHORT, SUPPORT, BETA = 2, 4, 2, 0.01

#: the catalog row of the published config.json (hf:ibm-granite/
#: granite-4.0-h-micro), in the registered config's field names
CATALOG = dict(
    num_layers=40, d_model=2048, num_heads=32, num_kv_heads=8, head_dim=64,
    d_ff=8192, vocab_size=100_352, ssm_state=128, ssm_head_dim=64,
    ssm_expand=2, ssm_conv_width=4, ssm_chunk=256, norm_eps=1e-5,
    embedding_multiplier=12.0, residual_multiplier=0.22,
    attention_multiplier=0.015625, logits_scaling=8.0, use_rope=False,
    tie_embeddings=True, act="silu")


def _tokens(seed, shape):
    x = np.random.default_rng(seed).integers(0, SMALL.vocab_size, shape)
    y = np.concatenate([x[..., 1:], np.full(shape[:-1] + (1,), -1)], -1)
    return x.astype(np.int32), y.astype(np.int32)


@pytest.fixture(scope="module")
def params():
    """Seeded weights with every leaf moved off its init, so that norms,
    biases, D and dt_bias all enter the comparison."""
    p = MODEL.init(jax.random.PRNGKey(0))
    leaves, tree = jax.tree.flatten(p)
    keys = jax.random.split(jax.random.PRNGKey(1), len(leaves))
    return jax.tree.unflatten(tree, [
        leaf + 0.1 * jax.random.normal(k, leaf.shape)
        for leaf, k in zip(leaves, keys)])


def _program_loss(model):
    return jax.jit(jax.value_and_grad(lm_loss(model)))


_PROGRAM_LOSS = _program_loss(MODEL)


@jax.jit
def _reference_loss(p, batch):
    with jax.default_matmul_precision("highest"):
        return jax.value_and_grad(lambda q: ref.loss(
            ref.spec_of(SMALL), q, batch["x"], batch["y"], block=16))(p)


def _rel(a, b):
    return float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))


def test_registered_config_is_the_catalog_row():
    for field, want in CATALOG.items():
        assert getattr(GRANITE, field) == want, field
    kinds = GRANITE.block_kinds()
    assert [i for i, k in enumerate(kinds) if k == "attn"] == [5, 15, 25, 35]
    assert GRANITE.source.startswith("https://huggingface.co/ibm-granite/")
    cut = dataclasses.replace(GRANITE, num_layers=10, vocab_size=12_544)
    shapes = jax.eval_shape(build_model(cut).init, jax.random.PRNGKey(0))
    assert cut.param_count() == 772_160_448 == sum(
        int(np.prod(a.shape)) for a in jax.tree.leaves(shapes))
    small = MODEL.init(jax.random.PRNGKey(0))
    assert SMALL.param_count() == sum(a.size for a in jax.tree.leaves(small))


def test_loss_and_gradients_match_the_reference(params):
    x, y = _tokens(0, (1, SEQ))
    batch = {"x": jnp.asarray(x), "y": jnp.asarray(y)}
    lo, g = _PROGRAM_LOSS(params, batch)
    want, g_ref = _reference_loss(params, batch)
    # float32 on the CPU: the program's chunked SSD and blockwise online
    # softmax only reorder the reference's sums, so loss and gradients
    # agree to float32 round-off amplified by the backward pass (8.7e-6
    # worst leaf seen); 1e-4 leaves 10x room and fails every fault below
    assert abs(float(lo) - float(want)) <= 1e-5 * abs(float(want))
    worst = max(jax.tree.leaves(jax.tree.map(_rel, g, g_ref)))
    assert worst <= 1e-4, worst


@pytest.mark.parametrize("fault", ["residual_multiplier", "mamba_D"])
def test_planted_fault_fails_the_comparison(params, fault):
    x, y = _tokens(0, (1, SEQ))
    batch = {"x": jnp.asarray(x), "y": jnp.asarray(y)}
    want, g_ref = _reference_loss(params, batch)
    if fault == "residual_multiplier":
        lo, g = _program_loss(build_model(dataclasses.replace(
            SMALL, residual_multiplier=1.0)))(params, batch)
    else:                        # the program drops one Mamba layer's D
        dropped = jax.tree.map(lambda a: a, params)
        dropped["layers"][0]["mamba"]["D"] = jnp.zeros_like(
            params["layers"][0]["mamba"]["D"])
        lo, g = _PROGRAM_LOSS(dropped, batch)
    gap = abs(float(lo) - float(want)) / abs(float(want))
    worst = max(jax.tree.leaves(jax.tree.map(_rel, g, g_ref)))
    assert gap > 1e-5 or worst > 1e-4, (gap, worst)


class _FixedTasks(TaskDistribution):
    """The block ``data`` handed out in the engine's order, client by
    client (round-major) and sample by sample."""

    def __init__(self, data):
        self.data, self.next_client = data, 0

    def sample_task(self, rng):
        r, c = divmod(self.next_client, COHORT)
        self.next_client += 1
        samples = zip(self.data["x"][r, c], self.data["y"][r, c])
        return ClientTask(make_sample=lambda _rng: next(samples),
                          task_id=self.next_client - 1)


def _federated(params, data, trace_dir=None):
    tracker = MetricsTracker()
    kw = dict(rounds=ROUNDS, clients_per_round=COHORT, support=SUPPORT,
              beta=BETA, alpha=1.0, anneal=True, seed=5, tracker=tracker)
    if trace_dir is None:
        out = run_federated(params, _FixedTasks(data), STRATEGY, **kw)
    else:
        with jax.profiler.trace(trace_dir):
            out = run_federated(params, _FixedTasks(data), STRATEGY, **kw)
    return out["params"], tracker


def test_federated_routes_match_client_by_client_reference(
        params, monkeypatch, tmp_path):
    x, y = _tokens(1, (ROUNDS, COHORT, SUPPORT, SEQ))
    data = {"x": x, "y": y}
    whole, t_whole = _federated(params, data)
    # the same run again, compiled, under the profiler for its spans
    traced, _ = _federated(params, data, str(tmp_path))
    with monkeypatch.context() as m:
        m.setattr(engine, "_memory_budget", lambda device: 1)
        passes, t_passes = _federated(params, data)
    assert t_whole.gauges["engine.clients_per_pass"] == COHORT
    assert t_passes.gauges["engine.clients_per_pass"] == 1

    alphas = [1.0, 0.5]              # annealed: alpha (1 - r / rounds)
    want = ref.tinyreptile(ref.spec_of(SMALL), params,
                           [(x[r], y[r], alphas[r]) for r in range(ROUNDS)],
                           BETA, block=16)
    change = jax.tree.map(lambda a, b: a - b, want[-1][0], params)
    for got, tracker in ((whole, t_whole), (passes, t_passes)):
        # against the reference: the backward pass's float32 round-off
        # (1e-4, as above) on each step's change, over 2 rounds of 2 steps
        gaps = jax.tree.map(lambda g, w, c: float(
            jnp.linalg.norm(g - w) / max(float(jnp.linalg.norm(c)), 1e-30)),
            got, want[-1][0], change)
        assert max(jax.tree.leaves(gaps)) <= 1e-3
        np.testing.assert_allclose(
            tracker.series_values("round.inner_loss"),
            [lo for _, lo in want], rtol=1e-5)
    # the two routes differ only in the order of the cohort sum: one
    # float32 rounding of the uplinks' mean, scaled by alpha
    for a, b in zip(jax.tree.leaves(whole), jax.tree.leaves(passes)):
        np.testing.assert_allclose(a, b, rtol=0, atol=1e-6 * max(
            1.0, float(jnp.max(jnp.abs(b)))))

    found = glob.glob(f"{tmp_path}/**/*.xplane.pb", recursive=True)
    host = ProfileData.from_file(found[0]).find_plane_with_name("/host:CPU")
    spans = {ev.name: dict(ev.stats) for line in host.lines
             for ev in line.events if ev.name.startswith("engine.")}
    tokens = ROUNDS * COHORT * SUPPORT * SEQ
    assert spans["engine.dispatch"]["clients_per_pass"] == COHORT
    assert spans["engine.dispatch"]["tokens"] == tokens
    assert spans["engine.run"]["clients_per_pass"] == COHORT
    assert spans["engine.run"]["tokens"] == tokens


@pytest.mark.parametrize("model", ["sine_mlp", "omniglot_conv"])
def test_paper_models_keep_the_whole_cohort(model, monkeypatch):
    """With a v5e's memory budget the paper models' cohorts fit in one
    pass, by their shapes alone (no client step is compiled to be
    measured), so the engine builds the runner it always built and the
    results are bit-for-bit those of a device that reports no memory."""
    from repro.configs.paper_models import OMNIGLOT_CONV
    from repro.data import OmniglotTasks
    cfg, tasks = ((SINE_MLP, SineTasks) if model == "sine_mlp"
                  else (OMNIGLOT_CONV, OmniglotTasks))
    strategy = TinyReptileStrategy(
        functools.partial(paper_model_loss, cfg))
    phi = init_paper_model(cfg, jax.random.PRNGKey(0))

    def go():
        tracker = MetricsTracker()
        out = run_federated(phi, tasks(), strategy, rounds=2,
                            clients_per_round=8, support=4, seed=1,
                            tracker=tracker)
        return out["params"], tracker.gauges["engine.clients_per_pass"]
    base, n_base = go()
    monkeypatch.setattr(engine, "_memory_budget",
                        lambda device: 16_908_812_288)
    monkeypatch.setattr(engine, "_client_step_bytes", lambda *a: pytest.fail(
        "a paper model's client step was compiled to be measured"))
    got, n_got = go()
    assert n_base == n_got == 8
    for a, b in zip(jax.tree.leaves(base), jax.tree.leaves(got)):
        np.testing.assert_array_equal(a, b)
    assert all(k[-2] is None for k in engine._RUNNER_CACHE.keys()
               if k[0] is strategy)
