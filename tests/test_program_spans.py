"""The program's own spans (listed in repro.metering.tracker) as a
profiler session records them.

- ``run_federated`` records ``engine.run`` and each block's phases; the
  prefetch thread's spans pair with the consumer's by ``block``, and
  with ``prefetch=0`` every span sits on the calling thread.
  ``engine.sample`` names the sampler its block's data came through.
- The server's ``serve.tick`` counts add up to the stream: admitted and
  retired to the requests, ``live`` to the steps they asked for, and the
  admitted id ranges follow one another; each tick holds its four
  leaves, and its dispatch and readback each name the bytes of the one
  packed array they move.
- Tracing changes nothing the program computes.
"""
import functools
import glob

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.configs.paper_models import SINE_MLP
from repro.core import ClientPool, UniformSampling, run_federated
from repro.core.strategies import TinyReptileStrategy
from repro.data import SineTasks
from repro.data.tasks import TaskDistribution
from repro.models.paper_nets import init_paper_model, paper_model_loss
from repro.serving import AdaptationServer, Fp32Adapter

LOSS = functools.partial(paper_model_loss, SINE_MLP)
BLOCK_PHASES = ("engine.plan", "engine.sample", "engine.stage",
                "engine.wait", "engine.dispatch", "engine.fetch")
TICK_LEAVES = ("serve.refill", "serve.dispatch", "serve.readback",
               "serve.retire")


def _spans(trace_dir):
    """Every engine./serve. span of the trace: name, host line (one per
    thread), start and end in ns, arguments."""
    found = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    assert len(found) == 1, found
    host = ProfileData.from_file(found[0]).find_plane_with_name("/host:CPU")
    return [dict(name=ev.name, thread=i, start=ev.start_ns,
                 end=ev.start_ns + ev.duration_ns, args=dict(ev.stats))
            for i, line in enumerate(host.lines) for ev in line.events
            if ev.name.startswith(("engine.", "serve."))]


class _PerTaskSine(TaskDistribution):
    """Sine tasks with no block call of their own: the base class's
    ``sample_support_block`` runs the per-task loop."""

    def sample_task(self, rng):
        return SineTasks().sample_task(rng)


def _train(prefetch, dist=None, **kw):
    phi = init_paper_model(SINE_MLP, jax.random.PRNGKey(0))
    return run_federated(phi, dist or SineTasks(), TinyReptileStrategy(LOSS),
                         rounds=8, clients_per_round=2, support=4, seed=3,
                         max_block=4, prefetch=prefetch, **kw)["params"]


def _serve():
    """Nine requests of k = 1..5 on 4 slots at 2 steps a tick: five
    queued, two ticks, four more, then drained."""
    phi = init_paper_model(SINE_MLP, jax.random.PRNGKey(0))
    server = AdaptationServer(phi, Fp32Adapter(loss_fn=LOSS), slots=4,
                              k_max=5, steps_per_tick=2)
    rng = np.random.default_rng(0)
    ks = [1 + i % 5 for i in range(9)]

    def submit(lo, hi):
        for k in ks[lo:hi]:
            sx = rng.uniform(-5, 5, (6, 1)).astype(np.float32)
            qx = rng.uniform(-5, 5, (4, 1)).astype(np.float32)
            server.submit(sx, np.sin(sx), qx, np.sin(qx), k)
    submit(0, 5)
    results = server.step() + server.step()
    submit(5, 9)
    return server, ks, results + server.drain()


@pytest.mark.parametrize("kw,sampler", [
    ({}, "vectorized"),
    ({"sampling": UniformSampling("reference")}, "reference"),
    ({"pool": ClientPool(SineTasks(), 4)}, "pool"),
    ({"dist": _PerTaskSine()}, "reference"),
])
def test_engine_sample_names_its_sampler(tmp_path, kw, sampler):
    """Each block's ``engine.sample`` names the path its data took: one
    block call by default, the per-task loop when named or when the
    distribution has no block call of its own, a pool's check-ins when
    pooled."""
    _train(2, **kw)                            # compiled outside the trace
    with jax.profiler.trace(str(tmp_path)):
        _train(2, **kw)
    found = [s["args"] for s in _spans(tmp_path)
             if s["name"] == "engine.sample"]
    assert [a["block"] for a in found] == [0, 1]
    assert all(a["sampler"] == sampler for a in found), found


@pytest.mark.parametrize("prefetch", [0, 2])
def test_engine_spans_pair_by_block(tmp_path, prefetch):
    _train(prefetch)                           # compiled outside the trace
    with jax.profiler.trace(str(tmp_path)):
        _train(prefetch)
    spans = _spans(tmp_path)
    (run,) = [s for s in spans if s["name"] == "engine.run"]
    # sine samples are no tokens; both clients fit one pass
    assert run["args"] == {"rounds": 8, "clients": 2, "seed": 3,
                           "blocks": 2, "clients_per_pass": 2, "tokens": 0}
    by = {}
    for s in spans:
        if s["name"] == "engine.run":
            continue
        assert run["start"] <= s["start"] <= s["end"] <= run["end"]
        assert s["args"]["rounds"] == 4
        by.setdefault(s["name"], {})[s["args"]["block"]] = s
    assert set(by) == set(BLOCK_PHASES)
    assert all(set(v) == {0, 1} for v in by.values())
    assert all(s["args"]["bytes"] > 0 for s in by["engine.stage"].values())
    assert all(s["args"]["clients_per_pass"] == 2 and s["args"]["tokens"] == 0
               for s in by["engine.dispatch"].values())
    producer = {s["thread"] for n in BLOCK_PHASES[:3]
                for s in by[n].values()}
    consumer = {s["thread"] for n in BLOCK_PHASES[3:]
                for s in by[n].values()}
    assert consumer == {run["thread"]} and len(producer) == 1
    assert (producer == consumer) == (prefetch == 0)
    for b in (0, 1):
        # a block is planned, sampled and staged, then dispatched
        plan, sample, stage, wait, dispatch = (
            by[n][b] for n in BLOCK_PHASES[:5])
        assert (plan["end"] <= sample["start"] <= sample["end"]
                <= stage["start"] <= stage["end"] <= dispatch["start"])
        assert wait["end"] <= dispatch["start"]


def test_serve_tick_counts_add_up(tmp_path):
    _serve()                                   # compiled outside the trace
    with jax.profiler.trace(str(tmp_path)):
        server, ks, results = _serve()
    spans = _spans(tmp_path)
    ticks = sorted((s for s in spans if s["name"] == "serve.tick"),
                   key=lambda s: s["start"])
    assert len(ticks) == server.ticks == len(
        [s for s in spans if s["name"] == "serve.refill"])
    assert [t["args"]["tick"] for t in ticks] == list(range(len(ticks)))
    total = {key: sum(t["args"][key] for t in ticks)
             for key in ("admitted", "retired", "live")}
    assert len(results) == 9
    assert total == {"admitted": 9, "retired": 9, "live": sum(ks)}
    assert all(t["args"]["computed"] == 4 * 2 for t in ticks)
    assert all(0 <= t["args"]["live"] <= 8 for t in ticks)
    rid = 0
    for t in ticks:
        leaves = [s for s in spans if s["name"] in TICK_LEAVES
                  and t["start"] <= s["start"] <= s["end"] <= t["end"]]
        assert sorted(s["name"] for s in leaves) == sorted(TICK_LEAVES)
        assert all(s["thread"] == t["thread"] for s in leaves)
        (refill,) = [s for s in leaves if s["name"] == "serve.refill"]
        assert refill["args"] == {"rid_lo": rid,
                                  "rid_hi": rid + t["args"]["admitted"]}
        rid = refill["args"]["rid_hi"]
    assert rid == 9
    assert not server._left.any()


def test_serve_tick_moves_one_packed_array_each_way(tmp_path):
    """4 slots of requests with 6 support and 4 query samples of one
    feature: a refill row is the slot, k and 6 + 6 + 4 + 4 float32
    values, all 4-byte words; the result is finished, step and query
    loss for each slot."""
    _serve()                                   # compiled outside the trace
    with jax.profiler.trace(str(tmp_path)):
        server, _, _ = _serve()
    spans = _spans(tmp_path)
    slots, row_words = 4, 2 + 6 + 6 + 4 + 4
    for name, nbytes in (("serve.dispatch", 4 * slots * row_words),
                         ("serve.readback", 4 * 3 * slots)):
        found = [s["args"] for s in spans if s["name"] == name]
        assert len(found) == server.ticks
        assert all(args == {"bytes": nbytes} for args in found), found


@pytest.mark.parametrize("side", ["engine", "server"])
def test_tracing_changes_nothing_computed(tmp_path, side):
    def once():
        if side == "engine":
            return [np.asarray(x) for x in jax.tree.leaves(_train(2))]
        _, _, results = _serve()
        return [np.asarray([(r.rid, r.query_loss, r.steps)
                            for r in results])]
    plain = once()
    with jax.profiler.trace(str(tmp_path)):
        traced = once()
    for a, b in zip(plain, traced, strict=True):
        np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("side", ["engine", "server"])
def test_device_scopes_reach_the_lowered_programs(side):
    """The block runner's and the tick's ``jax.named_scope`` names are in
    the lowered programs' op metadata."""
    phi = init_paper_model(SINE_MLP, jax.random.PRNGKey(0))
    if side == "engine":
        from repro.core.engine import CommChannel, _BlockRunner
        from repro.core.pipeline import ClientSchedule
        r, c, s = 2, 2, 4
        sched = ClientSchedule(
            valid=np.ones(r, bool), alpha=np.ones(r, np.float32),
            round_index=np.arange(r, dtype=np.int32),
            participation=np.ones((r, c), bool),
            local_steps=np.full((r, c), s, np.int32),
            weights=np.full((r, c), 1 / c, np.float32))
        batch = {k: np.zeros((r, c, s, 1), np.float32) for k in "xy"}
        runner = _BlockRunner(TinyReptileStrategy(LOSS), 0.01,
                              CommChannel())
        lowered = runner._jit.lower(phi, sched, batch)
        scopes = ("client_phase", "server_update")
    else:
        server = AdaptationServer(phi, Fp32Adapter(loss_fn=LOSS), slots=4,
                                  k_max=5, steps_per_tick=2)
        x = np.zeros((6, 1), np.float32)
        server.submit(x, x, x[:4], x[:4], 2)
        server._alloc_state()
        refill, _ = server._build_refill()
        lowered = jax.jit(server._tick_fn).lower(server._pack,
                                                 server._state, refill)
        scopes = ("refill_scatter", "advance", "query")
    text = lowered.as_text(debug_info=True)
    assert all(f"/{scope}/" in text for scope in scopes)
