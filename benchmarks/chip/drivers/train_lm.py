"""Driver of the federated language-model training cells
(``"driver": "train_lm"``).

The program is the registered architecture that the config names
(``registered``), cut as the config says (its ``num_hidden_layers``
layers and its ``vocab_size``), in float32, trained by
``TinyReptileStrategy(lm_loss(model))`` through ``run_federated``.

Each call's token data are made in bulk from the call's seed: every
client of every round is a domain of its own, with a Zipf unigram law
over a permutation of the vocabulary and a bigram successor table, as
``repro.data.lm.LMClientStream`` draws them, here for all clients at
once. Set-up makes the weights on the device from the seed and runs the
job's first calls, the first of which compiles; the window continues the
job with calls of the traffic's fixed round count, each from the last
call's params with the next seed, as the train driver's window does.

``correct`` follows the first two set-up calls with the plain float32
reference (``kinds/hybrid_lm.py``), client by client, on the same data:
every round's mean client loss, and each leaf's change after the first
call and after the second.
"""
from __future__ import annotations

import dataclasses
import functools
import time

import jax
import numpy as np

from chipbench import tasks
from chipbench.harness import BenchError, Check, Outcome, load_module

#: set-up calls that the reference follows
FOLLOWED = 2

_train = load_module("drivers", "train")
call_seed, alphas = _train.call_seed, _train.alphas


def token_block(seed, rounds, clients, support, seq, vocab,
                zipf_a=(1.05, 1.6), succ_p=(0.1, 0.4)):
    """One call's support sequences, ``x`` and next-token labels ``y``
    (-1 on each sequence's last position), (rounds, clients, support,
    seq) int32. Each (round, client) is a domain: its Zipf exponent,
    permutation, successor table and successor probability are drawn
    first, then every sequence's Zipf ranks and successor coins as one
    array each; the bigram chain then runs along the sequence for all
    sequences at once."""
    rng = np.random.default_rng(seed)
    n = rounds * clients
    a = rng.uniform(*zipf_a, size=n)
    perm = np.argsort(rng.random((n, vocab)), axis=1)
    succ = rng.integers(0, vocab, size=(n, vocab))
    p = rng.uniform(*succ_p, size=n)
    ranks = rng.zipf(np.repeat(a, support)[:, None], size=(n * support, seq))
    coin = rng.random((n * support, seq)) < np.repeat(p, support)[:, None]
    dom = np.repeat(np.arange(n), support)
    tok = perm[dom[:, None], np.clip(ranks - 1, 0, vocab - 1)]
    for t in range(1, seq):
        tok[:, t] = np.where(coin[:, t], succ[dom, tok[:, t - 1]],
                             tok[:, t])
    x = tok.astype(np.int32).reshape(rounds, clients, support, seq)
    y = np.concatenate([x[..., 1:], np.full(x.shape[:-1] + (1,), -1,
                                             np.int32)], axis=-1)
    return {"x": x, "y": y}


def call_data(run, k):
    tr, cfg = run.cell.traffic, run.cell.config
    return token_block(call_seed(run.seed, k), tr["rounds_per_call"],
                       tr["cohort"], tr["support"], tr["seq"],
                       cfg["vocab_size"])


#: the registered config's fields that the benchmark config gives under
#: the published names, and must equal
WIDTHS = {"hidden_size": "d_model", "num_attention_heads": "num_heads",
          "num_key_value_heads": "num_kv_heads",
          "shared_intermediate_size": "d_ff", "mamba_d_state": "ssm_state",
          "mamba_d_head": "ssm_head_dim", "mamba_expand": "ssm_expand",
          "mamba_d_conv": "ssm_conv_width", "mamba_chunk_size": "ssm_chunk",
          "rms_norm_eps": "norm_eps",
          "embedding_multiplier": "embedding_multiplier",
          "residual_multiplier": "residual_multiplier",
          "attention_multiplier": "attention_multiplier",
          "logits_scaling": "logits_scaling"}


def arch_config(cfg):
    """The registered architecture with the benchmark config's cut."""
    from repro.configs import get_arch
    try:
        arch = get_arch(cfg["registered"])
    except KeyError as e:
        raise BenchError(f"the program registers no {cfg['registered']!r}: "
                         f"{e}") from None
    for key, field in WIDTHS.items():
        if float(cfg[key]) != float(getattr(arch, field)):
            raise BenchError(f"{cfg['name']}: {key} {cfg[key]} differs from "
                             f"the registered {field} "
                             f"{getattr(arch, field)}")
    if list(arch.layer_types) != cfg["layer_types"]:
        raise BenchError(f"{cfg['name']}: layer_types differ from the "
                         f"registered config's")
    return dataclasses.replace(arch, num_layers=cfg["num_hidden_layers"],
                               vocab_size=cfg["vocab_size"],
                               dtype="float32")


def _program(run, arch=None):
    """The system under test, built as a user builds it."""
    from repro.core import run_federated
    from repro.core.strategies import TinyReptileStrategy
    from repro.data.lm import lm_loss
    from repro.data.tasks import ClientTask, TaskDistribution
    from repro.models.transformer import build_model

    tr = run.cell.traffic
    if tr["strategy"] != "tinyreptile":
        raise BenchError(f"train_lm driver: no strategy {tr['strategy']!r}")
    if run.cell.chips != 1:
        raise BenchError("train_lm driver: one chip only")
    model = build_model(arch or arch_config(run.cell.config))
    strategy = TinyReptileStrategy(lm_loss(model))

    class CallTasks(TaskDistribution):
        """One call's client data, made in bulk the first time the
        engine's sampler asks, handed out in the engine's order (see the
        train driver's twin)."""

        def __init__(self, k):
            self.k, self.data = k, None
            self.next_client = self.next_round = 0

        def _data(self):
            if self.data is None:
                self.data = call_data(run, self.k)
            return self.data

        def sample_task(self, rng):
            d = self._data()
            r, c = divmod(self.next_client, tr["cohort"])
            self.next_client += 1
            samples = zip(d["x"][r, c], d["y"][r, c])
            return ClientTask(make_sample=lambda _rng: next(samples),
                              task_id=self.next_client - 1)

        def sample_support_block(self, rng, rounds, clients, support,
                                 data_mode="batch", participation=None):
            d = self._data()
            r0, self.next_round = self.next_round, self.next_round + rounds
            return self._mask_block({k: v[r0:r0 + rounds].copy()
                                     for k, v in d.items()}, participation)

    kw = dict(rounds=tr["rounds_per_call"], clients_per_round=tr["cohort"],
              support=tr["support"], alpha=tr["alpha"], beta=tr["beta"],
              anneal=tr["anneal"])

    def call(box, k, tracker=None):
        """One call from the params in ``box``, which it empties: the
        engine copies its input, and no reference to the input may stay
        behind to hold a second copy of the weights on the device. The
        call's data are made first, while the chip finishes the last call;
        then the call waits for it, since the engine's copy of phi does not
        fit beside the last call's working copies."""
        data = CallTasks(k)
        data._data()
        jax.block_until_ready(box[0])
        return run_federated(box.pop(), data, strategy,
                             seed=call_seed(run.seed, k), tracker=tracker,
                             **kw)["params"]
    return call


def flat_host(tree):
    """Leaves by path, float32 on the host."""
    return {jax.tree_util.keystr(path): np.asarray(leaf, np.float32)
            for path, leaf in jax.tree_util.tree_leaves_with_path(tree)}


def setup(run, arch=None):
    """Weights from the seed, then the job's first calls, each with a
    tracker for its rounds' losses. Returns the call, the initial weights
    and each followed call's (params, losses) on the host, the live
    params, and the engine's clients per pass."""
    from repro.metering import MetricsTracker

    cfg, kind = run.cell.config, run.cell.kind
    call = _program(run, arch)
    run.mark("program")
    # committed to the chip, as the engine's outputs are: the first call
    # then compiles the program the later calls run
    phi = jax.device_put(jax.jit(functools.partial(kind.init, cfg))(
        tasks.weights_key(run.seed)), run.devices[0])
    phi0_host = jax.tree.map(lambda a: np.asarray(a, np.float32), phi)
    run.mark("weights")
    followed, per_pass = [], None
    for k in range(FOLLOWED):
        tracker = MetricsTracker()
        box = [phi]
        del phi
        phi = call(box, k, tracker)
        followed.append((jax.tree.map(np.asarray, phi), np.asarray(
            tracker.series_values("round.inner_loss"), np.float64)))
        per_pass = tracker.gauges.get("engine.clients_per_pass", per_pass)
        run.mark("first_call" if k == 0 else f"call_{k + 1}")
    return call, phi0_host, followed, phi, per_pass


def follow(run, phi0_host, dtype=np.float32, **fault):
    """The plain reference over the followed calls' data."""
    tr = run.cell.traffic
    calls = [dict(call_data(run, k), alpha=alphas(tr))
             for k in range(FOLLOWED)]
    return run.cell.kind.tinyreptile(run.cell.config, phi0_host, calls,
                                     tr["beta"], dtype=dtype, **fault)


def readings(phi0, got, ref):
    """The train driver's compared numbers over flattened trees."""
    flat = [(flat_host(p), lo) for p, lo in got]
    return _train.readings(flat_host(phi0), flat,
                           [(flat_host(p), lo) for p, lo in ref])


def measure(run) -> Outcome:
    cfg, tr, kind = run.cell.config, run.cell.traffic, run.cell.kind
    call, phi0_host, followed, phi, per_pass = setup(run)
    calls = 0
    with run.window():
        t0 = time.perf_counter()
        while True:
            with run.span("bench.call"):
                box = [phi]
                del phi
                phi = call(box, FOLLOWED + calls)
            calls += 1
            run.host.lap("call")
            if time.perf_counter() - t0 >= run.seconds:
                break
        with run.span("bench.sync"):
            jax.block_until_ready(phi)
        run.host.lap("sync")
    rounds = calls * tr["rounds_per_call"]
    finite = all(bool(np.isfinite(np.asarray(v)).all())
                 for v in jax.tree.leaves(phi))
    del phi

    got = readings(phi0_host, followed, follow(run, phi0_host))
    checks = [Check(n, v, tr["limits"][n]) for n, v in got.items()]
    checks.append(Check("nonfinite_params", 0.0 if finite else 1.0, 0.0))
    tokens = tr["cohort"] * tr["support"] * tr["seq"]
    return Outcome(
        metrics={"rounds_per_s": rounds / run.window_s,
                 "tokens_per_s": rounds * tokens / run.window_s,
                 "clients_per_pass": per_pass},
        attempted=rounds, failed=0 if finite else rounds, checks=checks,
        counts={"rounds": rounds, "calls": calls,
                "clients_per_pass": per_pass,
                "flops_per_round": kind.train_flops(cfg, tr["seq"])
                * tr["support"] * tr["cohort"]})
