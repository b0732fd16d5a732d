"""Training launcher: federated meta-training (TinyReptile rounds) of any
--arch over heterogeneous synthetic LM clients, with checkpointing.

``--strategy reptile|fedavg|fedsgd|transfer|tifed`` switches to the
round engine (repro.core.run_federated) — by default on the paper's
sine workload; ``--arch transformer|mamba2|moe`` swaps in next-token
personalization of the family's reduced config over heterogeneous LM
clients. ``tifed`` runs TIFeD integer-only int8 local training with
native int8 uplink billing. ``--devices N`` (or ``--mesh clients:K``)
shards the client axis over a 1-D mesh; ``--mesh clients:K,model:M``
builds the 2-D (clients, model) mesh — cohort split K ways AND phi's
weight matrices split M ways per the family's ModelPartitioner.
Incompatible flag combos (e.g. ``--strategy transfer --buffer-size``,
``tifed`` with a model-sharded mesh) are rejected at parse time.

The fleet is persistent (one ``LMClientStream`` per client id).
``--participation`` thins check-ins i.i.d.; ``--availability
diurnal|markov`` replaces that with a structured check-in process over
the fleet (rounds where nobody is available are idle: no step, no
transport). ``--buffer-size K`` makes the server FedBuff-style async:
each round's client delta lands in a buffer that is applied only every
K arrivals, staleness-discounted (1/sqrt(1+tau)) — the launcher-scale
mirror of the round engine's ``BufferedAggregation``.

On this CPU container use --reduced (the full configs are dry-run only):

  PYTHONPATH=src python -m repro.launch.train --arch tinyllama-1.1b \
      --reduced --rounds 20 --seq 64 --batch 8 --k-inner 4

``--mesh data --devices N`` shards the fused round over a 1-D data mesh
(batch split across N devices, model GSPMD-sharded by the
repro.runtime.sharding rules); ``--mesh pod`` instead makes every
device ONE federated pod client (repro.core.federated pod-client mode:
inner SGD per pod, one cross-pod all-reduce per round). Both work on
CPU under XLA_FLAGS=--xla_force_host_platform_device_count=N. On a
real TPU pod the same entrypoint runs the full config under
make_production_mesh() with the sharding rules from
repro.runtime.sharding.
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import restore_checkpoint, save_checkpoint
from repro.configs import ALL_ARCHS, get_arch
from repro.core.engine import CommChannel, meta_interpolate, streaming_sgd
from repro.core.pipeline import (DEFAULT_SAMPLER, SAMPLERS,
                                 PartialParticipation, single_device_of)
from repro.core.pool import (DiurnalAvailability, MarkovAvailability,
                             default_staleness_weight)
from repro.data import LMClientStream
from repro.models import build_model
from repro.optim.schedules import linear_anneal
from repro.runtime.compile_cache import enable_compile_cache
from repro.runtime.sharding import init_distributed
from repro.runtime.steps import (make_meta_train_step, microbatch,
                                 prefetch_batches)


def fraction_arg(s: str) -> float:
    """argparse type: a fraction in (0, 1] — rejected AT PARSE TIME with
    a clear message instead of failing deep inside schedule planning."""
    try:
        v = float(s)
    except ValueError:
        raise argparse.ArgumentTypeError(f"expected a number, got {s!r}")
    if not 0.0 < v <= 1.0:
        raise argparse.ArgumentTypeError(
            f"must be a fraction in (0, 1], got {v}")
    return v


def positive_int_arg(s: str) -> int:
    try:
        v = int(s)
    except ValueError:
        raise argparse.ArgumentTypeError(f"expected an integer, got {s!r}")
    if v < 1:
        raise argparse.ArgumentTypeError(f"must be >= 1, got {v}")
    return v


ENGINE_STRATEGIES = ("reptile", "fedavg", "fedsgd", "transfer", "tifed")

#: engine-path --arch family keywords -> canonical arch configs (run
#: REDUCED there: the engine trains every cohort client per round, so
#: the full configs are far beyond this container); each family also
#: names a registered ModelPartitioner for --mesh clients:K,model:M
ARCH_FAMILIES = {"transformer": "tinyllama-1.1b",
                 "mamba2": "mamba2-130m",
                 "moe": "mixtral-8x22b"}


def mesh_arg(s: str):
    """argparse type for --mesh: the LM launcher keywords
    ('none'|'data'|'pod') pass through; an engine mesh spec
    'clients:K[,model:M]' parses to a {'clients': K[, 'model': M]}
    dict — rejected AT PARSE TIME on malformed axis names/extents."""
    if s in ("none", "data", "pod"):
        return s
    spec = {}
    for part in s.split(","):
        name, sep, extent = part.partition(":")
        if not sep or name not in ("clients", "model") or name in spec:
            raise argparse.ArgumentTypeError(
                f"expected 'none', 'data', 'pod', or "
                f"'clients:K[,model:M]', got {s!r}")
        try:
            v = int(extent)
        except ValueError:
            raise argparse.ArgumentTypeError(
                f"mesh axis extent must be an integer, got {extent!r}")
        if v < 1:
            raise argparse.ArgumentTypeError(
                f"mesh axis extent must be >= 1, got {v}")
        spec[name] = v
    if "clients" not in spec:
        raise argparse.ArgumentTypeError(
            f"an engine mesh spec needs a clients axis: "
            f"'clients:K[,model:M]', got {s!r}")
    return spec


def parse_args(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--strategy", default="tinyreptile",
                    choices=("tinyreptile",) + ENGINE_STRATEGIES,
                    help="'tinyreptile' (default) runs this LM launcher; "
                         "any other choice runs the round engine "
                         "(repro.core.run_federated) on the paper's sine "
                         "workload — 'tifed' is integer-only int8 local "
                         "training with native int8 uplinks")
    ap.add_argument("--arch",
                    choices=list(ALL_ARCHS) + sorted(ARCH_FAMILIES),
                    help="LM architecture. Canonical names "
                         "(tinyllama-1.1b, ...) run the tinyreptile LM "
                         "launcher; the family keywords "
                         "transformer|mamba2|moe ALSO work with engine "
                         "strategies (--strategy reptile|...), which "
                         "then meta-train the reduced config over "
                         "heterogeneous LM clients instead of the paper "
                         "sine MLP")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--rounds", type=int, default=20)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--k-inner", type=int, default=4)
    ap.add_argument("--beta", type=float, default=0.02)
    ap.add_argument("--alpha", type=float, default=1.0)
    ap.add_argument("--clients", type=int, default=64)
    ap.add_argument("--pool-size", type=positive_int_arg, default=None,
                    help="size of the persistent client fleet (overrides "
                         "--clients; every client keeps its own data "
                         "stream across check-ins)")
    ap.add_argument("--pool-sampler", default=DEFAULT_SAMPLER,
                    choices=SAMPLERS,
                    help="host data path for --pool-size and its "
                         "--availability/--participation policy: "
                         "'vectorized' (default) derives each "
                         "check-in from a counter array — O(cohort) "
                         "host work and an O(N) int32 footprint; "
                         "'reference' keeps one RNG per client on the "
                         "host (bit-for-bit legacy stream)")
    ap.add_argument("--pool-residency", default="device",
                    choices=("device", "host"),
                    help="where --pool-size per-client state lives: "
                         "'device' keeps the full (N,) arrays resident; "
                         "'host' keeps them in host slabs and stages "
                         "only each round's cohort rows")
    ap.add_argument("--participation", type=fraction_arg, default=1.0,
                    help="fraction of the client fleet that checks in "
                         "each round (a PartialParticipation schedule "
                         "over the pool); each round's training client "
                         "is drawn among that round's participants; "
                         "must be in (0, 1]")
    ap.add_argument("--availability", default="iid",
                    choices=("iid", "diurnal", "markov"),
                    help="structured check-in process over the fleet "
                         "(diurnal sine / two-state Markov); rounds "
                         "where nobody is available are idle")
    ap.add_argument("--buffer-size", type=positive_int_arg, default=None,
                    help="FedBuff-style async server: apply buffered "
                         "client deltas only every K arrivals, "
                         "staleness-discounted")
    ap.add_argument("--devices", type=positive_int_arg, default=None,
                    help="use the first N jax devices (default: all "
                         "when --mesh is set; CPU runs force host "
                         "devices via XLA_FLAGS="
                         "--xla_force_host_platform_device_count)")
    ap.add_argument("--mesh", default="none", type=mesh_arg,
                    help="shard the round across devices: 'data' runs "
                         "the fused cohort step on a 1-D data mesh "
                         "(batch split, GSPMD-sharded model); 'pod' "
                         "treats each device as one federated pod "
                         "client (repro.core.federated pod-client "
                         "mode: inner SGD per pod, one cross-pod "
                         "all-reduce per round); 'clients:K[,model:M]' "
                         "runs an engine strategy on a 1-D client mesh "
                         "(K-way cohort split) or a 2-D (clients, "
                         "model) mesh (phi's weight matrices "
                         "additionally split M ways per the family's "
                         "ModelPartitioner); 'none' (default) stays "
                         "single-device")
    ap.add_argument("--coordinator", default=None,
                    help="jax.distributed coordinator address host:port "
                         "for multi-process runs; required with "
                         "--num-processes > 1 (every process passes the "
                         "SAME address) and meaningless without it")
    ap.add_argument("--num-processes", type=positive_int_arg, default=1,
                    help="total process count of a cross-host run; the "
                         "client mesh (--devices) then spans every "
                         "process's devices and each process stages its "
                         "local shard only")
    ap.add_argument("--process-id", type=int, default=0,
                    help="this process's rank in [0, --num-processes)")
    ap.add_argument("--ckpt-dir", default=None,
                    help="snapshot directory: the LM launcher saves phi "
                         "every --ckpt-every rounds; engine strategies "
                         "snapshot the FULL round state (phi, pool "
                         "state, rng, bills) on a background thread and "
                         "resume bit-for-bit via --resume")
    ap.add_argument("--ckpt-every", type=positive_int_arg, default=10)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    if args.num_processes > 1 and not args.coordinator:
        ap.error("--num-processes > 1 is a cross-host run; pass the "
                 "shared --coordinator host:port")
    if args.coordinator and args.num_processes == 1:
        ap.error("--coordinator only applies with --num-processes > 1")
    if not 0 <= args.process_id < args.num_processes:
        ap.error(f"--process-id {args.process_id} out of range for "
                 f"--num-processes {args.num_processes}")
    if args.num_processes > 1 and args.strategy not in ENGINE_STRATEGIES:
        ap.error("multi-process runs drive the round engine; pass an "
                 f"engine --strategy ({'|'.join(ENGINE_STRATEGIES)})")
    if args.num_processes > 1:
        # must precede the first jax.devices() call below: after
        # initialize, the device list spans every process in the run
        init_distributed(args.coordinator, args.num_processes,
                         args.process_id)
    if args.resume and not args.ckpt_dir:
        ap.error("--resume restores from --ckpt-dir; pass both")
    if args.availability != "iid" and args.participation < 1.0:
        ap.error("--availability replaces the i.i.d. --participation "
                 "schedule; pass one or the other")
    if args.mesh == "pod" and args.buffer_size:
        ap.error("--mesh pod runs the fused pod-client round; FedBuff "
                 "buffering (--buffer-size) needs the split inner/flush "
                 "step — pass one or the other")
    # incompatible flag combos are rejected HERE, not deep inside the
    # engine (the --participation precedent from PR 4)
    if args.strategy == "tinyreptile":
        if args.arch is None:
            ap.error("--arch is required for the tinyreptile LM launcher "
                     "(engine strategies --strategy "
                     f"{'|'.join(ENGINE_STRATEGIES)} default to the "
                     "paper sine workload instead)")
        if isinstance(args.mesh, dict):
            ap.error("--mesh clients:K[,model:M] drives the round "
                     "engine; pass an engine --strategy "
                     f"({'|'.join(ENGINE_STRATEGIES)})")
        if args.devices is not None and args.mesh == "none":
            ap.error("--devices only applies with --mesh data|pod (or "
                     "with an engine --strategy, where it sizes the "
                     "client mesh)")
        # family keyword -> the canonical config it names
        args.arch = ARCH_FAMILIES.get(args.arch, args.arch)
        return args
    if args.arch is not None and args.arch not in ARCH_FAMILIES:
        ap.error(f"--strategy {args.strategy} meta-trains a reduced LM "
                 f"family (--arch {'|'.join(sorted(ARCH_FAMILIES))}) or, "
                 f"without --arch, the paper sine MLP; the canonical "
                 f"config {args.arch!r} runs the tinyreptile LM launcher")
    if args.arch is not None and args.strategy == "tifed":
        ap.error("--strategy tifed runs TIFeD integer-only training on "
                 "the paper's ReLU sine net; the LM families are fp32 — "
                 "drop --arch")
    if args.mesh in ("data", "pod"):
        ap.error(f"--strategy {args.strategy} shards the client axis "
                 f"via --devices N or --mesh clients:K[,model:M]; "
                 f"--mesh data|pod belongs to the LM launcher")
    if isinstance(args.mesh, dict):
        spec = ",".join(f"{k}:{v}" for k, v in args.mesh.items())
        if args.devices is not None:
            ap.error(f"--mesh {spec} already sizes the client mesh; "
                     f"drop --devices")
        if "model" in args.mesh and args.strategy == "tifed":
            ap.error("--strategy tifed uplinks NATIVE int8 trees whose "
                     "quantization grids need each parameter tensor "
                     "whole on every device; a model-sharded mesh "
                     "splits them — use --mesh clients:K (no model "
                     "axis)")
        need = args.mesh["clients"] * args.mesh.get("model", 1)
        if need > len(jax.devices()):
            ap.error(f"--mesh {spec} needs {need} devices; only "
                     f"{len(jax.devices())} visible (force host devices "
                     f"via XLA_FLAGS)")
    if args.strategy == "transfer" and args.buffer_size:
        ap.error("--strategy transfer uplinks raw client batches "
                 "(uplink_ref='none'); the FedBuff buffer stages "
                 "phi-shaped updates and cannot hold them — drop "
                 "--buffer-size")
    if args.buffer_size and args.pool_size is None:
        ap.error("--buffer-size (FedBuff) needs persistent clients to "
                 "be stale against on the engine path: pass "
                 "--pool-size N too")
    if args.availability != "iid" and args.pool_size is None:
        ap.error("--availability needs a persistent fleet on the engine "
                 "path: pass --pool-size N")
    if args.pool_size is None and (args.pool_sampler != DEFAULT_SAMPLER
                                   or args.pool_residency != "device"):
        ap.error("--pool-sampler/--pool-residency configure the "
                 "persistent fleet: pass --pool-size N")
    if args.pool_size is not None and args.pool_size < args.clients:
        ap.error(f"--pool-size {args.pool_size} cannot seat a cohort of "
                 f"--clients {args.clients} (identities are unique "
                 f"within a round)")
    if args.devices is not None and args.devices > len(jax.devices()):
        ap.error(f"--devices {args.devices}: only {len(jax.devices())} "
                 f"devices visible (force host devices via XLA_FLAGS)")
    return args


def run_engine_strategy(args):
    """--strategy reptile|fedavg|fedsgd|transfer|tifed: one round-engine
    run (repro.core.run_federated) on the paper's sine workload, with
    the launcher's fleet flags mapped onto the engine's plugins
    (--pool-size -> ClientPool, --participation/--availability ->
    SamplingPolicy, --buffer-size -> BufferedAggregation, --devices or
    --mesh clients:K[,model:M] -> client / client-model mesh). tifed
    runs integer-only local training and bills its native int8 uplinks;
    everything else is the fp32 engine path. --arch
    transformer|mamba2|moe swaps the sine workload for next-token
    personalization of the family's REDUCED config over heterogeneous
    LM clients (LmTaskDistribution); with a model axis on the mesh, phi
    is sharded per the family's registered ModelPartitioner.
    --ckpt-dir arms the engine's round-state snapshotter (background
    writer, every --ckpt-every rounds) and --resume continues a
    preempted run bit-for-bit — including past the original --rounds
    horizon. Prints one summary JSON row."""
    import functools

    from repro.configs.paper_models import SINE_MLP
    from repro.core import (BufferedAggregation, ClientPool, run_federated)
    from repro.core.strategies import (FedAvgStrategy, FedSGDStrategy,
                                       ReptileStrategy, TifedStrategy,
                                       TransferStrategy)
    from repro.data import LmTaskDistribution, SineTasks, lm_loss
    from repro.models.paper_nets import (init_paper_model, paper_model_loss,
                                         relu_mlp_loss)
    from repro.runtime.sharding import client_model_mesh, partitioner_for

    if args.arch is not None:
        # family keyword -> the canonical config, reduced for the
        # engine's every-client-every-round cost profile
        cfg = get_arch(ARCH_FAMILIES[args.arch]).reduced()
        model = build_model(cfg)
        loss = lm_loss(model)
        dist = LmTaskDistribution(cfg.vocab_size, args.seq)
        params = model.init(jax.random.PRNGKey(args.seed))
        support = args.batch
        eval_kwargs = dict(num_tasks=2, support=4, k_steps=4, lr=0.01,
                           query=8)
    else:
        loss = functools.partial(paper_model_loss, SINE_MLP)
        dist = SineTasks()
        params = init_paper_model(SINE_MLP, jax.random.PRNGKey(args.seed))
        support = 32
        # eval finetune rate: the tanh paper net takes 0.02; tifed's
        # ReLU net diverges there at k_steps 16 — 0.005 is safe
        eval_kwargs = dict(num_tasks=5, support=10, k_steps=16,
                           lr=0.005 if args.strategy == "tifed" else 0.02,
                           query=20)
    mesh = args.devices
    partitioner = None
    if isinstance(args.mesh, dict):
        if "model" in args.mesh:
            mesh = client_model_mesh(args.mesh["clients"],
                                     args.mesh["model"])
            # the family's registered partitioner; the sine MLP takes
            # the default matrix-sharding rules
            partitioner = partitioner_for(args.arch or "default")
        else:
            mesh = args.mesh["clients"]     # 1-D client mesh
    strategy = {
        "reptile": lambda: ReptileStrategy(loss, epochs=8),
        "fedavg": lambda: FedAvgStrategy(loss, epochs=8),
        "fedsgd": lambda: FedSGDStrategy(loss),
        "transfer": lambda: TransferStrategy(loss),
        "tifed": lambda: TifedStrategy(relu_mlp_loss, epochs=8),
    }[args.strategy]()
    channel = (CommChannel("int8", quantize=False)
               if args.strategy == "tifed" else CommChannel())
    pool = (ClientPool(dist, args.pool_size, seed=args.seed,
                       sampler=args.pool_sampler,
                       residency=args.pool_residency)
            if args.pool_size else None)
    if args.availability == "diurnal":
        sampling = DiurnalAvailability(period=24,
                                       sampler=args.pool_sampler)
    elif args.availability == "markov":
        sampling = MarkovAvailability(sampler=args.pool_sampler)
    elif args.participation < 1.0:
        sampling = PartialParticipation(args.participation,
                                        sampler=args.pool_sampler)
    else:
        sampling = None
    buffered = (BufferedAggregation(args.buffer_size)
                if args.buffer_size else None)
    t0 = time.time()
    out = run_federated(
        params, dist, strategy, rounds=args.rounds,
        clients_per_round=args.clients, alpha=args.alpha, beta=args.beta,
        support=support, seed=args.seed, eval_every=args.rounds,
        eval_kwargs=eval_kwargs,
        channel=channel, sampling=sampling, pool=pool, buffered=buffered,
        mesh=mesh, partitioner=partitioner, ckpt_dir=args.ckpt_dir,
        ckpt_every=args.ckpt_every, resume=args.resume)
    jax.block_until_ready(jax.tree.leaves(out["params"])[0])
    row = {"strategy": args.strategy, "rounds": args.rounds,
           "clients": args.clients, "dt_s": round(time.time() - t0, 3)}
    if args.arch is not None:
        row["arch"] = args.arch
    if isinstance(args.mesh, dict):
        row["mesh"] = ",".join(f"{k}:{v}" for k, v in args.mesh.items())
    if out["history"]:
        row["query_loss"] = round(float(out["history"][-1]["query_loss"]),
                                  4)
    if "comm_bytes" in out:
        row["comm_mb"] = round(out["comm_bytes"] / 2 ** 20, 3)
    print(json.dumps(row), flush=True)


def main():
    enable_compile_cache()
    args = parse_args()
    if args.strategy != "tinyreptile":
        return run_engine_strategy(args)

    cfg = get_arch(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    model = build_model(cfg)
    phi = model.init(jax.random.PRNGKey(args.seed))
    start_round = 0
    if args.resume and args.ckpt_dir:
        try:
            phi, start_round, _ = restore_checkpoint(args.ckpt_dir, phi)
            print(f"resumed from round {start_round}")
        except FileNotFoundError:
            pass

    fleet = args.pool_size or args.clients
    clients = [LMClientStream(cfg.vocab_size, cid) for cid in range(fleet)]
    alpha_sched = linear_anneal(args.alpha, args.rounds, floor=args.alpha * 0.1)
    rng = np.random.default_rng(args.seed)

    # device-availability schedule over the persistent fleet: with
    # --participation < 1 only a subset checks in each round (i.i.d.);
    # --availability swaps that for a diurnal/Markov process whose
    # troughs can leave a round with NOBODY available (idle round).
    # The round's training client is drawn among the participants.
    # Transport is billed per non-idle round at the paper's fp32
    # accounting.
    checkin = None
    # bill the full trajectory on resume (the old absolute-round
    # formula), minus any pre-resume idle rounds under --availability
    billed_rounds = start_round
    if args.availability != "iid":
        proc = (DiurnalAvailability(period=24)
                if args.availability == "diurnal" else MarkovAvailability())
        full = np.asarray(proc.availability(rng, 0, args.rounds, fleet),
                          bool)
        billed_rounds = int(full[:start_round].any(axis=1).sum())
        checkin = full[start_round:]
    elif args.participation < 1.0:
        checkin = PartialParticipation(args.participation).plan_schedule(
            rng, start_round, args.rounds, fleet,
            args.k_inner)["participation"]
    channel = CommChannel()
    round_bill = 2 * channel.payload_bytes(phi)     # downlink + uplink

    # --mesh builds the device mesh the round runs on: 'data' shards the
    # batch (GSPMD shards the model via repro.runtime.sharding rules),
    # 'pod' makes every device one federated pod client
    # (repro.core.federated pod-client mode). shardctx.mesh_context is
    # entered for the whole loop so the model's internal constraints
    # resolve at trace time; batch staging below device_puts with the
    # matching NamedSharding instead of a bare single-device put.
    mesh = None
    batch_sharding = None
    if args.mesh != "none":
        from jax.sharding import Mesh, NamedSharding, PartitionSpec
        devs = jax.devices()
        n = args.devices or len(devs)
        if n > len(devs):
            raise SystemExit(f"--devices {n}: only {len(devs)} devices "
                             f"visible (force host devices via XLA_FLAGS)")
        if args.mesh == "data":
            mesh = Mesh(np.array(devs[:n]), ("data",))
            batch_axis = "data"
        else:
            mesh = Mesh(np.array(devs[:n]).reshape(n, 1), ("pod", "data"))
            batch_axis = "pod"
        mb = args.batch // args.k_inner
        if mb % n:
            raise SystemExit(f"--mesh {args.mesh}: the per-step "
                             f"microbatch ({mb} = --batch/--k-inner) "
                             f"must divide over {n} devices")

        def batch_sharding(leaf_ndim):
            return NamedSharding(mesh, PartitionSpec(
                *([None, batch_axis] + [None] * (leaf_ndim - 2))))

        phi = jax.device_put(phi, NamedSharding(mesh, PartitionSpec()))

    from contextlib import ExitStack
    from repro.runtime.shardctx import mesh_context
    stack = ExitStack()
    if mesh is not None:
        stack.enter_context(mesh_context(mesh))

    if args.mesh == "pod":
        from repro.core.federated import make_pod_client_meta_step
        step = jax.jit(make_pod_client_meta_step(model, mesh,
                                                 beta=args.beta,
                                                 alpha=args.alpha),
                       donate_argnums=(0,))
    else:
        step = jax.jit(make_meta_train_step(model, beta=args.beta,
                                            alpha=args.alpha),
                       donate_argnums=(0,))
    # FedBuff mode splits the fused round: the inner stream runs
    # immediately, the server interpolation is deferred to the flush
    # (phi is NOT donated — the delta needs it)
    inner = jax.jit(lambda p, b: streaming_sgd(model.loss_fn, p, b,
                                               args.beta))
    buffer = []                 # (round, delta) pairs awaiting a flush
    flushes = 0

    def flush_buffer(phi, flush_rnd, alpha_t):
        """Apply the buffered deltas, staleness-discounted and
        normalized, as one meta step. Also called to DRAIN the buffer
        before checkpoints and at run end — pending updates must not be
        silently dropped (a resume would otherwise lose up to
        buffer_size - 1 rounds of client work)."""
        taus = jnp.asarray([float(flush_rnd - r) for r, _ in buffer])
        ws = default_staleness_weight(taus)
        ws = ws / ws.sum()
        mean_delta = jax.tree.map(
            lambda *ds: sum(w * d for w, d in zip(ws, ds)),
            *[d for _, d in buffer])
        phi_hat = jax.tree.map(jnp.add, phi, mean_delta)
        buffer.clear()
        return meta_interpolate(phi, phi_hat, alpha_t, use_pallas=False)

    device = single_device_of(phi)      # staging target for the prefetcher

    def make_round_batch(i):
        # TinyReptile serial schema: ONE client per round. Runs on the
        # prefetch thread, strictly in round order, so the seeded rng
        # draws exactly the synchronous sequence while batch building +
        # device staging for round N+1 hide behind the step on round N.
        rnd = start_round + i
        if checkin is None:
            client = clients[int(rng.integers(len(clients)))]
        else:
            avail = np.flatnonzero(checkin[i])
            if len(avail) == 0:
                return rnd, None, float(alpha_sched(rnd)), None
            client = clients[int(avail[rng.integers(len(avail))])]
        raw = client.batch(rng, args.batch, args.seq)
        batch = {}
        if cfg.frontend == "vision":
            batch["patch_embeds"] = np.asarray(
                rng.normal(size=(args.batch, cfg.frontend_tokens,
                                 cfg.d_model)), np.float32)
        if cfg.family == "audio":
            batch["frames"] = np.asarray(
                rng.normal(size=(args.batch, cfg.encoder_tokens,
                                 cfg.d_model)), np.float32)
        batch["tokens"] = raw["tokens"]
        batch["labels"] = raw["labels"]
        batch = microbatch(batch, args.k_inner)
        if batch_sharding is not None:
            # mesh staging: split the microbatch dim across the mesh's
            # batch axis instead of a bare single-device put
            batch = jax.device_put(batch, jax.tree.map(
                lambda a: batch_sharding(np.asarray(a).ndim), batch))
        else:
            batch = jax.device_put(batch, device)
        return rnd, client.zipf_a, float(alpha_sched(rnd)), batch

    staged = prefetch_batches(make_round_batch, args.rounds - start_round)
    for rnd, zipf_a, alpha_t, batch in staged:
        t0 = time.time()
        if batch is None:                   # availability trough: idle
            print(json.dumps({"round": rnd, "idle": True,
                              "alpha": alpha_t}), flush=True)
            continue
        if args.buffer_size:
            phi_hat, losses = inner(phi, batch)
            buffer.append((rnd, jax.tree.map(jnp.subtract, phi_hat, phi)))
            metrics = {"loss": losses.mean(), "inner_first": losses[0],
                       "inner_last": losses[-1]}
            if len(buffer) >= args.buffer_size:
                phi = flush_buffer(phi, rnd, alpha_t)
                flushes += 1
        else:
            phi, metrics = step(phi, batch, jnp.float32(alpha_t))
        billed_rounds += 1
        comm_bytes = billed_rounds * round_bill
        row = {"round": rnd, "client": zipf_a,
               "loss": float(metrics["loss"]),
               "inner_first": float(metrics["inner_first"]),
               "inner_last": float(metrics["inner_last"]),
               "alpha": alpha_t, "comm_mb": round(comm_bytes / 2**20, 2),
               "dt_s": round(time.time() - t0, 3)}
        if args.buffer_size:
            row["buffered"] = len(buffer)
            row["flushes"] = flushes
        print(json.dumps(row), flush=True)
        if args.ckpt_dir and (rnd + 1) % args.ckpt_every == 0:
            if buffer:                      # checkpoints see ALL updates
                phi = flush_buffer(phi, rnd, alpha_t)
                flushes += 1
            save_checkpoint(args.ckpt_dir, phi, rnd + 1,
                            extra={"arch": args.arch})
    if buffer:                              # drain the pending tail
        phi = flush_buffer(phi, buffer[-1][0], float(alpha_sched(
            buffer[-1][0])))
        flushes += 1
    if args.ckpt_dir:
        save_checkpoint(args.ckpt_dir, phi, args.rounds,
                        extra={"arch": args.arch})
    stack.close()


if __name__ == "__main__":
    main()
