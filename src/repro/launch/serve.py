"""Serving launchers.

Two modes, picked by ``--mode`` with parse-time flag validation (flags
belonging to the other mode are rejected before any JAX work starts):

- ``decode`` (default, backward compatible): batched autoregressive LM
  decoding with a KV cache — fills a fixed batch of slots with prompts,
  prefills via teacher-forced decode steps, then decodes greedily.

      PYTHONPATH=src python -m repro.launch.serve --arch tinyllama-1.1b \
          --reduced --requests 6 --batch 2 --max-new 8

- ``adapt``: the TinyReptile deployment loop — a continuous-batching
  `serving.AdaptationServer` over the sine-MLP meta-init sustains a
  ragged stream of client-adaptation requests (fp32 online-SGD or
  int8 TIFeD epochs) and reports requests/sec + latency percentiles.

      PYTHONPATH=src python -m repro.launch.serve --mode adapt \
          --strategy fp32 --requests 512 --slots 64 --k-max 10

  ``--ckpt-dir`` serves a `run_federated(ckpt_dir=...)` snapshot's phi
  (via `checkpoint.load_params`) instead of a fresh seeded init.
"""
from __future__ import annotations

import argparse
import json
import time

import numpy as np


# flags that only make sense for one mode: (flag, argparse dest, default)
_DECODE_ONLY = (("--arch", "arch", None), ("--reduced", "reduced", False),
                ("--batch", "batch", 2), ("--prompt-len", "prompt_len", 8),
                ("--max-new", "max_new", 8), ("--cache-len", "cache_len", 64))
_ADAPT_ONLY = (("--strategy", "strategy", "fp32"), ("--slots", "slots", 64),
               ("--support", "support", 10), ("--k-max", "k_max", 10),
               ("--query", "query", 20),
               ("--steps-per-tick", "steps_per_tick", 5),
               ("--ckpt-dir", "ckpt_dir", None))


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--mode", choices=("decode", "adapt"), default="decode")
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--seed", type=int, default=0)
    # decode-mode flags
    ap.add_argument("--arch", default=None)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=2)
    ap.add_argument("--prompt-len", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=8)
    ap.add_argument("--cache-len", type=int, default=64)
    # adapt-mode flags
    ap.add_argument("--strategy", choices=("fp32", "tifed"), default="fp32")
    ap.add_argument("--slots", type=int, default=64)
    ap.add_argument("--support", type=int, default=10)
    ap.add_argument("--k-max", type=int, default=10)
    ap.add_argument("--query", type=int, default=20)
    ap.add_argument("--steps-per-tick", type=int, default=5)
    ap.add_argument("--ckpt-dir", default=None)
    return ap


def parse_args(argv=None) -> argparse.Namespace:
    """Parse + cross-validate BEFORE touching JAX: a decode flag on an
    adapt run (or vice versa) is a config mistake, not a silent
    default."""
    ap = build_parser()
    args = ap.parse_args(argv)
    wrong = _ADAPT_ONLY if args.mode == "decode" else _DECODE_ONLY
    for flag, dest, default in wrong:
        if getattr(args, dest) != default:
            ap.error(f"{flag} only applies with --mode "
                     f"{'adapt' if args.mode == 'decode' else 'decode'}")
    if args.requests < 1:
        ap.error(f"--requests must be >= 1, got {args.requests}")
    if args.mode == "decode":
        from repro.configs import ALL_ARCHS
        if args.arch is None:
            ap.error("--arch is required for --mode decode")
        if args.arch not in ALL_ARCHS:
            ap.error(f"--arch {args.arch!r} not in "
                     f"{sorted(ALL_ARCHS)}")
    else:
        if args.slots < 1:
            ap.error(f"--slots must be >= 1, got {args.slots}")
        if args.k_max < 1:
            ap.error(f"--k-max must be >= 1, got {args.k_max}")
        if args.steps_per_tick < 1:
            ap.error(f"--steps-per-tick must be >= 1, got "
                     f"{args.steps_per_tick}")
        if args.strategy == "fp32" and args.k_max > args.support:
            ap.error(f"--k-max {args.k_max} online steps need --support "
                     f">= k-max, got {args.support}")
        if args.strategy == "tifed" and args.support & (args.support - 1):
            ap.error(f"--support must be a power of two for tifed "
                     f"(bit-shift batch mean), got {args.support}")
    return args


def run_decode(args):
    import jax
    import jax.numpy as jnp

    from repro.configs import get_arch
    from repro.models import build_model

    cfg = get_arch(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(args.seed))
    decode = jax.jit(model.decode_fn, donate_argnums=())

    rng = np.random.default_rng(args.seed)
    queue = [rng.integers(0, cfg.vocab_size, size=args.prompt_len)
             for _ in range(args.requests)]
    done = []
    B = args.batch

    # NOTE: per-slot cache_len requires the batched cache variant; this
    # loop advances all slots in lockstep (same prompt length) — the
    # standard static-batching baseline. Continuous batching with
    # per-slot offsets is what --mode adapt does for the adaptation
    # workload.
    t_start = time.time()
    tokens_out = 0
    while queue:
        wave, queue = queue[:B], queue[B:]
        while len(wave) < B:
            wave.append(np.zeros(args.prompt_len, np.int64))  # pad slot
        cache = model.init_cache(B, args.cache_len)
        prompts = jnp.asarray(np.stack(wave), jnp.int32)
        # prefill via decode steps (teacher forcing)
        logits = None
        for t in range(args.prompt_len):
            batch = {"tokens": prompts[:, t:t + 1], "cache": cache,
                     "cache_len": jnp.int32(t)}
            logits, cache = decode(params, batch)
        outs = [[] for _ in range(B)]
        for t in range(args.max_new):
            nxt = jnp.argmax(logits[:, 0], axis=-1).astype(jnp.int32)
            for i in range(B):
                outs[i].append(int(nxt[i]))
            batch = {"tokens": nxt[:, None], "cache": cache,
                     "cache_len": jnp.int32(args.prompt_len + t)}
            logits, cache = decode(params, batch)
            tokens_out += B
        done.extend(outs)
    dt = time.time() - t_start
    print(json.dumps({
        "arch": cfg.name, "requests": args.requests,
        "tokens_generated": tokens_out, "wall_s": round(dt, 2),
        "tok_per_s": round(tokens_out / dt, 1),
        "sample_output": done[0][:8]}, indent=1))


def run_adapt(args):
    import functools

    import jax

    from repro.configs.paper_models import SINE_MLP
    from repro.metering import MetricsTracker
    from repro.models.paper_nets import init_paper_model, paper_model_loss
    from repro.serving import AdaptationServer, Fp32Adapter, TifedAdapter

    phi = init_paper_model(SINE_MLP, jax.random.PRNGKey(args.seed))
    if args.strategy == "tifed":
        from repro.core.strategies import tifed_requantize
        phi = tifed_requantize(phi)
        adapter = TifedAdapter(support=args.support, k_max=args.k_max)
    else:
        adapter = Fp32Adapter(
            loss_fn=functools.partial(paper_model_loss, SINE_MLP))
    if args.ckpt_dir is not None:
        from repro.checkpoint import load_params
        phi = load_params(args.ckpt_dir, phi)

    tracker = MetricsTracker()
    server = AdaptationServer(phi, adapter, slots=args.slots,
                              k_max=args.k_max,
                              steps_per_tick=args.steps_per_tick,
                              metrics=tracker)
    rng = np.random.default_rng(args.seed)
    a = rng.uniform(0.1, 5.0, args.requests)
    b = rng.uniform(0.0, np.pi, args.requests)

    def submit(i):
        sx = rng.uniform(-5, 5, (args.support, 1)).astype(np.float32)
        qx = rng.uniform(-5, 5, (args.query, 1)).astype(np.float32)
        k = int(rng.integers(1, args.k_max + 1))
        server.submit(sx, np.float32(a[i] * np.sin(sx + b[i])),
                      qx, np.float32(a[i] * np.sin(qx + b[i])), k)

    submit(0)
    server.drain()                    # warm the (single) jit trace
    server.reset()
    t0 = time.perf_counter()
    for i in range(args.requests):
        submit(i)
    results = server.drain()
    dt = time.perf_counter() - t0
    print(json.dumps({
        "mode": "adapt", "strategy": args.strategy,
        "requests": len(results), "slots": args.slots,
        "k_max": args.k_max, "steps_per_tick": args.steps_per_tick,
        "wall_s": round(dt, 3),
        "req_per_s": round(len(results) / dt, 1),
        "ticks": server.ticks, "trace_count": server.trace_count,
        "latency_ms": {k: round(v, 3) for k, v in
                       tracker.percentiles("serve.latency_ms").items()},
        "mean_query_loss": round(
            float(np.mean([r.query_loss for r in results])), 5)},
        indent=1))


def main(argv=None):
    args = parse_args(argv)
    from repro.runtime.compile_cache import enable_compile_cache
    enable_compile_cache()
    if args.mode == "decode":
        run_decode(args)
    else:
        run_adapt(args)


if __name__ == "__main__":
    main()
