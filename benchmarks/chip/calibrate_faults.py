"""Readings that the limits of ``correct`` are set from, for the training
cells whose faults ``calibrate.py`` does not plant: a cell on several
chips and a language-model cell. Not part of a benchmark run.

    python3 benchmarks/chip/calibrate_faults.py --workload <cell> \
        --seeds 11,12,13 [--fault-seeds 11] [--out readings.jsonl]

For each seed, one JSON line of the compared numbers: ``program``, the
program's followed set-up calls against the float32 reference, with
``leaves``: for each change gap its worst leaves (``worst_leaves``). For each
of ``--fault-seeds`` also ``control``, the reference in bfloat16 (the
precision one step below the configs' float32), and the planted faults,
each against the float32 reference:

- ``half_cohort``: the reference aggregating half of each cohort;
- on several chips, ``no_allreduce``: the program with the cross-chip
  sum of the uplinks left out, so that each chip moves phi toward its
  own shard's share;
- for a language model, ``residual``: the reference with residual
  multiplier 1 in place of the config's.

All seeds run in one process, so the programs compile once.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

_HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, _HERE)
sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(_HERE)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from chipbench import harness  # noqa: E402


def worst_leaves(phi0, got, ref, top=3):
    """For the change after the first followed call and after the last,
    the ``top`` leaves of the worst gap, as ``worst_leaf_gap`` weighs
    them, each with what tells rounding from a wrong change: the
    reference's change norm (``ref``), the median leaf's (``median``),
    the norm of one float32 ulp of each of the leaf's weights (``ulp``),
    the leaf's RMS weight (``rms``) and the cosine between the program's
    and the reference's change (``cos``). One leaf at a time in float64,
    so that a model of 3 GB adds no more than its largest leaf."""
    def leaves(tree):
        return jax.tree_util.tree_leaves_with_path(tree)
    out = {}
    for k in (1, len(got)):
        stats = []
        for (path, w0), g, r in zip(leaves(phi0),
                                    jax.tree.leaves(got[k - 1][0]),
                                    jax.tree.leaves(ref[k - 1][0])):
            w0 = np.asarray(w0, np.float64)
            dg = np.asarray(g, np.float64) - w0
            dr = np.asarray(r, np.float64) - w0
            gn, rn = float(np.linalg.norm(dg)), float(np.linalg.norm(dr))
            stats.append(dict(
                leaf=jax.tree_util.keystr(path), gn=gn, ref=rn,
                ulp=float(np.linalg.norm(np.spacing(
                    np.asarray(r, np.float32)))),
                rms=float(np.sqrt(np.mean(w0 ** 2))),
                cos=float(np.vdot(dg, dr) / max(gn * rn, 1e-300))))
        med = float(np.median([d["ref"] for d in stats]))
        rows = [dict(d, gap=abs(d.pop("gn") - d["ref"]) / max(d["ref"], med),
                     median=med)
                for d in stats if d["ref"] >= 1e-3 * med]
        out[f"change{k}"] = sorted(rows, key=lambda d: -d["gap"])[:top]
    return out


def no_allreduce_program(run, driver):
    """The train driver's set-up with each chip's uplink sum kept local."""
    from repro.core import engine, strategies
    mean = strategies.weighted_client_mean

    def local(trees, weights, axis_name=None):
        return mean(trees, weights)
    engine.clear_runner_cache()
    strategies.weighted_client_mean = local
    try:
        return driver.setup(run)
    finally:
        strategies.weighted_client_mean = mean
        engine.clear_runner_cache()


def training(run, driver, faults):
    lm = run.cell.traffic["driver"] == "train_lm"
    if lm:
        _, phi0, followed, phi, _ = driver.setup(run)
        del phi
        ref = driver.follow(run, phi0)
    else:
        _, gen, phi0, followed, phi = driver.setup(run)
        del phi
        ref = driver.follow(run, gen, phi0)

    def follow(**kw):
        return driver.follow(run, phi0, **kw) if lm \
            else driver.follow(run, gen, phi0, **kw)
    row = {"program": dict(driver.readings(phi0, followed, ref),
                           leaves=worst_leaves(phi0, followed, ref))}
    if faults:
        row["control"] = driver.readings(phi0, follow(dtype=jnp.bfloat16),
                                         ref)
        row["half_cohort"] = driver.readings(
            phi0, follow(half_cohort=True), ref)
        if lm:
            row["residual"] = driver.readings(phi0, follow(residual=1.0),
                                              ref)
        elif run.cell.chips > 1:
            _, _, _, bad, phi = no_allreduce_program(run, driver)
            del phi
            row["no_allreduce"] = driver.readings(phi0, bad, ref)
    return row


def main(argv):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True)
    p.add_argument("--fault-seeds", default="")
    p.add_argument("--out")
    args = p.parse_args(argv)
    cell = harness.load_cell(args.workload)
    peaks = harness.load_json(harness.BENCH_DIR / "peaks.json")
    devices, device, peak = harness.check_device(cell.chips, peaks)
    harness.enable_compile_cache()
    driver = harness.load_module("drivers", cell.traffic["driver"])
    fault_seeds = {int(s) for s in args.fault_seeds.split(",") if s}
    for seed in (int(s) for s in args.seeds.split(",")):
        run = harness.Run(cell, seed, 1.0, False, time.perf_counter(), peak,
                          devices)
        t0 = time.perf_counter()
        row = dict(training(run, driver, seed in fault_seeds), seed=seed,
                   workload=cell.name, device=device["kind"],
                   seconds=time.perf_counter() - t0,
                   phases_s={name: t - run.phases[i][1] for i, (name, t)
                             in enumerate(run.phases[1:])})
        line = json.dumps(row, default=float)
        print(line, flush=True)
        if args.out:
            with open(args.out, "a") as f:
                f.write(line + "\n")
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main(sys.argv[1:]))
    except harness.BenchError as e:
        print(f"calibrate_faults: {e}", file=sys.stderr)
        sys.exit(2)
