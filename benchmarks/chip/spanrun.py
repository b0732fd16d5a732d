"""One traced run of a cell, as ``run.py --trace 1`` makes it, that also
reads the program's own spans from the same trace.

    python3 benchmarks/chip/spanrun.py --workload <cell> --seed <n> \
        --seconds <s>

The last line on stdout is run.py's result object with two more keys:

- ``program_metrics``: each metric of ``program_metrics.json`` that lists
  the cell, read by its ``metrics/<name>.py`` from the program's spans;
- ``agreement``: the program's spans against the benchmark's. Serving:
  the median ``serve.tick`` in ms (``serve_tick_ms`` times the same
  ticks from outside) and the share of the ticks' time that their four
  leaves cover. Training: the share of the ``bench.call`` time that
  ``engine.run`` covers.

``trace.Summary`` does not carry the program's spans, so ``run.py`` does
not read these metrics; this script hangs them on the summary it makes.
"""
import time

T_START = time.perf_counter()

import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

_HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, _HERE)
sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(_HERE)),
                                "src"))

from chipbench import harness, spans, trace  # noqa: E402


def program_metrics(cell: str):
    """The entries of ``program_metrics.json`` that list ``cell``."""
    return [m for m in harness.load_json(
        harness.BENCH_DIR / "program_metrics.json")
        if cell in m["workloads"]]


def agreement(program: spans.ProgramSpans):
    out = {}
    ticks = program.named("serve.tick")
    if ticks:
        took = [s.end - s.start for s in ticks]
        out["tick_ms_median"] = 1e-6 * sorted(took)[len(took) // 2]
        out["tick_leaf_cover"] = 1 - sum(s.own for s in ticks) / sum(took)
    calls = trace._union([(s.start, s.end)
                          for s in program.named("bench.call")])
    if calls:
        runs = trace._union([(s.start, s.end)
                             for s in program.named("engine.run")])
        out["run_cover_of_call"] = spans._overlap(calls, runs) / sum(
            e - s for s, e in calls)
    return out


def measure(args, t_start: float,
            device_check=harness.check_device):
    """run.py's traced result for ``args``, with ``program_metrics`` and
    ``agreement``."""
    reduce_dir, got = trace.reduce_dir, {}

    def with_program(trace_dir, chips):
        got["summary"] = spans.attach(reduce_dir(trace_dir, chips),
                                      spans.reduce_dir(trace_dir))
        return got["summary"]
    trace.reduce_dir = with_program
    try:
        result = harness.measure(args, t_start, device_check=device_check)
    finally:
        trace.reduce_dir = reduce_dir
    summary = got["summary"]
    values = {}
    for m in program_metrics(args.workload):
        value = harness.load_module("metrics", m["name"]).read(
            None, None, summary)
        if value is not None:
            values[m["name"]] = {"value": harness._num(value),
                                 "unit": m["unit"]}
    result["program_metrics"] = values
    result["agreement"] = agreement(spans.of(summary))
    return result


def main(argv, t_start: float) -> int:
    args = harness.parse_args(list(argv) + ["--trace", "1"])
    try:
        result = measure(args, t_start)
    except harness.BenchError as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 2
    sys.stdout.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:], T_START))
