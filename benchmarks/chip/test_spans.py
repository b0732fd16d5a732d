"""CPU tests of the reduction of the program's own spans
(``chipbench/spans.py``), the per-layer metrics that read them
(``program_metrics.json``), and ``spanrun.py``, which runs a cell with
them.

Run from the repository root with ``PYTHONPATH=src JAX_PLATFORMS=cpu``.
"""
from __future__ import annotations

import sys
import time
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

from chipbench import harness, spans, trace  # noqa: E402

#: the first recording: benchmark spans only, from a program with none of its own
RECORDED = HERE / "testdata" / "tpu_v5e_serve_train.xplane.pb"
#: ``testdata/record.py`` run on the program with its own spans
PROGRAM = HERE / "testdata" / "tpu_v5e_program_spans.xplane.pb"
ENTRIES = harness.load_json(HERE / "program_metrics.json")
NAMES = [m["name"] for m in ENTRIES]


def _read(name, summary):
    return harness.load_module("metrics", name).read(None, None, summary)


def test_self_times_and_unnamed_idle_on_synthetic_spans():
    def span(name, thread, start, end):
        return spans.Span(name, thread, start, end, {})
    main = [span("engine.run", 0, 0, 55), span("engine.wait", 0, 10, 40),
            span("engine.plan", 0, 12, 20), span("engine.sample", 0, 20, 35),
            span("engine.dispatch", 0, 40, 50),
            span("engine.run", 0, 60, 100)]
    other = [span("engine.sample", 1, 45, 70)]
    for group in (main, other):
        spans._set_self_times(group)
    program = spans.ProgramSpans(window=(0, 100), spans=main + other,
                                 idle=[(0, 5), (30, 48), (55, 90)])
    assert [s.own for s in main] == [15, 7, 8, 15, 10, 40]
    assert program.self_s("engine.sample") == pytest.approx([15e-9, 25e-9])
    assert program.idle_s == pytest.approx(58e-9)
    # covered: 30-48 by wait, dispatch and the other thread's sample;
    # 55-70 by that sample; unnamed: 0-5 and 70-90
    assert program.idle_unnamed_s(spans.TRAIN_LEAVES) == pytest.approx(
        25e-9)
    assert spans._overlap([(0, 5), (30, 48)], [(3, 31), (47, 60)]) == 4
    assert spans.idle_unnamed_share(program, "engine.",
                                    spans.TRAIN_LEAVES) == pytest.approx(
        100 * 25 / 58)
    assert spans.idle_unnamed_share(program, "serve.",
                                    spans.SERVE_LEAVES) is None
    assert spans.per_round_ms(program, "engine.sample") is None  # no rounds


def test_first_recording_reads_as_it_did():
    """The benchmark's reduction of the first recording, pinned at the
    values it read when the recording was made."""
    s = trace.reduce_file(str(RECORDED), 1)
    assert s.window_s == 0.5383926960000001
    assert s.busy_per_chip == [0.004729097000000001]
    assert s.collectives_per_chip == [0.0]
    assert len(s.kernels) == 408
    assert s.gaps == {"bench.call": 0.4953092210000004,
                      "bench.step": 0.03835265199999866,
                      "other": 3.7999999999999996e-08,
                      "bench.submit": 1.6880000000000027e-06}
    b = s.breakdown()
    assert b["device_ops"][:3] == [["fusion.18", 0.00018295],
                                   ["multiply_reduce_fusion.3", 9.7119e-05],
                                   ["fusion.16", 6.3458e-05]]
    assert len(s.ops_self_s) == 431


def test_readers_find_nothing_without_program_spans():
    """On the first recording, whose program opened no spans of its own,
    and on a summary with no spans hung on it, every reader gives
    None."""
    program = spans.reduce_file(str(RECORDED))
    assert {s.name.split(".")[0] for s in program.spans} == {"bench"}
    assert program.idle_s == pytest.approx(0.5383926960000001
                                           - 0.004729097000000001)
    summary = trace.reduce_file(str(RECORDED), 1)
    assert {n: _read(n, summary) for n in NAMES} == dict.fromkeys(NAMES)
    spans.attach(summary, program)
    assert {n: _read(n, summary) for n in NAMES} == dict.fromkeys(NAMES)


@pytest.fixture(scope="module")
def program_summary():
    return spans.attach(trace.reduce_file(str(PROGRAM), 1),
                        spans.reduce_file(str(PROGRAM)))


@pytest.mark.parametrize("name", NAMES)
def test_reader_reads_the_program_recording(program_summary, name):
    value = _read(name, program_summary)
    assert value is not None and value >= 0
    if ENTRIES[NAMES.index(name)]["unit"] == "%":
        assert value <= 100


def test_program_recording_spans(program_summary):
    """The recording's two calls of one 4-round block and 12 ticks, as
    the program's spans count them."""
    program = spans.of(program_summary)
    runs = program.named("engine.run")
    assert [s.args["rounds"] for s in runs] == [4, 4]
    assert sum(program.values("engine.dispatch", "rounds")) == 8
    assert len(program.named("serve.tick")) == 12
    assert program.values("serve.tick", "computed") == [64 * 5] * 12
    ticks = program.named("serve.tick")
    admitted = program.values("serve.tick", "admitted")
    assert [t.args["tick"] for t in ticks] == list(
        range(ticks[0].args["tick"], ticks[0].args["tick"] + 12))
    assert sum(admitted) == 12 * 25


def test_program_metrics_are_ready_for_the_benchmark():
    """Each entry reads with its own file, names a layer and cells the
    benchmark has, and moves an end-to-end metric those cells report."""
    bench = harness.load_bench()
    taken = {m["name"] for m in bench["per_layer"] + bench["end_to_end"]}
    cells = {w["name"] for w in bench["workloads"]}
    for m in ENTRIES:
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["name"] not in taken
        assert (HERE / "metrics" / f"{m['name']}.py").is_file()
        assert set(m["workloads"]) <= cells
        for cell in m["workloads"]:
            assert m["moves"] in {e["name"] for e in
                                  harness.reported_end_to_end(bench, cell)}
    assert len(set(NAMES)) == len(NAMES) == 14


def _cpu_device(chips, peaks):
    import jax
    d = jax.devices()
    return d[:chips], {"platform": d[0].platform, "kind": d[0].device_kind,
                       "count": len(d)}, peaks["devices"]["TPU v5 lite"]


@pytest.mark.parametrize("cell,small,reads", [
    ("sine.tinyreptile.c32", {"rounds_per_call": 4, "cohort": 8,
                              "support": 8},
     {"train_sample_ms_per_round", "train_stage_ms_per_round",
      "train_call_host_ms"}),
    ("sine.serve.fp32.r80", {"rate": 400},
     {"serve_refill_ms", "serve_dispatch_ms", "serve_readback_ms",
      "serve_live_slot_share"})])
def test_cpu_traced_run_reads_span_metrics(monkeypatch, cell, small, reads):
    """On a CPU the span readers read; the idle shares, which need a
    device plane, do not. The benchmark's own metrics are as run.py
    reads them."""
    import spanrun
    load = harness.load_cell

    def cut(name, bench=None):
        c = load(name, bench)
        c.traffic.update(small)
        return c
    monkeypatch.setattr(harness, "load_cell", cut)
    monkeypatch.setattr(harness, "enable_compile_cache", lambda: None)
    args = harness.parse_args(["--workload", cell, "--seed", str(2**31 + 9),
                               "--seconds", "0.5", "--trace", "1"])
    res = spanrun.measure(args, time.perf_counter(),
                          device_check=_cpu_device)
    assert res["correct"], res["checks"]
    assert set(res["program_metrics"]) == reads
    assert all(m["value"] >= 0 for m in res["program_metrics"].values())
    if cell.startswith("sine.serve"):
        assert set(res["metrics"]) == {"serve_tick_ms", "serve_p95_ms",
                                       "slot_occupancy", "gen_late_ms"}
        assert 0 < res["agreement"]["tick_leaf_cover"] <= 1
        assert res["agreement"]["tick_ms_median"] > 0
    else:
        assert set(res["metrics"]) == {"train_mfu"}
        assert 0 < res["agreement"]["run_cover_of_call"] <= 1
    assert trace.reduce_dir.__name__ == "reduce_dir"     # put back
