"""The program's own spans in a profiler trace (``.xplane.pb``), and chip
0's idle time that they leave unnamed: what the per-layer metrics of the
round engine's and the adaptation server's host phases read.

- The round engine and the adaptation server open ``engine.*`` and
  ``serve.*`` spans, listed with their arguments in the program's
  ``repro/metering/tracker.py``. They are read from every thread's line
  of the host plane, with the thread, the interval and the arguments,
  beside the benchmark's own ``bench.*`` spans.
- Every interval is clipped to the window, the benchmark's host span
  ``bench.window``.
- A span's self time is its clipped time less the part its child spans
  on the same thread cover.
- Chip 0's idle time is the window less the union of its ``XLA Ops``
  intervals, as ``trace.reduce_file`` takes it; a trace with no device
  plane has none.

``trace.Summary`` does not carry these spans: ``attach`` hangs them on a
summary as ``program``, and the readers find them with ``of``.
"""
from __future__ import annotations

import dataclasses
import glob
import os
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from chipbench.trace import WINDOW_SPAN, _union

PREFIXES = ("engine.", "serve.", "bench.")
#: the engine's phases, on either thread; ``engine.run`` holds them
TRAIN_LEAVES = ("engine.plan", "engine.sample", "engine.stage",
                "engine.wait", "engine.dispatch", "engine.fetch",
                "engine.eval", "engine.ckpt")
#: the serving tick's phases; ``serve.tick`` holds them
SERVE_LEAVES = ("serve.refill", "serve.dispatch", "serve.readback",
                "serve.retire")


@dataclasses.dataclass
class Span:
    """One span in the window. Times are in ns, clipped to the window;
    ``own`` is the self time."""
    name: str
    thread: int                 # the host line, one per thread
    start: float
    end: float
    args: Dict[str, Any]
    own: float = 0.0


@dataclasses.dataclass
class ProgramSpans:
    window: Tuple[float, float]
    spans: List[Span]
    #: chip 0's idle intervals in the window; None without a device plane
    idle: Optional[List[Tuple[float, float]]]

    def named(self, name: str) -> List[Span]:
        return [s for s in self.spans if s.name == name]

    def has(self, prefix: str) -> bool:
        return any(s.name.startswith(prefix) for s in self.spans)

    def self_s(self, name: str) -> List[float]:
        """Each ``name`` span's self time in the window, in seconds."""
        return [s.own * 1e-9 for s in self.named(name)]

    def values(self, name: str, key: str) -> List[float]:
        """The argument ``key`` of every ``name`` span that has it."""
        return [s.args[key] for s in self.named(name) if key in s.args]

    @property
    def idle_s(self) -> Optional[float]:
        if self.idle is None:
            return None
        return sum(e - s for s, e in self.idle) * 1e-9

    def idle_unnamed_s(self, leaves: Sequence[str]) -> Optional[float]:
        """Chip 0's idle time in the window that no span named in
        ``leaves``, on any thread, covers."""
        if self.idle is None:
            return None
        covered = _union([(s.start, s.end) for s in self.spans
                          if s.name in leaves])
        return self.idle_s - _overlap(self.idle, covered) * 1e-9


def _overlap(a: List[Tuple[float, float]],
             b: List[Tuple[float, float]]) -> float:
    """The length both sorted, disjoint interval lists cover."""
    total, i, j = 0.0, 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def _set_self_times(spans: List[Span]) -> None:
    """Spans of one thread nest; each one's self time is its own time
    less that of the spans directly inside it."""
    stack: List[Span] = []
    for s in sorted(spans, key=lambda t: (t.start, -t.end)):
        s.own = s.end - s.start
        while stack and stack[-1].end <= s.start:
            stack.pop()
        if stack:
            stack[-1].own -= s.end - s.start
        stack.append(s)


def reduce_file(path: str) -> ProgramSpans:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    planes = {p.name: p for p in data.planes}

    raw = []
    host = planes.get("/host:CPU")
    for i, line in enumerate(host.lines if host is not None else []):
        for ev in line.events:
            if ev.name.startswith(PREFIXES):
                raw.append((ev.name, i, ev.start_ns,
                            ev.start_ns + ev.duration_ns, dict(ev.stats)))
    windows = [(s, e) for n, _, s, e, _ in raw if n == WINDOW_SPAN]
    if windows:
        w0, w1 = windows[0]
    else:
        w0 = min((s for _, _, s, _, _ in raw), default=0.0)
        w1 = max((e for _, _, _, e, _ in raw), default=0.0)
    spans = [Span(n, t, max(s, w0), min(e, w1), args)
             for n, t, s, e, args in raw if e > w0 and s < w1]
    for t in {s.thread for s in spans}:
        _set_self_times([s for s in spans if s.thread == t])

    idle = None
    chip0 = planes.get("/device:TPU:0")
    if chip0 is not None:
        busy = _union([(max(ev.start_ns, w0),
                        min(ev.start_ns + ev.duration_ns, w1))
                       for ln in chip0.lines if ln.name == "XLA Ops"
                       for ev in ln.events
                       if ev.start_ns + ev.duration_ns > w0
                       and ev.start_ns < w1])
        idle, cursor = [], w0
        for s, e in busy + [(w1, w1)]:
            if s > cursor:
                idle.append((cursor, s))
            cursor = max(cursor, e)
    return ProgramSpans(window=(w0, w1), spans=spans, idle=idle)


def reduce_dir(trace_dir: str) -> ProgramSpans:
    found = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(found) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {trace_dir}, "
                           f"found {len(found)}")
    return reduce_file(found[0])


def per_round_ms(program: Optional[ProgramSpans],
                 name: str) -> Optional[float]:
    """The self time of the window's ``name`` spans over the rounds of
    its ``engine.dispatch`` spans, in ms."""
    if program is None:
        return None
    rounds = sum(program.values("engine.dispatch", "rounds"))
    own = program.self_s(name)
    if not rounds or not own:
        return None
    return 1e3 * sum(own) / rounds


def median_ms(program: Optional[ProgramSpans],
              name: str) -> Optional[float]:
    """The median self time of the window's ``name`` spans, in ms."""
    own = program.self_s(name) if program is not None else []
    return 1e3 * float(np.median(own)) if own else None


def idle_unnamed_share(program: Optional[ProgramSpans], prefix: str,
                       leaves: Sequence[str]) -> Optional[float]:
    """The share of chip 0's idle time in the window that none of
    ``leaves`` covers, in %; None where the trace has no device plane,
    no idle time, or no span of ``prefix``."""
    if program is None or not program.idle_s or not program.has(prefix):
        return None
    return 100.0 * program.idle_unnamed_s(leaves) / program.idle_s


def attach(summary, program: ProgramSpans):
    """``summary`` with the program's spans hung on it."""
    summary.program = program
    return summary


def of(summary) -> Optional[ProgramSpans]:
    """The program's spans hung on ``summary``, or None."""
    return getattr(summary, "program", None)
