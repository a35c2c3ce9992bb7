"""Reduction of a profiler trace to the numbers the per-layer metrics
read.

The profiler writes an XSpace (``*.xplane.pb``). :func:`load_events`
flattens it into plain ``Event`` tuples; everything after that is pure
arithmetic on those tuples, so it is tested on a small recorded fixture
(``tests/bench/fixtures``) and every later change computes the same
numbers in the same way.

Planes: the chip's ops are on ``/device:TPU:<n>`` planes, one event per
executed op on the ``XLA Ops`` line and one per executed program on the
``XLA Modules`` line. The benchmark's own spans are
``jax.profiler.TraceAnnotation`` events named ``bench.*`` on the host
plane, on the same clock.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"


@dataclasses.dataclass(frozen=True)
class Event:
    plane: str
    line: str
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


def find_xplane(trace_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def load_events(path: str) -> List[Event]:
    """Device op and program events, and the benchmark's host spans."""
    import jax

    pd = jax.profiler.ProfileData.from_file(path)
    out = []
    for plane in pd.planes:
        dev = DEVICE_PLANE.match(plane.name) is not None
        for line in plane.lines:
            if dev and line.name not in (OPS_LINE, MODULES_LINE):
                continue
            for e in line.events:
                if not dev and not e.name.startswith(SPAN_PREFIX):
                    continue
                out.append(Event(plane.name, line.name, e.name,
                                 float(e.start_ns), float(e.duration_ns)))
    return out


def plane_summary(path: str, top: int = 8) -> list:
    """[plane, line, events, most frequent names] of every line of the
    trace, for reading a trace's layout by eye."""
    import collections

    import jax

    out = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        for line in plane.lines:
            names = collections.Counter(e.name for e in line.events)
            out.append([plane.name, line.name, sum(names.values()),
                        [n[:80] for n, _ in names.most_common(top)]])
    return out


def merge_intervals(iv: Iterable[Tuple[float, float]]
                    ) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for s, e in sorted(iv):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def overlap(merged: Sequence[Tuple[float, float]], lo: float, hi: float
            ) -> float:
    """Length of [lo, hi) covered by sorted disjoint intervals."""
    tot = 0.0
    for s, e in merged:
        if e <= lo:
            continue
        if s >= hi:
            break
        tot += min(e, hi) - max(s, lo)
    return tot


@dataclasses.dataclass
class Reduced:
    """The traced window, reduced. Times in nanoseconds."""

    window: Tuple[float, float]
    n_devices: int
    busy: Dict[str, List[Tuple[float, float]]]   # plane -> merged op intervals
    ops: List[Event]                             # device ops in the window
    modules: List[Event]                         # device programs in window
    spans: List[Event]                           # bench.* host spans

    @property
    def window_ns(self) -> float:
        return self.window[1] - self.window[0]

    def busy_ns(self, lo: Optional[float] = None,
                hi: Optional[float] = None) -> float:
        """Device-busy time within [lo, hi), averaged over the chips."""
        lo = self.window[0] if lo is None else lo
        hi = self.window[1] if hi is None else hi
        if not self.busy:
            return 0.0
        return sum(overlap(iv, lo, hi) for iv in self.busy.values()) \
            / len(self.busy)

    def spans_named(self, name: str) -> List[Event]:
        return [e for e in self.spans if e.name == name]

    def op_ns(self, pattern: str) -> Tuple[float, int]:
        """Summed device time and count of ops whose name matches."""
        rx = re.compile(pattern)
        hits = [e for e in self.ops if rx.search(e.name)]
        return sum(e.dur_ns for e in hits), len(hits)

    def module_ns(self, pattern: str) -> Tuple[float, int]:
        rx = re.compile(pattern)
        hits = [e for e in self.modules if rx.search(e.name)]
        return sum(e.dur_ns for e in hits), len(hits)


def reduce_events(events: Sequence[Event]) -> Reduced:
    """Clip device events to the ``bench.window`` span and merge each
    chip's op intervals into its busy set."""
    wins = [e for e in events if e.name == WINDOW_SPAN]
    if len(wins) != 1:
        raise ValueError(f"expected one {WINDOW_SPAN} span, found "
                         f"{len(wins)}")
    lo, hi = wins[0].start_ns, wins[0].end_ns
    inside = [e for e in events
              if DEVICE_PLANE.match(e.plane) and e.end_ns > lo
              and e.start_ns < hi]
    ops = [e for e in inside if e.line == OPS_LINE]
    busy: Dict[str, List[Tuple[float, float]]] = {}
    for e in ops:
        busy.setdefault(e.plane, []).append(
            (max(e.start_ns, lo), min(e.end_ns, hi)))
    busy = {p: merge_intervals(iv) for p, iv in busy.items()}
    planes = {e.plane for e in events if DEVICE_PLANE.match(e.plane)}
    return Reduced(
        window=(lo, hi), n_devices=len(planes), busy=busy, ops=ops,
        modules=[e for e in inside if e.line == MODULES_LINE],
        spans=[e for e in events if e.name.startswith(SPAN_PREFIX)
               and not DEVICE_PLANE.match(e.plane)])


def exposed_ms(red: Reduced, span: str = "bench.engine_call"
               ) -> Optional[float]:
    """Median over calls of (wall time of the span - device-busy time
    inside it), in milliseconds: the host time the call adds."""
    calls = red.spans_named(span)
    if not calls:
        return None
    vals = sorted((e.dur_ns - red.busy_ns(e.start_ns, e.end_ns)) / 1e6
                  for e in calls)
    m = len(vals) // 2
    return vals[m] if len(vals) % 2 else 0.5 * (vals[m - 1] + vals[m])


def idle_gaps(red: Reduced) -> List[Tuple[str, float]]:
    """Each idle stretch of the first chip within the window, cut where a
    benchmark span opens or closes, each piece labelled with the
    innermost span open over it."""
    if not red.busy:
        return [("no device op", red.window_ns / 1e9)]
    iv = next(iter(sorted(red.busy.items())))[1]
    gaps, cur = [], red.window[0]
    for s, e in iv:
        if s > cur:
            gaps.append((cur, s))
        cur = max(cur, e)
    if cur < red.window[1]:
        gaps.append((cur, red.window[1]))
    inner = [e for e in red.spans if e.name != WINDOW_SPAN]
    edges = sorted({t for x in inner for t in (x.start_ns, x.end_ns)})
    out = []
    for s, e in gaps:
        cuts = [s] + [t for t in edges if s < t < e] + [e]
        for a, b in zip(cuts[:-1], cuts[1:]):
            mid = 0.5 * (a + b)
            open_ = [x for x in inner if x.start_ns <= mid < x.end_ns]
            name = max(open_, key=lambda x: x.start_ns).name if open_ \
                else WINDOW_SPAN
            out.append((name, (b - a) / 1e9))
    return out


def op_label(name: str) -> str:
    """An op's HLO instruction name, without its long signature; a
    Mosaic kernel is marked as such."""
    head = name.split(" = ", 1)[0]
    return head + " (Mosaic kernel)" if "tpu_custom_call" in name else head


def breakdown(red: Reduced, top: int = 10) -> dict:
    """Device ops that took most time, and idle time by the benchmark
    span that was open, each in seconds."""
    by_op: Dict[str, float] = {}
    for e in red.ops:
        key = op_label(e.name)
        by_op[key] = by_op.get(key, 0.0) + e.dur_ns / 1e9
    by_span: Dict[str, float] = {}
    for name, sec in idle_gaps(red):
        by_span[name] = by_span.get(name, 0.0) + sec
    rank = lambda d: [[k, v] for k, v in sorted(  # noqa: E731
        d.items(), key=lambda kv: -kv[1])[:top]]
    return {"device_ops": rank(by_op), "idle_gaps": rank(by_span)}
