#!/usr/bin/env python3
"""The program's own spans in a traced run, beside the device's ops.

The program brackets its host work with ``repro.obs`` spans. With a
profiler-mode tracer installed (``obs.Tracer(profiler=True)``) each span
is also a profiler annotation on its thread's line of the host plane, on
the clock of the device's ops, in the same ``.xplane.pb``. This module
reads those spans, attributes the device's idle time to them, and sums
device time by the named scope (``jax.named_scope``) an op came from.

    python3 bench/program_trace.py --workload <cell> --seed <n> \\
        --seconds <s> [--out <file>]

runs one cell as ``bench/run.py --trace 1`` does, with the tracer
installed around the measured window, and prints one JSON object: the
run's result line, its end-to-end numbers, and the reduction below. The
harness of ``bench/run.py`` does not install the tracer, so its traced
runs hold no program spans.

Attribution: an idle piece of the first chip is labelled with the
innermost program span open over it on a line that launches device work
(one holding ``megastep.device_step``), and only where none is open
there with the innermost span open on another line (a caller's
``serve.admission``, say).
"""
from __future__ import annotations

import argparse
import bisect
import dataclasses
import glob
import json
import os
import statistics
import sys
from typing import Dict, List, Optional, Sequence, Tuple

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench import trace as tr  # noqa: E402

PROGRAM_PREFIXES = ("serve.", "megastep.", "stream.", "quant.",
                    "sharded.", "index.")
LAUNCH_SPAN = "megastep.device_step"
ENGINE_SPANS = ("megastep.dispatch", "megastep.fetch")
PLAN_SCOPES = ("assign", "bounds", "schedule")
NONE = "no program span"


def load_program_spans(path: str) -> List[tr.Event]:
    """The program spans of the host planes. A host plane has one line
    per thread, and threads' lines may share a name, so each line is
    told apart by its index: ``<line name>#<index>``."""
    import jax

    out = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if tr.DEVICE_PLANE.match(plane.name):
            continue
        for i, line in enumerate(plane.lines):
            for e in line.events:
                if e.name.startswith(PROGRAM_PREFIXES):
                    out.append(tr.Event(plane.name, f"{line.name}#{i}",
                                        e.name, float(e.start_ns),
                                        float(e.duration_ns)))
    return out


def in_window(red: tr.Reduced, spans: Sequence[tr.Event]
              ) -> List[tr.Event]:
    lo, hi = red.window
    return [e for e in spans if e.start_ns >= lo and e.end_ns <= hi]


def idle_intervals(red: tr.Reduced) -> List[Tuple[float, float]]:
    """The first chip's idle stretches within the window: where the
    stretches of ``trace.idle_gaps`` lie, which it does not return. (Its
    labelling scans every span edge for each stretch, too slow for an
    online window's ~10^5 stretches and ~2·10^4 program spans.)"""
    if not red.busy:
        return [red.window]
    busy = next(iter(sorted(red.busy.items())))[1]
    out, cur = [], red.window[0]
    for s, e in busy:
        if s > cur:
            out.append((cur, s))
        cur = max(cur, e)
    if cur < red.window[1]:
        out.append((cur, red.window[1]))
    return out


class _Sweep:
    """The innermost span open on each line at increasing times: spans
    of one line nest (one thread), so a stack a line holds them."""

    def __init__(self, spans: Sequence[tr.Event]):
        bounds = []
        for i, e in enumerate(spans):
            bounds.append((e.start_ns, 1, i))
            bounds.append((e.end_ns, 0, i))      # ends first at a tie
        bounds.sort()
        self._bounds = bounds
        self._spans = spans
        self._next = 0
        self.open: Dict[str, List[tr.Event]] = {}

    def at(self, t: float) -> Dict[str, List[tr.Event]]:
        """Advance to ``t`` (never backwards): spans with start <= t <
        end are open."""
        while self._next < len(self._bounds) and \
                self._bounds[self._next][0] <= t:
            _, kind, i = self._bounds[self._next]
            e = self._spans[i]
            stack = self.open.setdefault(e.line, [])
            if kind:
                stack.append(e)
            elif e in stack:
                stack.remove(e)
            self._next += 1
        return self.open


def _innermost(open_: Dict[str, List[tr.Event]], lines) -> Optional[str]:
    tops = [st[-1] for ln, st in open_.items() if st and ln in lines]
    return max(tops, key=lambda e: e.start_ns).name if tops else None


def idle_by_program_span(red: tr.Reduced, program: Sequence[tr.Event]
                         ) -> dict:
    """Device-idle seconds, pieces and the longest piece per innermost
    program span, the idle time under no program span, and the share of
    idle time a span covers."""
    spans = sorted(in_window(red, program), key=lambda e: e.start_ns)
    launching = {e.line for e in spans if e.name == LAUNCH_SPAN}
    others = {e.line for e in spans} - launching
    edges = sorted({t for e in spans for t in (e.start_ns, e.end_ns)})
    sweep = _Sweep(spans)
    by: Dict[str, List[float]] = {}
    total = 0.0
    for s, e in idle_intervals(red):
        total += e - s
        i = bisect.bisect_right(edges, s)
        j = bisect.bisect_left(edges, e)
        cuts = [s] + edges[i:j] + [e]
        last, run = None, 0.0
        for a, b in zip(cuts[:-1], cuts[1:]):
            open_ = sweep.at(0.5 * (a + b))
            name = _innermost(open_, launching) or _innermost(open_, others) \
                or NONE
            if name != last and last is not None:
                by.setdefault(last, []).append(run)
                run = 0.0
            last, run = name, run + (b - a)
        if last is not None:
            by.setdefault(last, []).append(run)
    none = sum(by.get(NONE, ()))
    return {
        "idle_s": total / 1e9,
        "by_span": {k: {"seconds": sum(v) / 1e9, "count": len(v),
                        "longest_s": max(v) / 1e9}
                    for k, v in sorted(by.items(), key=lambda kv:
                                       -sum(kv[1])) if k != NONE},
        "no_program_span_s": none / 1e9,
        "attributed_share": 1.0 - none / total if total else None,
    }


def with_program(red: tr.Reduced, program: Sequence[tr.Event]
                 ) -> tr.Reduced:
    """The reduction with the window's program spans as its spans, so
    that ``trace.exposed_ms`` reads a program span as it reads a
    benchmark one: the median of wall time less device-busy time."""
    return dataclasses.replace(red, spans=in_window(red, program))


def sched_idle_ms(red: tr.Reduced, program: Sequence[tr.Event]
                  ) -> Optional[float]:
    """Median over the window's ``serve.step`` spans of the device-idle
    time inside the step that no ``megastep.dispatch`` or
    ``megastep.fetch`` covers: the scheduler's own host work while the
    chip waits, in ms."""
    spans = in_window(red, program)
    engine = tr.merge_intervals((e.start_ns, e.end_ns) for e in spans
                                if e.name in ENGINE_SPANS)
    vals = []
    for st in (e for e in spans if e.name == "serve.step"):
        idle, cur = 0.0, st.start_ns
        for a, b in engine + [(st.end_ns, st.end_ns)]:
            if b <= cur:
                continue
            if a >= st.end_ns:
                a = st.end_ns
            if a > cur:
                idle += (a - cur) - red.busy_ns(cur, a)
            cur = max(cur, b)
            if cur >= st.end_ns:
                break
        vals.append(idle)
    return statistics.median(vals) / 1e6 if vals else None


def clock_check(red: tr.Reduced, program: Sequence[tr.Event],
                module: str = "_megastep", slack_ns: float = 1e6) -> dict:
    """Host spans against the device programs they wait for. Each
    launch (``megastep.device_step``) is matched with the first
    ``module`` program still running when it opened, and with the first
    ``megastep.fetch.wait`` after it on its line. Of the waits that
    opened before their program ended: the share that ended no earlier
    than the program and within ``slack_ns`` after it, and the lag from
    the program's end to the wait's end. ``lead_ms`` is the program's
    start less its launch's start, which is never below 0 on one
    clock."""
    spans = sorted(in_window(red, program), key=lambda e: e.start_ns)
    mods = sorted((m for m in red.modules if module in m.name),
                  key=lambda m: m.end_ns)
    ends = [m.end_ns for m in mods]
    pending: Dict[str, tr.Event] = {}
    lags, leads = [], []
    for e in spans:
        if e.name == LAUNCH_SPAN:
            k = bisect.bisect_right(ends, e.start_ns)
            if k < len(mods):
                pending[e.line] = mods[k]
                leads.append(mods[k].start_ns - e.start_ns)
        elif e.name == "megastep.fetch.wait" and e.line in pending:
            m = pending.pop(e.line)
            if e.start_ns < m.end_ns:
                lags.append(e.end_ns - m.end_ns)

    def summary(v):
        return {"min": min(v) / 1e6, "median": statistics.median(v) / 1e6,
                "max": max(v) / 1e6} if v else None

    ok = sum(0 <= x <= slack_ns for x in lags)
    return {"batches": len(lags),
            "share_ok": ok / len(lags) if lags else None,
            "lag_ms": summary(lags), "lead_ms": summary(leads)}


def admissions_vs_fetch(red: tr.Reduced, program: Sequence[tr.Event]
                        ) -> dict:
    """Where ``serve.admission`` spans (the caller's ``submit``) start
    relative to the consumer's ``megastep.fetch``: the share that start
    inside a fetch against the share of the window fetches cover, and
    the share that start within 1 ms after one ends. A fetch that holds
    the interpreter lock lets no admission start inside it and
    releases them all just after it."""
    spans = in_window(red, program)
    fetch = sorted((e.start_ns, e.end_ns) for e in spans
                   if e.name == "megastep.fetch")
    adm = sorted(e.start_ns for e in spans if e.name == "serve.admission")
    if not fetch or not adm:
        return {"admissions": len(adm), "fetches": len(fetch)}
    starts = [a for a, _ in fetch]          # one thread's: disjoint
    inside = after = 0
    for t in adm:
        k = bisect.bisect_right(starts, t) - 1
        if k >= 0 and t < fetch[k][1]:
            inside += 1
        elif k >= 0 and t - fetch[k][1] <= 1e6:
            after += 1
    cover = sum(b - a for a, b in fetch) / red.window_ns
    gap_after = sum(min(1e6, (fetch[i + 1][0] if i + 1 < len(fetch)
                              else red.window[1]) - b)
                    for i, (_, b) in enumerate(fetch)) / red.window_ns
    adm_ms = sorted(e.dur_ns / 1e6 for e in spans
                    if e.name == "serve.admission")
    return {"admissions": len(adm), "fetches": len(fetch),
            "share_starting_inside_fetch": inside / len(adm),
            "share_of_window_inside_fetch": cover,
            "share_starting_within_1ms_after_fetch": after / len(adm),
            "share_of_window_within_1ms_after_fetch": gap_after,
            "admission_ms": {"median": statistics.median(adm_ms),
                             "max": adm_ms[-1]}}


def _varint(buf, i: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf):
    """(field number, value) of one protobuf message: an int for a
    varint, a slice for a length-delimited field; fixed-width fields are
    skipped."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            v, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            i += 8 if wire == 1 else 4
            continue
        else:
            raise ValueError(f"protobuf wire type {wire} is not read here")
        yield key >> 3, v


def op_scopes(path: str) -> Dict[str, str]:
    """HLO op name -> its ``tf_op`` stat (``jit(<fn>)/<scope>/.../<op>:``,
    the op's ``op_name`` metadata), for the ops of the device planes.

    The stat sits on the op's event metadata, which
    ``jax.profiler.ProfileData`` does not expose, so the XSpace is read
    here by field number (tsl ``xplane.proto``: XSpace.planes = 1;
    XPlane.name = 2, event_metadata = 4, stat_metadata = 5, maps of
    key = 1 / value = 2; XEventMetadata.name = 2, stats = 5;
    XStatMetadata.name = 2; XStat.metadata_id = 1, str_value = 5,
    ref_value = 7)."""
    with open(path, "rb") as f:
        data = memoryview(f.read())
    out: Dict[str, str] = {}
    for field, plane in _fields(data):
        if field != 1:
            continue
        name, events, stat_names = "", [], {}
        for g, v in _fields(plane):
            if g == 2:
                name = bytes(v).decode()
            elif g in (4, 5):
                value = dict(_fields(v)).get(2)
                if value is None:
                    continue
                if g == 4:
                    events.append(value)
                else:
                    md = dict(_fields(value))
                    stat_names[md.get(1, 0)] = bytes(md.get(2, b"")).decode()
        if not tr.DEVICE_PLANE.match(name):
            continue
        tf_op = {k for k, v in stat_names.items() if v == "tf_op"}
        for ev in events:
            op, scope = "", None
            for g, v in _fields(ev):
                if g == 2:
                    op = bytes(v).decode()
                elif g == 5:
                    st = dict(_fields(v))
                    if st.get(1) in tf_op:
                        scope = bytes(st[5]).decode() if 5 in st \
                            else stat_names.get(st.get(7))
            if op and scope:
                out[op] = scope
    return out


def run_xplane(run) -> Optional[str]:
    """The trace file of a traced harness run: the newest under the
    harness's trace directory for the run's cell and seed whose
    ``bench.window`` span is the run's window. A stale file of an earlier
    run is passed over, and a run traced into another directory
    (``harness.run(..., out_dir=...)``) finds none."""
    from bench import harness

    d = harness.OUT / f"trace-{run.ctx.cell}-{run.ctx.seed}"
    for path in sorted(glob.glob(str(d / "**" / "*.xplane.pb"),
                                 recursive=True), reverse=True):
        wins = [(e.start_ns, e.end_ns) for e in tr.load_events(path)
                if e.name == tr.WINDOW_SPAN]
        if wins == [run.trace.window]:
            return path
    return None


def plan_ms(red: tr.Reduced, scopes: Dict[str, str],
            module: str = "_megastep") -> Optional[float]:
    """Device time per ``module`` execution in the window spent in ops
    whose scope lies under a planning stage (``PLAN_SCOPES``), in ms;
    None where no op carries those scopes."""
    marks = tuple(f"jit({module})/{s}/" for s in PLAN_SCOPES)
    ns = sum(e.dur_ns for e in red.ops
             if scopes.get(e.name, "").startswith(marks))
    n = red.module_ns(module)[1]
    return ns / n / 1e6 if n and ns else None


def reduce_program(xplane: str) -> dict:
    """Everything this module reads from one traced run's xplane."""
    red = tr.reduce_events(tr.load_events(xplane))
    program = load_program_spans(xplane)
    both = with_program(red, program)
    return {
        "program_spans": len(both.spans),
        "idle_by_program_span": idle_by_program_span(red, program),
        "engine.dispatch_idle_ms": tr.exposed_ms(both, "megastep.dispatch"),
        "engine.fetch_idle_ms": tr.exposed_ms(both, "megastep.fetch"),
        "fetch.wait_idle_ms": tr.exposed_ms(both, "megastep.fetch.wait"),
        "fetch.copy_idle_ms": tr.exposed_ms(both, "megastep.fetch.copy"),
        "sched.idle_ms": sched_idle_ms(red, program),
        "step.plan_ms": plan_ms(red, op_scopes(xplane)),
        "clock": clock_check(red, program),
        "admissions": admissions_vs_fetch(red, program),
        "device_idle_share": 1.0 - red.busy_ns() / red.window_ns,
    }


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out", help="also write the JSON object here")
    args = ap.parse_args()
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from bench import harness
    from repro import obs

    spec = harness.load_spec()
    cell = {c["name"]: c for c in spec["workloads"]}[args.workload]
    loop = harness.load_module(
        "loops", harness.load_json("traffic", cell["traffic"])["loop"])
    measure = loop.measure

    def traced_measure(*a, **kw):
        obs.install(obs.Tracer(profiler=True))
        try:
            return measure(*a, **kw)
        finally:
            obs.uninstall()

    loop.measure = traced_measure
    result = harness.run(args.workload, args.seed, args.seconds, True)
    side = harness.OUT / (f"{args.workload}-{args.seed}-trace1-"
                          f"{os.getpid()}.json")
    with open(side) as f:
        end_to_end = json.load(f)["end_to_end"]
    xplane = tr.find_xplane(str(harness.OUT /
                                f"trace-{args.workload}-{args.seed}"))
    out = {"workload": args.workload, "seed": args.seed,
           "seconds": args.seconds, "end_to_end": end_to_end,
           "result": result, **reduce_program(xplane)}
    text = json.dumps(out, default=float)
    if args.out:
        with open(args.out, "w") as f:
            f.write(text + "\n")
    print(text, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
