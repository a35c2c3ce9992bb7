"""One run of one cell: set-up, measured window, check, result line.

Everything specific to a configuration, traffic mix or metric is found
by name under this directory (see the package docstring); this module
only sequences a run:

1. check that JAX sees the chips the cell asks for, on a TPU;
2. turn on the program's persistent compile cache
   (``repro.compile_cache``) at its fixed path;
3. make the configuration's data from its fixed data seed and build the
   system under test;
4. make the traffic from the run's seed and warm every shape it uses
   (``setup_s`` ends here);
5. measure for ``--seconds`` (traced by the profiler with ``--trace 1``);
6. read the device's memory peak, drop the system, run the reference
   over a sample of the answers and decide ``correct``;
7. print the numbers compared, and the result line.
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import hashlib
import importlib.util
import json
import os
import pathlib
import sys
import time
from typing import Any, Optional

import numpy as np

ROOT = pathlib.Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"
OUT = ROOT / "bench_runs"


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def process_start_age() -> float:
    """Seconds since this process started, from the kernel's record."""
    try:
        with open("/proc/self/stat") as f:
            start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        return uptime - start_ticks / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError):
        return 0.0


def load_json(kind: str, name: str) -> dict:
    with open(BENCH / kind / f"{name}.json") as f:
        return json.load(f)


def load_module(kind: str, name: str):
    """``bench/<kind>/<name>.py``, loaded by file name (metric names
    hold dots)."""
    path = BENCH / kind / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no {kind} named {name!r} ({path})")
    key = f"bench._{kind}.{name}"
    mod = sys.modules.get(key)
    if mod is None:
        spec = importlib.util.spec_from_file_location(key, path)
        mod = importlib.util.module_from_spec(spec)
        sys.modules[key] = mod
        spec.loader.exec_module(mod)
    return mod


def load_spec() -> dict:
    with open(ROOT / "BENCHMARK.json") as f:
        return json.load(f)


def cell_metrics(spec: dict, cell: str, section: str) -> list:
    """The metrics of ``section`` this cell reports: those that list it,
    and those that list no cells (a per-layer one only where the cell
    reports the end-to-end metric it moves)."""
    e2e = [m for m in spec["end_to_end"]
           if cell in m.get("workloads", [cell])]
    if section == "end_to_end":
        return e2e
    moved = {m["name"] for m in e2e}
    return [m for m in spec["per_layer"]
            if cell in m.get("workloads", ())
            or ("workloads" not in m and m["moves"] in moved)]


def subseed(seed: int, tag: str) -> int:
    """A seed for one purpose, derived from the run's seed."""
    h = hashlib.sha256(f"{int(seed)}:{tag}".encode()).digest()
    return int.from_bytes(h[:8], "little")


@dataclasses.dataclass
class Context:
    cell: str
    seed: int
    seconds: float
    cfg: dict
    mix: dict
    datagen: Any
    data: Optional[np.ndarray] = None

    def subseed(self, tag: str) -> int:
        return subseed(self.seed, tag)


@dataclasses.dataclass
class Run:
    """What a metric reader reads."""

    ctx: Context
    setup_s: float
    record: Any
    device: dict
    trace: Any = None
    tiles: Optional[dict] = None
    peaks: Optional[dict] = None


class CompileClock:
    """Compilations JAX made since the last read, and their seconds, from
    its own monitoring events (a copy of the one in ``chip_smoke.py``)."""

    _EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
               "/jax/core/compile/jaxpr_to_mlir_module_duration",
               "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax
        self.seconds, self.count = 0.0, 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_kw):
        if event in self._EVENTS:
            self.seconds += duration
            self.count += event.endswith("backend_compile_duration")

    def read(self) -> tuple[int, float]:
        out = (self.count, self.seconds)
        self.count, self.seconds = 0, 0.0
        return out


def check_device(chips: int, require_tpu: bool = True):
    import jax
    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise NoChip(f"JAX found platform {devs[0].platform!r}, not 'tpu'; "
                     f"the benchmark never runs elsewhere")
    if len(devs) < chips:
        raise NoChip(f"the cell asks for {chips} chips, JAX sees "
                     f"{len(devs)}")
    return devs[0]


def make_context(cell: str, seed: int, seconds: float, cfg: dict,
                 mix: dict) -> Context:
    """The run's context with the configuration's data. The data comes
    from the configuration's fixed ``data.seed``, not the run's: every
    run of a configuration measures the same rows and the same index,
    and the run's seed orders the traffic and draws the check's
    sample."""
    ctx = Context(cell=cell, seed=int(seed), seconds=float(seconds),
                  cfg=cfg, mix=mix,
                  datagen=load_module("datagen", cfg["data"]["generator"]))
    ctx.data = ctx.datagen.generate(cfg["data"], int(cfg["data"]["seed"]))
    return ctx


def build_system(ctx: Context):
    """The system under test over ``ctx.data``, by the configuration's
    builder, with a build seed fixed by the data seed."""
    builder = load_module("builders", ctx.cfg["build"]["builder"])
    return builder.build(ctx.cfg, ctx.data,
                         subseed(int(ctx.cfg["data"]["seed"]), "build")
                         % (2**31))


class GcPauses:
    """The collector's pauses while the window runs (``gc.callbacks``):
    counts and seconds per generation, and the longest, for the side
    file."""

    def __init__(self):
        self.by_gen = {}
        self.longest = (0.0, -1)
        self._t = None

    def _on(self, phase, info):
        if phase == "start":
            self._t = time.perf_counter()
        elif self._t is not None:
            took = time.perf_counter() - self._t
            n, s = self.by_gen.get(info["generation"], (0, 0.0))
            self.by_gen[info["generation"]] = (n + 1, s + took)
            self.longest = max(self.longest, (took, info["generation"]))
            self._t = None

    def __enter__(self):
        gc.callbacks.append(self._on)
        return self

    def __exit__(self, *exc):
        gc.callbacks.remove(self._on)

    def summary(self) -> dict:
        return {"collections": {str(g): n for g, (n, _) in
                                sorted(self.by_gen.items())},
                "seconds": {str(g): s for g, (_, s) in
                            sorted(self.by_gen.items())},
                "longest_s": self.longest[0],
                "longest_generation": self.longest[1]}


def span_factory(tracing: bool):
    import jax
    if tracing:
        return jax.profiler.TraceAnnotation
    return lambda name: contextlib.nullcontext()


def instrument(system, span) -> None:
    """Bracket every call into the engine, whoever makes it (the closed
    loop or the scheduler's thread), with ``bench.engine_call``, and the
    megastep engine's dispatch and finalize halves inside it with
    ``bench.dispatch`` / ``bench.finalize``. The wrappers sit on the
    instances; the program itself is not touched."""
    me = system.engine.megastep_engine
    targets = [(system.engine, "join_batch", "bench.engine_call"),
               (me, "dispatch", "bench.dispatch"),
               (me, "finalize", "bench.finalize")]
    for obj, attr, name in targets:
        fn = getattr(obj, attr)

        def wrapped(*a, _fn=fn, _name=name, **kw):
            with span(_name):
                return _fn(*a, **kw)
        setattr(obj, attr, wrapped)


def tile_counts(system, record) -> Optional[dict]:
    """Scheduled and total (R tile, S tile) pairs of the window's batches,
    and the tile shape, by the engine's own count (after the window)."""
    me = system.engine.megastep_engine
    if not record.batch_queries:
        return None
    cfg = system.engine.config
    bm_cap = 1 << (int(cfg.tile_r).bit_length() - 1)
    visited = total = 0
    per = []
    for q in record.batch_queries:
        v, t = me.tile_counts(q)
        bucket = me.bucket_for(q.shape[0])
        bm = min(bucket, bm_cap)
        nr = -(-bucket // bm)
        visited += v
        total += t
        per.append([int(v), int(t), int(bm), int(nr)])
    return {"visited": visited, "total": total, "bn": int(cfg.tile_s),
            "per_batch": per}


def judge(ctx: Context, record, n_sample: int):
    """(correct, {number: {value, limit}}, rows checked), from a sample
    of the answered rows drawn from the seed."""
    from bench import correct

    numbers = {"unanswered": record.unanswered}
    n_checked = 0
    if record.queries.shape[0]:
        ref = load_module("references", ctx.cfg["metric"])
        rng = np.random.default_rng(ctx.subseed("sample"))
        pick = correct.sample_rows(record.queries.shape[0], n_sample, rng,
                                   record.must)
        q = record.queries[pick]
        dref = ref.truth(ref.Searcher(ctx.data), q, int(ctx.cfg["k"]))
        numbers.update(correct.gaps(q, ctx.data, record.dists[pick],
                                    record.ids[pick], dref,
                                    ref.exact_dists))
        n_checked = int(pick.size)
    ok, table = correct.verdict(numbers, ctx.cfg["check"]["limits"])
    return ok and n_checked > 0, table, n_checked


def run(cell: str, seed: int, seconds: float, trace: bool, *,
        require_tpu: bool = True, spec: Optional[dict] = None,
        cfg: Optional[dict] = None, mix: Optional[dict] = None,
        out_dir: Optional[pathlib.Path] = None) -> dict:
    """One run; returns the result line's object. ``spec``/``cfg``/``mix``
    default to the files found by name (tests pass small ones)."""
    t_age = process_start_age()
    t_mark = time.perf_counter()
    spec = spec or load_spec()
    cells = {c["name"]: c for c in spec["workloads"]}
    if cell not in cells:
        raise KeyError(f"no workload named {cell!r} in BENCHMARK.json")
    c = cells[cell]
    dev = check_device(int(c["chips"]), require_tpu)
    import jax
    from repro import compile_cache
    cache_dir = compile_cache.enable_compile_cache()
    clock = CompileClock()

    cfg = cfg or load_json("configs", c["config"])
    mix = mix or load_json("traffic", c["traffic"])
    ctx = make_context(cell, seed, seconds, cfg, mix)
    system = build_system(ctx)
    loop = load_module("loops", mix["loop"])
    span = span_factory(trace)
    instrument(system, span)
    p = loop.plan(ctx)
    loop.warm(system, p)
    n_warm_compiles, warm_compile_s = clock.read()
    setup_s = t_age + time.perf_counter() - t_mark

    trace_dir = None
    if trace:
        out = out_dir or OUT
        trace_dir = out / f"trace-{cell}-{seed}"
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0     # host spans only: no per-call cost
        jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    pauses = GcPauses()
    try:
        with pauses:
            record = loop.measure(system, p, float(seconds), span)
    finally:
        if trace:
            jax.profiler.stop_trace()
    n_window_compiles, window_compile_s = clock.read()
    stats = dev.memory_stats() or {}
    peak = int(stats.get("peak_bytes_in_use", 0))
    tiles = tile_counts(system, record) if trace else None

    del system, p
    gc.collect()
    ok, table, n_checked = judge(ctx, record,
                                 int(cfg["check"]["sample_rows"]))

    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": int(c["chips"]), "memory_peak_bytes": peak}
    run_ = Run(ctx=ctx, setup_s=setup_s, record=record, device=device,
               tiles=tiles)
    breakdown = layout = None
    if trace:
        from bench import trace as tr
        xplane = tr.find_xplane(str(trace_dir))
        red = tr.reduce_events(tr.load_events(xplane))
        run_.trace = red
        layout = tr.plane_summary(xplane)
        with open(BENCH / "peaks.json") as f:
            peaks = json.load(f)["devices"]
        if dev.device_kind not in peaks and require_tpu:
            raise KeyError(f"device kind {dev.device_kind!r} is not in "
                           f"bench/peaks.json")
        run_.peaks = peaks.get(dev.device_kind)
        device["busy_s"] = red.busy_ns() / 1e9
        device["window_s"] = red.window_ns / 1e9
        breakdown = tr.breakdown(red)
    section = "per_layer" if trace else "end_to_end"
    metrics = {}
    for m in cell_metrics(spec, cell, section):
        value = load_module("metrics", m["name"]).read(run_)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}

    side = {
        "workload": cell, "seed": int(seed), "seconds": float(seconds),
        "trace": bool(trace), "compile_cache": cache_dir,
        "setup_s": setup_s,
        "compiles_in_setup": n_warm_compiles,
        "compile_s_in_setup": warm_compile_s,
        "compiles_in_window": n_window_compiles,
        "compile_s_in_window": window_compile_s,
        "gc_in_window": pauses.summary(),
        "memory": {k: int(v) for k, v in stats.items()
                   if isinstance(v, (int, np.integer))},
        "tiles": {k: v for k, v in (tiles or {}).items()},
        "scheduler": record.sched, "batches": record.batches,
        "rows_checked": n_checked, "check": table,
        "end_to_end": record.values, **record.side,
        "trace_layout": layout,
    }
    (out_dir or OUT).mkdir(parents=True, exist_ok=True)
    side_path = (out_dir or OUT) / \
        f"{cell}-{seed}-trace{int(trace)}-{os.getpid()}.json"
    with open(side_path, "w") as f:
        json.dump(side, f, indent=1, default=float)

    result = {"correct": ok, "attempted": int(record.attempted),
              "failed": int(record.failed), "metrics": metrics,
              "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["check"] = table
    return result
