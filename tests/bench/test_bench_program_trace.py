"""The program's spans in a trace (``bench/program_trace.py``): they
leave the benchmark's own reduction as it was, and the attribution,
per-span idle, clock check and scope reading give the values worked out
by hand on small traces."""
import json
import os
import struct
import types

import numpy as np
import pytest

from bench import harness
from bench import program_trace as pt
from bench import trace as tr

DEV = "/device:TPU:0"
HOST = "/host:CPU"
LAUNCHER = "python#1"
CALLER = "python#0"
FIXTURES = os.path.join(os.path.dirname(__file__), "fixtures")
KERNEL = '%gather_topk.1 = custom-call(), custom_call_target="tpu_custom_call"'

EXISTING = ("sched.queue_ms", "sched.batch_rows", "serve.longest_gap_ms",
            "engine.exposed_ms.serve", "engine.exposed_ms.batch",
            "step.device_ms", "schedule.visited_frac",
            "gather_topk_roofline", "device.idle.batch",
            "device.idle.serve")


def ev(plane, line, name, start, end):
    return tr.Event(plane, line, name, float(start), float(end - start))


def bench_events():
    """A window of two engine calls (the benchmark's spans and the
    device's ops and programs; times in ns)."""
    return [
        ev(HOST, "python", "bench.window", 100, 1100),
        ev(HOST, "python", "bench.engine_call", 110, 500),
        ev(HOST, "python", "bench.dispatch", 120, 220),
        ev(HOST, "python", "bench.finalize", 220, 480),
        ev(HOST, "python", "bench.engine_call", 600, 1000),
        ev(DEV, "XLA Ops", "fusion.1", 50, 150),           # clipped in
        ev(DEV, "XLA Ops", "fusion.2", 200, 300),
        ev(DEV, "XLA Ops", KERNEL, 300, 400),
        ev(DEV, "XLA Ops", "fusion.3", 350, 450),          # overlaps
        ev(DEV, "XLA Ops", KERNEL, 650, 950),
        ev(DEV, "XLA Modules", "jit__megastep(1)", 190, 450),
        ev(DEV, "XLA Modules", "jit__megastep(1)", 645, 950),
        ev(DEV, "XLA Ops", "late", 1200, 1250),            # after window
    ]


def program_events():
    """The program's spans over the same window: two scheduler turns on
    the consumer's line, admissions on the caller's."""
    d, c = LAUNCHER, CALLER
    return [
        ev(HOST, d, "serve.step", 110, 500),
        ev(HOST, d, "serve.coalesce", 110, 120),
        ev(HOST, d, "megastep.dispatch", 120, 220),
        ev(HOST, d, "megastep.enqueue", 130, 170),
        ev(HOST, d, "megastep.device_step", 180, 210),
        ev(HOST, d, "megastep.fetch", 220, 480),
        ev(HOST, d, "megastep.fetch.wait", 220, 460),
        ev(HOST, d, "megastep.fetch.copy", 460, 480),
        ev(HOST, d, "serve.complete", 480, 495),
        ev(HOST, d, "serve.step", 600, 1000),
        ev(HOST, d, "megastep.dispatch", 610, 660),
        ev(HOST, d, "megastep.device_step", 640, 655),
        ev(HOST, d, "megastep.fetch", 660, 980),
        ev(HOST, d, "megastep.fetch.wait", 660, 955),
        ev(HOST, d, "megastep.fetch.copy", 955, 980),
        ev(HOST, d, "serve.complete", 980, 990),
        ev(HOST, d, "serve.wait", 1000, 1100),
        ev(HOST, c, "serve.admission", 455, 458),
        ev(HOST, c, "serve.admission", 520, 530),
        ev(HOST, c, "serve.admission", 700, 705),
    ]


def recorded():
    with open(os.path.join(FIXTURES, "bulk_two_calls.json")) as f:
        return [tr.Event(*row) for row in json.load(f)["events"]]


def recorded_program_spans(events):
    """Program spans laid over the recorded fixture where the program
    opens them: a dispatch inside each ``bench.dispatch``, a fetch split
    in two inside each ``bench.finalize``."""
    out = []
    for e in events:
        if e.name == "bench.dispatch":
            out.append(ev(HOST, LAUNCHER, "megastep.dispatch", e.start_ns + 1,
                          e.end_ns - 1))
        elif e.name == "bench.finalize":
            mid = e.start_ns + 0.9 * e.dur_ns
            out += [ev(HOST, LAUNCHER, "megastep.fetch", e.start_ns + 1,
                       e.end_ns - 1),
                    ev(HOST, LAUNCHER, "megastep.fetch.wait", e.start_ns + 2,
                       mid),
                    ev(HOST, LAUNCHER, "megastep.fetch.copy", mid,
                       e.end_ns - 2)]
    return out


def fake_run(red):
    """A run whose every existing reader has something to read."""
    ctx = types.SimpleNamespace(cell="none", seed=0, cfg={"k": 10},
                                data=np.zeros((1, 128), np.float32))
    record = types.SimpleNamespace(
        batches=[{"rows": 4096}], values={},
        tickets=[{"due": 0.0, "dispatched_at": 0.01, "completed_at": 0.05,
                  "rows": 3, "status": "done"},
                 {"due": 0.02, "dispatched_at": 0.04, "completed_at": 0.2,
                  "rows": 5, "status": "done"}],
        sched={"rows_completed": 8, "n_dispatches": 2})
    tiles = {"visited": 10, "total": 20, "bn": 512,
             "per_batch": [[5, 10, 128, 32], [5, 10, 128, 32]]}
    return harness.Run(ctx=ctx, setup_s=1.0, record=record, device={},
                       trace=red, tiles=tiles,
                       peaks={"bf16_flops_per_s": 1.97e14,
                              "hbm_bytes_per_s": 8.19e11})


@pytest.mark.parametrize("source", ["synthetic", "recorded"])
def test_program_spans_leave_the_bench_reduction_as_it_was(source):
    base = bench_events() if source == "synthetic" else recorded()
    extra = program_events() if source == "synthetic" \
        else recorded_program_spans(base)
    assert extra
    plain = tr.reduce_events(base)
    mixed = tr.reduce_events(base + extra)
    assert mixed.spans == plain.spans
    assert tr.exposed_ms(mixed) == tr.exposed_ms(plain)
    assert tr.idle_gaps(mixed) == tr.idle_gaps(plain)
    assert tr.breakdown(mixed) == tr.breakdown(plain)
    read = 0
    for name in EXISTING:
        reader = harness.load_module("metrics", name)
        value = reader.read(fake_run(plain))
        assert reader.read(fake_run(mixed)) == value, name
        read += value is not None
    assert read == len(EXISTING)


def test_idle_by_innermost_program_span():
    red = tr.reduce_events(bench_events())
    out = pt.idle_by_program_span(red, program_events())
    # idle [150,200), [450,650), [950,1100): 400 ns; cut where program
    # spans open and close, each piece labelled by the innermost span of
    # the launching line, else of the caller's line
    assert out["idle_s"] == pytest.approx(400e-9)
    want = {"megastep.enqueue": (20, 1, 20), "megastep.dispatch": (40, 2, 30),
            "megastep.device_step": (30, 2, 20),
            "megastep.fetch.wait": (15, 2, 10),
            "megastep.fetch.copy": (45, 2, 25),
            "serve.complete": (25, 2, 15), "serve.step": (25, 3, 10),
            "serve.wait": (100, 1, 100), "serve.admission": (10, 1, 10)}
    got = {k: (v["seconds"] * 1e9, v["count"], v["longest_s"] * 1e9)
           for k, v in out["by_span"].items()}
    assert got == {k: pytest.approx(v) for k, v in want.items()}
    assert out["no_program_span_s"] == pytest.approx(90e-9)
    assert out["attributed_share"] == pytest.approx(1 - 90 / 400)


def test_idle_inside_spans_and_the_schedulers_own():
    red = tr.reduce_events(bench_events())
    prog = program_events()
    # dispatch: 100 - 50 busy, 50 - 10 busy; fetch: 260 - 230, 320 - 290
    both = pt.with_program(red, prog)
    assert tr.exposed_ms(both, "megastep.dispatch") == pytest.approx(45e-6)
    assert tr.exposed_ms(both, "megastep.fetch") == pytest.approx(30e-6)
    assert tr.exposed_ms(both, "megastep.fetch.wait") == \
        pytest.approx(7.5e-6)
    assert tr.exposed_ms(both, "megastep.fetch.copy") == \
        pytest.approx(22.5e-6)
    assert tr.exposed_ms(both, "none") is None
    # steps: [110,120) busy + [480,500) idle 20; [600,610) 10 + [980,
    # 1000) 20 — the rest of each step is dispatch or fetch
    assert pt.sched_idle_ms(red, prog) == pytest.approx(25e-6)
    assert pt.sched_idle_ms(red, []) is None


def test_clock_check_matches_each_wait_with_its_program():
    red = tr.reduce_events(bench_events())
    out = pt.clock_check(red, program_events())
    assert out["batches"] == 2 and out["share_ok"] == 1.0
    assert out["lag_ms"] == pytest.approx(
        {"min": 5e-6, "median": 7.5e-6, "max": 10e-6})
    assert out["lead_ms"] == pytest.approx(
        {"min": 5e-6, "median": 7.5e-6, "max": 10e-6})
    assert pt.clock_check(red, program_events(), slack_ns=7)[
        "share_ok"] == 0.5


def test_admissions_against_fetches():
    red = tr.reduce_events(bench_events())
    out = pt.admissions_vs_fetch(red, program_events())
    assert out["admissions"] == 3 and out["fetches"] == 2
    assert out["share_starting_inside_fetch"] == pytest.approx(2 / 3)
    assert out["share_of_window_inside_fetch"] == pytest.approx(0.58)
    assert out["share_starting_within_1ms_after_fetch"] == \
        pytest.approx(1 / 3)
    assert out["share_of_window_within_1ms_after_fetch"] == \
        pytest.approx(0.3)
    assert out["admission_ms"]["median"] == pytest.approx(5e-6)


# ---------------------------------------- op scopes from the XSpace


def _varint(n):
    out = bytearray()
    while True:
        b = n & 0x7F
        n >>= 7
        out.append(b | (0x80 if n else 0))
        if not n:
            return bytes(out)


def _msg(*fields):
    out = b""
    for num, v in fields:
        if isinstance(v, float):
            out += _varint(num << 3 | 1) + struct.pack("<d", v)
        elif isinstance(v, int):
            out += _varint(num << 3) + _varint(v)
        else:
            v = v.encode() if isinstance(v, str) else v
            out += _varint(num << 3 | 2) + _varint(len(v)) + v
    return out


def xspace(op_scope_pairs, ref_op=None, window=(100, 1100)):
    """An XSpace with a device plane whose event metadata carry the
    given ``tf_op`` stats (one by reference), and a host plane whose
    metadata must be ignored, holding one ``bench.window`` span from
    ``window[0]`` to ``window[1]`` ns."""
    stat_md = [_msg((1, 1), (2, _msg((1, 1), (2, "tf_op")))),
               _msg((1, 2), (2, _msg((1, 2), (2, "flops")))),
               _msg((1, 3), (2, _msg((1, 3), (2, "jit(_megastep)/"
                                                 "schedule/iota:"))))]
    ev_md = []
    for i, (op, scope) in enumerate(op_scope_pairs, start=10):
        stats = [(5, _msg((1, 2), (2, 1.5e6)))]
        if scope:
            stats.append((5, _msg((1, 1), (5, scope))))
        ev_md.append(_msg((1, i), (2, _msg((1, i), (2, op), *stats))))
    if ref_op:
        ev_md.append(_msg((1, 99), (2, _msg((1, 99), (2, ref_op),
                                            (5, _msg((1, 1), (7, 3)))))))
    dev = _msg((1, 7), (2, DEV), *[(4, m) for m in ev_md],
               *[(5, m) for m in stat_md])
    lo, hi = window
    line = _msg((1, 1), (2, "python"), (3, 0),
                (4, _msg((1, 6), (2, lo * 1000), (3, (hi - lo) * 1000))))
    host = _msg((2, HOST), (3, line), (4, _msg((1, 5), (2, _msg(
        (1, 5), (2, "host op"), (5, _msg((1, 1), (5, "jit(x)/assign/y:"))))))),
        (4, _msg((1, 6), (2, _msg((1, 6), (2, "bench.window"))))),
        (5, stat_md[0]))
    return _msg((1, dev), (1, host), (4, "a-host"))


def test_op_scopes_read_from_the_event_metadata(tmp_path):
    p = tmp_path / "t.xplane.pb"
    p.write_bytes(xspace([("fusion.1", "jit(_megastep)/assign/dot_general:"),
                          ("copy.9", None)], ref_op="fusion.2"))
    assert pt.op_scopes(str(p)) == {
        "fusion.1": "jit(_megastep)/assign/dot_general:",
        "fusion.2": "jit(_megastep)/schedule/iota:"}


def test_plan_ms_counts_the_planning_scopes_per_program():
    red = tr.reduce_events(bench_events())
    scopes = {"fusion.1": "jit(_megastep)/assign/dot_general:",
              "fusion.2": "jit(_megastep)/bounds/top_k:",
              "fusion.3": "jit(other)/schedule/x:",
              KERNEL: "jit(_megastep)/gather_topk/gather_topk/pallas_call:"}
    # (100 + 100) ns over two programs
    assert pt.plan_ms(red, scopes) == pytest.approx(100e-6)
    assert pt.plan_ms(red, {}) is None


def test_plan_reader_finds_the_runs_trace(tmp_path, monkeypatch):
    red = tr.reduce_events(bench_events())
    run = fake_run(red)
    reader = harness.load_module("metrics", "step.plan_ms")
    monkeypatch.setattr(harness, "OUT", tmp_path)
    assert reader.read(run) is None              # no trace written
    d = tmp_path / f"trace-{run.ctx.cell}-{run.ctx.seed}" / "plugins"
    d.mkdir(parents=True)
    (d / "h.xplane.pb").write_bytes(xspace(
        [("fusion.1", "jit(_megastep)/assign/dot_general:"),
         ("fusion.2", "jit(_megastep)/bounds/top_k:")]))
    assert reader.read(run) == pytest.approx(100e-6)
    # an earlier run's trace, newer by name, with another window
    (d / "z.xplane.pb").write_bytes(xspace(
        [("fusion.1", "jit(_megastep)/bounds/top_k:")], window=(90, 1100)))
    assert pt.run_xplane(run) == str(d / "h.xplane.pb")
    assert reader.read(run) == pytest.approx(100e-6)
    # a program without the scopes (the parent's) reads nothing
    (d / "h.xplane.pb").write_bytes(xspace(
        [("fusion.1", "jit(_megastep)/dot_general:")]))
    assert reader.read(run) is None
    (d / "h.xplane.pb").unlink()
    assert reader.read(run) is None              # only the stale trace


def test_plan_reader_passes_over_a_stale_trace(tmp_path, monkeypatch):
    """A traced run written into its own directory, with an earlier
    run's trace of the same cell and seed in the harness's: the reader
    opens no file of the earlier run."""
    import bench_small

    seed = 3_000_000_021
    cell = "forest10.join"
    monkeypatch.setattr(harness, "OUT", tmp_path / "bench_runs")
    stale = harness.OUT / f"trace-{cell}-{seed}" / "plugins"
    stale.mkdir(parents=True)
    (stale / "h.xplane.pb").write_bytes(xspace(
        [("fusion.1", "jit(_megastep)/assign/dot_general:")]))
    opened = []
    scopes = pt.op_scopes

    def spy(path):
        opened.append(path)
        return scopes(path)

    monkeypatch.setattr(pt, "op_scopes", spy)
    res = bench_small.run_small(cell, seed, tmp_path / "run", trace=True,
                                monkeypatch=monkeypatch)
    assert res["correct"]
    assert "device.idle.batch" in res["metrics"]
    assert "step.plan_ms" not in res["metrics"]
    assert opened == []


def test_program_trace_on_a_small_online_run(tmp_path, monkeypatch):
    """The small online cell through the harness with the program's
    tracer in profiler mode: the result line holds the cell's metrics,
    and the trace's program spans cover the window's idle time (all of
    it, on a CPU: there is no device plane)."""
    from repro import obs

    import bench_small

    spec, cfg, mix = bench_small.small("sift1m.online")
    loop = harness.load_module("loops", mix["loop"])
    measure = loop.measure

    def traced(*a, **kw):
        obs.install(obs.Tracer(profiler=True))
        try:
            return measure(*a, **kw)
        finally:
            obs.uninstall()

    monkeypatch.setattr(loop, "measure", traced)
    from repro import compile_cache
    monkeypatch.setattr(compile_cache, "enable_compile_cache",
                        lambda: "off")
    seed = 3_000_000_019
    res = harness.run("sift1m.online", seed, 2.0, True, require_tpu=False,
                      spec=spec, cfg=cfg, mix=mix, out_dir=tmp_path)
    assert res["correct"]
    assert {"sched.queue_ms", "engine.exposed_ms.serve",
            "device.idle.serve"} <= set(res["metrics"])
    out = pt.reduce_program(tr.find_xplane(
        str(tmp_path / f"trace-sift1m.online-{seed}")))
    assert not obs.enabled()
    assert out["program_spans"] > 0
    assert out["idle_by_program_span"]["attributed_share"] > 0.9
    assert "serve.wait" in out["idle_by_program_span"]["by_span"]
    for key in ("engine.dispatch_idle_ms", "engine.fetch_idle_ms",
                "sched.idle_ms"):
        assert out[key] is not None and out[key] >= 0, key
    assert out["admissions"]["admissions"] > 0
