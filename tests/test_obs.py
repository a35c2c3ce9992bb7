"""Flight recorder (repro.obs): span tracer, metrics registry,
exporters, per-query explain — and their integration with the serving
stack.

The load-bearing properties:

* tracing is **off by default** and the disabled path records nothing
  (one ``None`` check; the shared ``NULL_SPAN`` sinks every call);
* enabled tracing is bounded (ring buffer drops oldest), thread-aware
  (same-thread parent links), and **never touches device values** —
  a traced steady-state megastep runs under
  ``jax.transfer_guard("disallow")`` and every recorded attribute is a
  host-side value;
* the metrics registry's fixed-bucket histograms give p50/p99/p999
  without stored samples, and render in Prometheus text format;
* ``explain(ticket)`` reconstructs one request's span tree, including
  a retried + failed-over request where the failed attempt, the
  failover remask, and the deadline re-check each appear exactly once
  (the incident-audit contract);
* ``JoinStats.merged`` folds per-attempt stats without the silent
  overwrite the shared-stats threading used to cause, and
  ``ServeScheduler.snapshot`` hands back an immutable copy.
"""
import json
import threading

import numpy as np
import pytest

from repro import obs
from repro.core import JoinConfig, StreamJoinEngine, build_index
from repro.core.types import JoinStats
from repro.serve.faultinject import FaultPlan, ShardFault
from repro.serve.scheduler import (SchedulerConfig, ServeScheduler,
                                   VirtualClock)

DIM = 6


def _data(n=400, seed=0):
    rng = np.random.default_rng(seed)
    return (rng.normal(size=(n, DIM)).astype(np.float32) * 2).copy()


def _index(n=400, k=5):
    cfg = JoinConfig(k=k, n_pivots=24, n_groups=6, grouping="geometric")
    return build_index(_data(n), cfg), cfg


# ------------------------------------------------------------- tracer

def test_tracing_disabled_records_nothing():
    assert not obs.enabled()
    sp = obs.span("x", a=1)
    assert sp is obs.trace.NULL_SPAN
    with sp as s:
        s.set(b=2)                       # sinks silently
    assert obs.event("y", c=3) is None
    assert obs.trace.current() is None


def test_span_nesting_parent_links_and_attrs():
    with obs.capture() as tr:
        with obs.span("outer", rows=4) as so:
            with obs.span("inner") as si:
                si.set(outcome="ok")
            obs.event("mark", at="inside")
        assert so.duration_s >= 0
    spans = tr.spans()
    by_name = {s.name: s for s in spans}
    # inner lands before outer (recorded on exit), both present
    assert [s.name for s in spans] == ["inner", "mark", "outer"]
    assert by_name["inner"].parent_id == by_name["outer"].span_id
    assert by_name["mark"].parent_id == by_name["outer"].span_id
    assert by_name["outer"].parent_id == 0
    assert by_name["inner"].attrs["outcome"] == "ok"
    assert by_name["outer"].attrs["rows"] == 4
    # tracing is off again outside the capture
    assert not obs.enabled()


def test_span_exception_stamps_error_outcome():
    with obs.capture() as tr:
        with pytest.raises(ValueError):
            with obs.span("boom"):
                raise ValueError("x")
    (sp,) = tr.spans()
    assert sp.attrs["outcome"] == "error:ValueError"


def test_ring_buffer_drops_oldest():
    with obs.capture(capacity=4) as tr:
        for i in range(10):
            obs.event("e", i=i)
    assert len(tr) == 4
    assert [s.attrs["i"] for s in tr.spans()] == [6, 7, 8, 9]


def test_parent_links_never_cross_threads():
    with obs.capture() as tr:
        with obs.span("main-side"):
            t = threading.Thread(
                target=lambda: obs.event("worker-side"))
            t.start()
            t.join()
    ev = next(s for s in tr.spans() if s.name == "worker-side")
    assert ev.parent_id == 0               # root in its own thread


# ------------------------------------------------------------ metrics

def test_counter_and_gauge():
    with obs.metrics.scoped() as reg:
        c = reg.counter("hits")
        c.inc()
        c.inc(2)
        assert c.value == 3
        with pytest.raises(ValueError):
            c.inc(-1)
        # same (name, labels) → same object; labels split series
        assert reg.counter("hits") is c
        assert reg.counter("hits", site="a") is not c
        g = reg.gauge("depth")
        g.set(7)
        g.inc(-2)
        assert g.value == 5


def test_histogram_quantiles_without_samples():
    with obs.metrics.scoped() as reg:
        h = reg.histogram("lat", buckets=tuple(float(b) for b in
                                               range(1, 101)))
        for v in range(1, 101):            # uniform 1..100
            h.observe(float(v))
        assert h.count == 100
        assert h.quantile(0.5) == pytest.approx(50.0, abs=1.0)
        assert h.quantile(0.99) == pytest.approx(99.0, abs=1.0)
        assert h.quantile(1.0) == pytest.approx(100.0, abs=1.0)
        h.observe(1e9)                     # overflow clamps to last bound
        assert h.quantile(1.0) == 100.0
        empty = reg.histogram("none")
        assert np.isnan(empty.quantile(0.5))
        with pytest.raises(ValueError):
            h.quantile(1.5)
        snap = reg.snapshot()
        assert snap["lat_count"] == 101.0
        assert "lat_p999" in snap


def test_histogram_rejects_unsorted_buckets():
    with obs.metrics.scoped() as reg:
        with pytest.raises(ValueError):
            reg.histogram("bad", buckets=(2.0, 1.0))


def test_prometheus_rendering():
    with obs.metrics.scoped() as reg:
        reg.counter("req_total", site="a").inc(3)
        reg.gauge("depth").set(2)
        h = reg.histogram("lat_s", buckets=(0.1, 1.0))
        h.observe(0.05)
        h.observe(0.5)
        h.observe(5.0)
        text = obs.render_prometheus(reg)
    assert '# TYPE req_total counter' in text
    assert 'req_total{site="a"} 3' in text
    assert 'depth 2' in text
    assert 'lat_s_bucket{le="0.1"} 1' in text
    assert 'lat_s_bucket{le="1"} 2' in text
    assert 'lat_s_bucket{le="+Inf"} 3' in text
    assert 'lat_s_count 3' in text


def test_scoped_registry_restores_global():
    base = obs.metrics.REGISTRY
    with obs.metrics.scoped() as reg:
        assert obs.metrics.REGISTRY is reg
        obs.metrics.REGISTRY.counter("x").inc()
    assert obs.metrics.REGISTRY is base


# ---------------------------------------------------------- exporters

def test_jsonl_and_chrome_trace_exports(tmp_path):
    with obs.capture() as tr:
        with obs.span("stage", rows=np.int64(3), sel=np.float32(0.5)):
            obs.event("flag", shard=0)
    spans = tr.spans()
    # JSONL: one valid object per line, numpy scalars made JSON-clean
    lines = obs.spans_to_jsonl(spans).strip().split("\n")
    assert len(lines) == 2
    recs = [json.loads(ln) for ln in lines]
    assert {r["name"] for r in recs} == {"stage", "flag"}
    stage = next(r for r in recs if r["name"] == "stage")
    assert stage["attrs"] == {"rows": 3, "sel": 0.5}
    # Chrome trace: durations are "X" phase in µs, instants are "i"
    p = tmp_path / "trace.json"
    obs.write_chrome_trace(spans, str(p))
    doc = json.loads(p.read_text())
    evs = {e["name"]: e for e in doc["traceEvents"]}
    assert evs["stage"]["ph"] == "X" and evs["stage"]["dur"] >= 0
    assert evs["flag"]["ph"] == "i"
    assert evs["flag"]["args"]["parent_id"] == evs["stage"]["args"][
        "span_id"]


def test_explain_builds_request_tree():
    with obs.capture() as tr:
        obs.event("serve.admission", ticket=7, outcome="admitted")
        with obs.span("serve.attempt", tickets=(7, 9), rung="engine"):
            with obs.span("megastep.device_step", bucket=16):
                pass
        obs.event("other.noise", ticket=8)
    roots = obs.explain(7, tr.spans())
    names = [n.span.name for r in roots for n in r.walk()]
    assert names == ["serve.admission", "serve.attempt",
                     "megastep.device_step"]
    # the engine child carries no ticket attr — pulled in via parent
    att = next(n for r in roots for n in r.walk()
               if n.span.name == "serve.attempt")
    assert att.children[0].span.name == "megastep.device_step"
    assert obs.explain(12345, tr.spans()) == []
    text = obs.format_explain(roots)
    assert "serve.attempt" in text and "megastep.device_step" in text
    with pytest.raises(ValueError):
        obs.explain(7)                     # no tracer, no spans
    with pytest.raises(TypeError):
        obs.explain("nope", tr.spans())


# ------------------------------------------------- JoinStats.merged

def test_joinstats_merged_semantics():
    a = JoinStats(n_r=10, n_s=400, pairs_computed=100,
                  pivot_pairs_computed=40, tiles_total=8, tiles_visited=4,
                  replicas_s=50, n_batches=1, recall_bound=0.9,
                  coverage_bound=0.8, n_failed_shards=1, n_shards=4,
                  quant_mode="int8", quant_mp=64, quant_autotuned=True,
                  n_segments=2, n_tombstones=3)
    b = JoinStats(n_r=5, n_s=400, pairs_computed=60,
                  pivot_pairs_computed=20, tiles_total=4, tiles_visited=1,
                  replicas_s=25, n_batches=1, recall_bound=0.95,
                  coverage_bound=0.7, n_failed_shards=2)
    m = a.merged(b)
    # counters sum; the originals are untouched
    assert (m.n_r, m.pairs_computed, m.pivot_pairs_computed) == (15, 160, 60)
    assert (m.tiles_total, m.tiles_visited, m.replicas_s) == (12, 5, 75)
    assert a.n_r == 10 and b.n_r == 5
    # n_s is a size, not work: max, so selectivity stays work-weighted
    assert m.n_s == 400
    assert m.selectivity == pytest.approx(220 / (15 * 400))
    # degradation keeps the worst
    assert m.recall_bound == 0.9
    assert m.coverage_bound == 0.7
    assert m.n_failed_shards == 2
    # routing fields keep the last writer iff it stamped them
    assert m.quant_mode == "int8" and m.quant_mp == 64
    assert m.n_shards == 4                 # b never stamped a mesh
    assert (m.n_segments, m.n_tombstones) == (2, 3)
    b2 = JoinStats(quant_mode="fp32", n_segments=5, n_tombstones=0,
                   n_shards=8)
    m2 = m.merged(b2)
    assert m2.quant_mode == "fp32" and m2.quant_autotuned is False
    assert (m2.n_segments, m2.n_tombstones) == (5, 0)
    assert m2.n_shards == 8


# -------------------------------------------- scheduler integration

def _host_sched():
    idx, cfg = _index()
    eng = StreamJoinEngine(idx, cfg)
    vc = VirtualClock()
    sched = ServeScheduler(eng, config=SchedulerConfig(),
                           clock=vc.now, sleep=vc.advance)
    return sched, eng


def test_scheduler_spans_carry_paper_metrics():
    """A traced request's span tree carries the §6 numbers live:
    tiles visited vs pruned, selectivity, replicas — as span attrs."""
    sched, eng = _host_sched()
    q = _data(8, seed=3)
    sched.join_now(q)                      # warm (untraced)
    with obs.capture() as tr:
        t = sched.join_now(q)
    assert t.done
    roots = obs.explain(t, tracer=tr)
    names = [n.span.name for r in roots for n in r.walk()]
    assert "serve.admission" in names
    assert "serve.coalesce" in names
    att = next(n.span for r in roots for n in r.walk()
               if n.span.name == "serve.attempt")
    assert att.attrs["outcome"] == "ok"
    assert att.attrs["tiles_total"] > 0
    assert att.attrs["tiles_pruned"] == (att.attrs["tiles_total"]
                                         - att.attrs["tiles_visited"])
    assert 0 < att.attrs["selectivity"] < 1
    assert att.attrs["replicas"] > 0
    assert "serve.complete" in names
    # every recorded attribute is host-side (the zero-sync contract)
    import jax
    for s in tr.spans():
        for v in s.attrs.values():
            assert not isinstance(v, jax.Array), (s.name, v)


def test_scheduler_metrics_published():
    sched, eng = _host_sched()
    with obs.metrics.scoped() as reg:
        sched.join_now(_data(8, seed=4))
        snap = reg.snapshot()
    assert snap["serve_submitted_total"] == 1
    assert snap["serve_completed_total"] == 1
    assert snap["serve_dispatch_total"] == 1
    assert snap["serve_latency_s_count"] == 1
    assert snap["serve_latency_s_p99"] >= 0


def test_snapshot_returns_independent_copy():
    sched, eng = _host_sched()
    sched.join_now(_data(4, seed=5))
    snap = sched.snapshot()
    assert snap.n_completed == 1
    assert snap is not sched.stats
    assert snap.join is not sched.stats.join
    snap.n_completed = 99
    snap.join.n_r = 12345
    assert sched.stats.n_completed == 1
    assert sched.stats.join.n_r != 12345


def test_retry_merges_join_stats_instead_of_overwriting():
    """A transient fault forces dispatch → host-oracle retry; the
    aggregate JoinStats must hold the *sum* of both attempts' work,
    not whichever attempt wrote last."""
    sched, eng = _host_sched()
    q = _data(8, seed=6)
    sched.join_now(q)
    base = sched.snapshot().join
    with FaultPlan().fail("sched.dispatch", times=1):
        t = sched.join_now(q)
    assert t.done
    js = sched.snapshot().join
    assert sched.snapshot().n_retries == 1
    # the retried request contributes exactly one batch of rows once
    # (the faulted attempt died before the engine ran)
    assert js.n_r == base.n_r + q.shape[0]
    assert js.pairs_computed > base.pairs_computed


# ------------------------------- trace correctness under faults (sat. 3)

def test_fault_trace_failed_attempt_failover_recheck_once():
    """Armed FaultPlan (shard_compute fault → failover → re-check →
    retry rung): the request's span tree shows the failed attempt, the
    failover remask, and the deadline re-check each exactly once, with
    correct shard id / generation attributes."""
    idx, cfg = _index()
    eng = StreamJoinEngine(idx, cfg, megastep=True, n_shards=1)
    vc = VirtualClock()
    sched = ServeScheduler(
        eng, config=SchedulerConfig(max_inflight=2, backoff_base_s=0.05),
        clock=vc.now, sleep=vc.advance)
    q = _data(9, seed=9)
    sched.join_now(q)                      # warm the serving view
    with obs.capture() as tr:
        with FaultPlan().fail(
                "sharded.shard_compute", times=1,
                exc=ShardFault("sharded.shard_compute", shard=0)):
            t = sched.join_now(q)
    assert t.done and t.degraded
    spans = tr.spans()
    roots = obs.explain(t, spans)
    tree = [n.span for r in roots for n in r.walk()]

    failed = [s for s in tree if s.name == "serve.attempt"
              and s.attrs.get("outcome") == "shard_failed"]
    assert len(failed) == 1
    assert failed[0].attrs["shard"] == 0
    assert failed[0].attrs["pipelined"] is True

    remasks = [s for s in spans if s.name == "sharded.failover_remask"]
    assert len(remasks) == 1
    assert remasks[0].attrs["shard"] == 0
    # generation bumped 0 → 1 by exactly this failure
    assert remasks[0].attrs["generation"] == 1
    assert eng.megastep_engine.health.generation == 1
    # the remask is parented inside the failed attempt (same thread)
    assert remasks[0].parent_id == failed[0].span_id

    rechecks = [s for s in tree if s.name == "serve.deadline_recheck"]
    assert len(rechecks) == 1
    assert rechecks[0].attrs["shed"] == 0

    failovers = [s for s in tree if s.name == "serve.failover"]
    assert len(failovers) == 1
    assert failovers[0].attrs["shard"] == 0
    # the failed-over attempt then completed on the covered rung
    ok = [s for s in tree if s.name == "serve.attempt"
          and s.attrs.get("outcome") == "ok"]
    assert len(ok) == 1
    assert ok[0].attrs["rung"] == "covered"
    assert ok[0].attrs["coverage_bound"] == 0.0


def test_traced_megastep_steady_state_stays_transfer_free():
    """The zero-steady-state-sync invariant with tracing ENABLED:
    the fused device step runs under jax.transfer_guard("disallow")
    with a tracer installed — recording spans must not fetch."""
    import jax
    idx, cfg = _index()
    eng = StreamJoinEngine(idx, cfg, megastep=True)
    me = eng.megastep_engine
    q = _data(16, seed=2)
    eng.join_batch(q)                      # warm + compile
    qd, nv = me.enqueue(q)
    jax.block_until_ready(me.join_batch_device(qd, nv))
    with obs.capture() as tr:
        with jax.transfer_guard("disallow"):
            state = jax.block_until_ready(me.join_batch_device(qd, nv))
    names = [s.name for s in tr.spans()]
    assert "megastep.device_step" in names
    # the stages are named scopes of the device program, not host
    # instants: they reach the profiler's op metadata at no host cost
    assert stage_scopes(me, qd, nv, state) == {
        "assign", "bounds", "schedule", "gather_topk", "canonical",
        "merge"}


def stage_scopes(me, qd, nv, state):
    """The megastep's stage scopes found in its lowered program (the
    schedule-driven variant, which keeps stages 2-3 alive on CPU)."""
    import re

    from repro.core import megastep as ms
    p = me._refresh()
    text = ms._megastep.lower(
        qd, nv, p.dead_total, p.segs, p.tiles, state, k=me.config.k,
        bm=min(int(qd.shape[0]), me._bm_cap), bn=me._bn,
        metric=me.config.metric, dim=p.dim,
        n_finite_total=p.n_finite_total, seg_meta=p.seg_meta,
        primary=p.primary, impl="ref_sched").as_text(debug_info=True)
    return set(re.findall(r"jit\(_megastep\)/([a-z_]+)/", text))


def test_faultinject_publishes_crossing_metrics():
    with obs.metrics.scoped() as reg:
        with FaultPlan().fail("sched.dispatch", times=1):
            sched, eng = _host_sched()
            t = sched.join_now(_data(4, seed=8))
        assert t.done
        snap = reg.snapshot()
    assert snap['fault_crossings_total{site="sched.dispatch"}'] >= 2
    assert snap['fault_injected_total{site="sched.dispatch"}'] == 1


# --------------------------------------------- profiler sink (tracing)

def _profiled_host_spans(logdir):
    """Every host event of the trace in ``logdir`` as (line, name,
    start_ns, end_ns, stats); line = (plane, index of the line)."""
    import glob

    import jax
    path = sorted(glob.glob(str(logdir / "**" / "*.xplane.pb"),
                            recursive=True))[-1]
    out = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for li, line in enumerate(plane.lines):
            for e in line.events:
                out.append(((plane.name, li), e.name, e.start_ns,
                            e.start_ns + e.duration_ns, dict(e.stats)))
    return out


def _innermost_parent(ev, events):
    """Name of the shortest other event on ``ev``'s line that contains
    it, or None."""
    line, name, s, e, _ = ev
    outer = [o for o in events if o[0] == line and o is not ev
             and o[2] <= s and e <= o[3] and (o[3] - o[2]) > (e - s)]
    return min(outer, key=lambda o: o[3] - o[2])[1] if outer else None


PROGRAM_PARENTS = {
    "serve.admission": None,
    "serve.coalesce": "serve.step",
    "serve.attempt": "serve.step",
    "megastep.dispatch": "serve.attempt",
    "megastep.enqueue": "megastep.dispatch",
    "megastep.device_step": "megastep.dispatch",
    "megastep.fetch": "serve.attempt",
    "megastep.fetch.wait": "megastep.fetch",
    "megastep.fetch.copy": "megastep.fetch",
    "serve.complete": "serve.step",
}


def test_profiler_mode_puts_program_spans_on_the_host_plane(tmp_path):
    """With a profiler-mode tracer installed, a scheduler turn over the
    megastep engine writes its spans into the profiler's trace: on the
    host plane, nested as the code nests them, with the scalar
    attributes known when each opened (tuples and late ``set``
    attributes stay in the ring buffer)."""
    import jax
    idx, cfg = _index()
    eng = StreamJoinEngine(idx, cfg, megastep=True)
    sched = ServeScheduler(eng, config=SchedulerConfig())
    q = _data(8, seed=11)
    sched.join_now(q)                      # warm: compile + payload
    tr = obs.install(obs.Tracer(profiler=True))
    try:
        jax.profiler.start_trace(str(tmp_path))
        try:
            t = sched.join_now(q)
        finally:
            jax.profiler.stop_trace()
    finally:
        obs.uninstall()
    assert t.done
    events = _profiled_host_spans(tmp_path)
    program = [ev for ev in events if ev[1] in PROGRAM_PARENTS
               or ev[1] == "serve.step"]
    by_name = {}
    for ev in program:
        by_name.setdefault(ev[1], []).append(ev)
    assert set(PROGRAM_PARENTS) <= set(by_name)
    for name, parent in PROGRAM_PARENTS.items():
        for ev in by_name[name]:
            assert _innermost_parent(ev, program) == parent, name
    # scalar attributes known at open are forwarded, nothing else
    (adm,) = by_name["serve.admission"]
    assert adm[4]["ticket"] == t.ticket_id
    assert adm[4]["rows"] == q.shape[0]
    assert "outcome" not in adm[4]              # set later
    (step,) = by_name["megastep.device_step"]
    assert step[4]["impl"] == eng.megastep_engine.resolved_impl
    assert step[4]["bucket"] == 16
    (att,) = by_name["serve.attempt"]
    assert att[4]["rung"] == "engine" and "tickets" not in att[4]
    # the ring buffer still holds everything explain() reads
    ring = {s.name: s for s in tr.spans()}
    assert ring["serve.attempt"].attrs["tickets"] == (t.ticket_id,)
    assert ring["serve.admission"].attrs["outcome"] == "admitted"
    names = [n.span.name for r in obs.explain(t, tr.spans())
             for n in r.walk()]
    assert names[0] == "serve.admission" and "megastep.fetch.copy" in names


def test_profiler_mode_wait_span_on_the_consumer_thread(tmp_path):
    """``serve_forever``'s condition-variable wait is a span of the
    consumer thread's line; admissions stay on the caller's line."""
    import time

    import jax
    idx, cfg = _index()
    sched = ServeScheduler(StreamJoinEngine(idx, cfg, megastep=True))
    q = _data(4, seed=12)
    sched.join_now(q)
    obs.install(obs.Tracer(profiler=True))
    try:
        jax.profiler.start_trace(str(tmp_path))
        try:
            sched.serve_forever()
            time.sleep(0.05)
            t = sched.submit(q)
            deadline = time.monotonic() + 30
            while t.status == "queued" and time.monotonic() < deadline:
                time.sleep(0.005)
            sched.shutdown()
        finally:
            jax.profiler.stop_trace()
    finally:
        obs.uninstall()
    assert t.done
    events = _profiled_host_spans(tmp_path)
    lines = {name: {ev[0] for ev in events if ev[1] == name}
             for name in ("serve.wait", "serve.step", "serve.admission")}
    assert lines["serve.wait"] and lines["serve.step"] \
        and lines["serve.admission"]
    assert lines["serve.wait"] == lines["serve.step"]
    assert not lines["serve.wait"] & lines["serve.admission"]


def test_obs_imports_jax_only_when_a_profiler_tracer_installs():
    import subprocess
    import sys
    code = ("import sys; import repro.obs as obs; a = 'jax' in sys.modules;"
            " obs.install(obs.Tracer()); b = 'jax' in sys.modules;"
            " obs.install(obs.Tracer(profiler=True));"
            " print(a, b, 'jax' in sys.modules)")
    import os

    import repro
    src = os.path.dirname(os.path.dirname(repro.__file__))
    env = dict(os.environ, PYTHONPATH=src)
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=120, check=True, env=env)
    assert out.stdout.split() == ["False", "False", "True"]


def test_untraced_path_never_touches_the_profiler(monkeypatch):
    """No tracer installed: a dispatch/finalize and a scheduler turn
    construct no profiler annotation (the off path is one ``None``
    check per site), and the fetch adds no wait of its own before its
    copies."""
    import jax

    class Refuse:
        def __init__(self, *a, **kw):
            raise AssertionError("profiler annotation on the off path")

    def refuse_wait(*a, **kw):
        raise AssertionError("block_until_ready on the off path")

    idx, cfg = _index()
    eng = StreamJoinEngine(idx, cfg, megastep=True)
    me = eng.megastep_engine
    q = _data(16, seed=13)
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Refuse)
    monkeypatch.setattr(jax, "block_until_ready", refuse_wait)
    assert not obs.enabled()
    d, i = me.finalize(me.dispatch(q, stats=JoinStats()))
    assert d.shape == (16, cfg.k)
    t = ServeScheduler(eng).join_now(q)
    assert t.done


@pytest.mark.parametrize("engine", ["fp32", "int8_resident", "int8_host"])
def test_traced_dispatch_fetches_nothing(engine, monkeypatch):
    """Once the payload is resident, ``dispatch`` (with stats and a
    tracer installed) makes no device-to-host transfer: the transfer
    guard refuses one on a chip; on the CPU, where the guard lets
    host-backed arrays through, turning any jax array into a host value
    raises."""
    import jax
    from jax._src import array as jarray

    from repro.quant.engine import QuantMegastepEngine
    from repro.core.megastep import MegastepEngine

    idx, cfg = _index()
    if engine == "fp32":
        me = MegastepEngine(idx, cfg)
    else:
        me = QuantMegastepEngine(idx, cfg, slack=8,
                                 resident=engine == "int8_resident")
    q = _data(16, seed=14)
    me.finalize(me.dispatch(q, stats=JoinStats()))   # payload + compile

    def refuse(*a, **kw):
        raise AssertionError("device-to-host fetch inside dispatch")

    def host_only(fn):
        def wrapped(x, *a, **kw):
            if isinstance(x, jax.Array):
                refuse()
            return fn(x, *a, **kw)
        return wrapped

    with obs.capture() as tr:
        with monkeypatch.context() as m:
            for name in ("asarray", "array", "ascontiguousarray"):
                m.setattr(np, name, host_only(getattr(np, name)))
            m.setattr(jarray.ArrayImpl, "__array__", refuse)
            m.setattr(jarray.ArrayImpl, "_value", property(refuse))
            with jax.transfer_guard_device_to_host("disallow"):
                js = JoinStats()
                handle = me.dispatch(q, stats=js)
    assert js.n_tombstones == 0 and js.n_r == 16
    assert "megastep.enqueue" in [s.name for s in tr.spans()]
    d, i = me.finalize(handle)
    ref_d, ref_i = me.join_batch(q)
    np.testing.assert_array_equal(d, ref_d)
    np.testing.assert_array_equal(i, ref_i)
