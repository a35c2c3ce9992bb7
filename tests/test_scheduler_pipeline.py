"""Double-buffered dispatch (SchedulerConfig.max_inflight > 1): the
scheduler overlaps batch N's device pass with batch N+1's formation via
the engine's async dispatch/finalize split — results stay bitwise the
sync path's, deadlines are re-checked at the dispatch instant, and
dispatch/finalize faults fall back onto the host-planned retry ladder
(serve.scheduler + core.stream dispatch/finalize)."""
import numpy as np
import pytest

from repro.core import JoinConfig, StreamJoinEngine, build_index, knn_join
from repro.serve import (
    FaultPlan, SchedulerConfig, ServeScheduler, VirtualClock)

DIM = 12


def _data(n=600, seed=0):
    return np.random.default_rng(seed).normal(size=(n, DIM)).astype(
        np.float32)


def _engine(n=600, *, quantized=False, k=4, seed=0):
    s = _data(n, seed)
    cfg = JoinConfig(k=k, n_pivots=32, n_groups=4,
                     quantize="int8" if quantized else "none")
    return StreamJoinEngine(build_index(s, cfg), cfg,
                            megastep="auto", quantized=quantized), s, cfg


@pytest.mark.parametrize("quantized", [False, True])
def test_pipelined_bitwise_matches_sync(quantized):
    """Same submissions through max_inflight=1 and max_inflight=2:
    every ticket's results are identical bit for bit — pipelining is a
    scheduling change, never a numerics change."""
    eng, s, cfg = _engine(quantized=quantized)
    qs = [_data(n, seed=70 + n) for n in (9, 4, 13, 7, 11)]
    outs = []
    for mi in (1, 2):
        sched = ServeScheduler(
            eng, config=SchedulerConfig(batch_rows=16, max_inflight=mi))
        # deadlines past the first batch's compile: this test is about
        # bits, and a cold jit cache must not shed the later requests
        tickets = [sched.submit(q, deadline_s=60.0) for q in qs]
        sched.drain()
        assert all(t.done and not t.degraded for t in tickets)
        outs.append(tickets)
    for q, t_sync, t_pipe in zip(qs, *outs):
        np.testing.assert_array_equal(t_pipe.distances, t_sync.distances)
        np.testing.assert_array_equal(t_pipe.indices, t_sync.indices)
        ref = knn_join(q, s, k=cfg.k, config=cfg)
        np.testing.assert_array_equal(t_pipe.distances, ref.distances)
        np.testing.assert_array_equal(t_pipe.indices, ref.indices)


def test_pipelined_coalesces_and_splits_back():
    eng, s, cfg = _engine()
    sched = ServeScheduler(
        eng, config=SchedulerConfig(batch_rows=64, max_inflight=2))
    qs = [_data(n, seed=80 + n) for n in (3, 17, 8)]
    tickets = [sched.submit(q) for q in qs]
    sched.drain()
    assert sched.stats.n_dispatches == 1       # one coalesced dispatch
    for q, t in zip(qs, tickets):
        assert t.done
        ref = knn_join(q, s, k=cfg.k, config=cfg)
        np.testing.assert_array_equal(t.distances, ref.distances)
        np.testing.assert_array_equal(t.indices, ref.indices)


def test_pipelined_window_overlaps_then_drains():
    """While work keeps arriving, one megastep stays in flight across
    steps (the overlap); an empty queue drains the window."""
    eng, _, _ = _engine()
    sched = ServeScheduler(
        eng, config=SchedulerConfig(batch_rows=8, max_inflight=2))
    tickets = [sched.submit(_data(8, seed=90 + i)) for i in range(3)]
    assert sched.step() == 8                   # dispatch #1, nothing done
    assert sched.inflight_batches == 1
    assert tickets[0].status == "queued" and sched.has_work
    sched.step()                               # dispatch #2, finalize #1
    assert tickets[0].done and tickets[1].status == "queued"
    assert sched.inflight_batches == 1
    sched.step()                               # dispatch #3, finalize #2
    assert tickets[1].done
    assert sched.step() == 8                   # queue empty: drain window
    assert tickets[2].done and sched.inflight_batches == 0
    assert not sched.has_work and sched.step() == 0
    assert all(t.attempts == 1 for t in tickets)
    assert sched.stats.n_retries == 0


def test_pipelined_join_now_resolves():
    eng, s, cfg = _engine()
    sched = ServeScheduler(eng, config=SchedulerConfig(max_inflight=3))
    q = _data(6, seed=100)
    t = sched.join_now(q)
    assert t.done and sched.inflight_batches == 0
    ref = knn_join(q, s, k=cfg.k, config=cfg)
    np.testing.assert_array_equal(t.distances, ref.distances)


def test_pipelined_dispatch_fault_falls_back_to_host_ladder():
    """A fault at the async dispatch routes that batch onto the
    synchronous retry ladder (host-planned oracle) — bitwise exact,
    counted as a retry, and the pipeline keeps going afterwards."""
    eng, s, cfg = _engine()
    sched = ServeScheduler(
        eng, config=SchedulerConfig(max_inflight=2), sleep=lambda _s: None)
    q = _data(6, seed=110)
    with FaultPlan().fail("sched.dispatch", times=1) as plan:
        t = sched.join_now(q)
    assert t.done and t.attempts == 2
    # fired twice: the raising async dispatch + the retry's pass-through
    assert plan.fired["sched.dispatch"] == 2
    assert sched.stats.n_retries == 1
    ref = knn_join(q, s, k=cfg.k, config=cfg)
    np.testing.assert_array_equal(t.distances, ref.distances)
    np.testing.assert_array_equal(t.indices, ref.indices)
    t2 = sched.join_now(_data(5, seed=111))    # pipeline still healthy
    assert t2.done and t2.attempts == 1


def test_pipelined_finalize_fault_falls_back_to_host_ladder():
    """A fault at fetch time (the finalize half) re-runs the in-flight
    batch's tickets through the retry ladder — no result is lost."""
    eng, s, cfg = _engine()
    sched = ServeScheduler(
        eng, config=SchedulerConfig(max_inflight=2), sleep=lambda _s: None)
    q = _data(6, seed=120)
    with FaultPlan().fail("megastep.fetch", times=1) as plan:
        t = sched.join_now(q)
    assert t.done and t.attempts == 2
    assert plan.fired["megastep.fetch"] == 1
    ref = knn_join(q, s, k=cfg.k, config=cfg)
    np.testing.assert_array_equal(t.distances, ref.distances)
    np.testing.assert_array_equal(t.indices, ref.indices)


def test_pipelined_deadline_rechecked_at_dispatch():
    """Expired requests are shed before the async dispatch exactly as
    on the sync path; a request that expires only *after* dispatch
    still completes — n_expired_dispatched stays 0 either way."""
    eng, _, _ = _engine()
    vc = VirtualClock()
    sched = ServeScheduler(
        eng, config=SchedulerConfig(batch_rows=8, max_inflight=2),
        clock=vc.now, sleep=vc.advance)
    t_dead = sched.submit(_data(4, seed=130), deadline_s=0.5)
    vc.advance(1.0)
    sched.drain()
    assert t_dead.status == "shed" and t_dead.reason == "deadline"
    assert t_dead.dispatched_at is None
    # expired mid-flight: dispatched while live, allowed to finish
    t_late = sched.submit(_data(4, seed=131), deadline_s=0.5)
    sched.step()                               # dispatches, stays in flight
    assert t_late.dispatched_at is not None
    vc.advance(1.0)                            # expires while in flight
    sched.drain()
    assert t_late.done
    assert sched.stats.n_expired_dispatched == 0


def test_pipelined_degraded_rung_stays_synchronous():
    """Above the degrade watermark the certified-approximate rung is a
    blocking engine call — the in-flight window is flushed first and
    degraded responses still carry their recall bounds."""
    eng, _, _ = _engine(quantized=True)
    sched = ServeScheduler(
        eng, config=SchedulerConfig(batch_rows=32, degrade_queued_rows=0,
                                    max_inflight=2))
    tickets = [sched.submit(_data(8, seed=140 + i)) for i in range(3)]
    sched.drain()
    assert sched.inflight_batches == 0
    for t in tickets:
        assert t.done and t.degraded
        rb = t.recall_bound
        assert rb.shape == (8,) and (rb >= 0).all() and (rb <= 1).all()


def test_host_engine_ignores_max_inflight():
    """An engine without the dispatch/finalize split (host-planned
    path) silently stays synchronous — max_inflight > 1 is a no-op."""
    s = _data(300, seed=1)
    cfg = JoinConfig(k=4, n_pivots=32, n_groups=4)
    eng = StreamJoinEngine(build_index(s, cfg), cfg, megastep=False)
    assert not eng.can_dispatch
    sched = ServeScheduler(eng, config=SchedulerConfig(max_inflight=4))
    q = _data(7, seed=150)
    t = sched.join_now(q)
    assert t.done and sched.inflight_batches == 0
    ref = knn_join(q, s, k=cfg.k, config=cfg)
    np.testing.assert_array_equal(t.distances, ref.distances)


def test_max_inflight_validation():
    with pytest.raises(ValueError):
        SchedulerConfig(max_inflight=0)
