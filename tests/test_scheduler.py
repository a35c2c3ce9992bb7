"""Serving scheduler: admission control, deadlines, priority lanes,
coalescing, the degradation ladder, and fault-injected retries
(serve.scheduler + serve.faultinject)."""
import threading

import numpy as np
import pytest

from repro.core import JoinConfig, StreamJoinEngine, build_index, knn_join
from repro.serve import (
    Arrival, FaultPlan, InjectedFault, LoadReport, Priority,
    SchedulerConfig, ServeScheduler, VirtualClock, bursty_times,
    poisson_times, run_open_loop)

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


def test_exact_path_bitwise_oracle():
    """A scheduled request's result is the engine's own output verbatim
    — admission/coalescing must not perturb a single bit."""
    eng, s, cfg = _engine()
    sched = ServeScheduler(eng)
    q = _data(10, seed=1)
    t = sched.join_now(q)
    assert t.done and not t.degraded
    ref = knn_join(q, s, k=cfg.k, config=cfg)
    np.testing.assert_array_equal(t.distances, ref.distances)
    np.testing.assert_array_equal(t.indices, ref.indices)
    np.testing.assert_array_equal(t.recall_bound, np.ones(10, np.float32))


def test_coalescing_splits_back_per_request():
    """Ragged requests coalesce into one dispatch and split back — each
    ticket's rows get exactly their own one-shot results."""
    eng, s, cfg = _engine()
    sched = ServeScheduler(eng, config=SchedulerConfig(batch_rows=64))
    qs = [_data(n, seed=10 + n) for n in (3, 17, 8, 5)]
    tickets = [sched.submit(q) for q in qs]
    assert sched.queued_rows == 33
    n_resolved = sched.step()
    assert n_resolved == 33
    assert sched.stats.n_dispatches == 1         # one coalesced batch
    for q, t in zip(qs, tickets):
        assert t.done
        ref = knn_join(q, s, k=cfg.k, config=cfg)
        np.testing.assert_array_equal(t.distances, ref.distances)
        np.testing.assert_array_equal(t.indices, ref.indices)


def test_batch_rows_caps_coalescing():
    eng, _, _ = _engine()
    sched = ServeScheduler(eng, config=SchedulerConfig(batch_rows=16))
    for _ in range(4):
        sched.submit(_data(10, seed=3))
    sched.drain()
    # 10-row requests against a 16-row cap: never two whole requests in
    # one dispatch, but an oversized request alone would still dispatch
    assert sched.stats.n_dispatches == 4


def test_expired_requests_shed_before_dispatch():
    """The hard invariant: a request whose deadline passed is shed at
    batch formation — the engine never sees it."""
    eng, _, _ = _engine()
    vc = VirtualClock()
    sched = ServeScheduler(eng, clock=vc.now, sleep=vc.advance)
    t_live = sched.submit(_data(4, seed=4), deadline_s=10.0)
    t_dead = sched.submit(_data(4, seed=5), deadline_s=0.5)
    vc.advance(1.0)                    # t_dead expires in the queue
    sched.drain()
    assert t_live.done
    assert t_dead.status == "shed" and t_dead.reason == "deadline"
    assert t_dead.dispatched_at is None
    assert sched.stats.n_shed_deadline == 1
    assert sched.stats.n_expired_dispatched == 0


def test_priority_lanes_interactive_first():
    eng, _, _ = _engine()
    sched = ServeScheduler(eng, config=SchedulerConfig(batch_rows=8))
    t_bulk = sched.submit(_data(8, seed=6), priority=Priority.BULK)
    t_int = sched.submit(_data(8, seed=7), priority=Priority.INTERACTIVE)
    sched.step()
    assert t_int.done and t_bulk.status == "queued"   # bulk waits
    sched.step()
    assert t_bulk.done


def test_admission_bound_rejects_and_interactive_evicts_bulk():
    eng, _, _ = _engine()
    cfg = SchedulerConfig(batch_rows=8, max_queued_rows=16,
                          degrade_queued_rows=16, shed_queued_rows=16)
    sched = ServeScheduler(eng, config=cfg)
    t1 = sched.submit(_data(10, seed=8), priority=Priority.BULK)
    # bulk over the cap: explicit rejection, not an unbounded queue
    t2 = sched.submit(_data(10, seed=9), priority=Priority.BULK)
    assert t2.status == "rejected" and t2.reason == "queue_full"
    # interactive over the cap: evicts queued bulk to get in
    t3 = sched.submit(_data(12, seed=10), priority=Priority.INTERACTIVE)
    assert t3.status == "queued"
    assert t1.status == "shed" and t1.reason == "overload"
    sched.drain()
    assert t3.done
    assert sched.stats.n_rejected == 1 and sched.stats.n_shed_overload == 1
    assert sched.queued_rows == 0


def test_overload_sheds_bulk_at_watermark():
    eng, _, _ = _engine()
    cfg = SchedulerConfig(batch_rows=8, max_queued_rows=64,
                          degrade_queued_rows=8, shed_queued_rows=24)
    sched = ServeScheduler(eng, config=cfg)
    bulk = [sched.submit(_data(8, seed=20 + i), priority=Priority.BULK)
            for i in range(4)]
    t_int = sched.submit(_data(8, seed=30))
    sched.drain()
    assert t_int.done
    # backlog was 40 > 24: newest bulk shed down to the watermark
    assert [b.status for b in bulk] == ["done", "done", "shed", "shed"]
    assert all(b.reason == "overload" for b in bulk if b.status == "shed")


def test_degraded_mode_certified_recall_bounds():
    """Above the degrade watermark a quantized engine serves coarse-only:
    responses are flagged degraded and carry a *valid* certified recall
    bound — checked against the true top-k, not just well-formedness."""
    eng, s, cfg = _engine(quantized=True)
    sched = ServeScheduler(
        eng, config=SchedulerConfig(batch_rows=32, degrade_queued_rows=0))
    assert sched.degraded_engine is not None
    qs = [_data(8, seed=40 + i) for i in range(3)]
    tickets = [sched.submit(q) for q in qs]
    sched.drain()
    for q, t in zip(qs, tickets):
        assert t.done and t.degraded
        rb = t.recall_bound
        assert rb.shape == (8,) and (rb >= 0).all() and (rb <= 1).all()
        ref = knn_join(q, s, k=cfg.k, config=cfg)
        # the bound is a guarantee: true recall >= reported bound
        for i in range(q.shape[0]):
            true_set = set(ref.indices[i].tolist())
            got = [x for x in t.indices[i].tolist() if x >= 0]
            recall = len(true_set & set(got)) / cfg.k
            assert recall >= float(rb[i]) - 1e-6
        # degraded distances are still exact per reported neighbor
        np.testing.assert_allclose(
            t.distances, np.asarray(
                [[np.linalg.norm(q[i] - s[j]) if j >= 0 else np.inf
                  for j in t.indices[i]] for i in range(q.shape[0])]),
            rtol=1e-5, atol=1e-5)
    assert sched.stats.n_degraded_requests == 3
    assert sched.stats.join.n_degraded == 24
    assert sched.stats.join.recall_bound <= 1.0


def test_no_degraded_engine_serves_exact_under_pressure():
    eng, s, cfg = _engine()                    # fp32: no coarse tier
    sched = ServeScheduler(
        eng, config=SchedulerConfig(batch_rows=32, degrade_queued_rows=0))
    assert sched.degraded_engine is None
    t = sched.join_now(_data(5, seed=50))
    assert t.done and not t.degraded


def test_transient_fault_retried_onto_host_path():
    """An injected dispatch fault is retried with backoff onto the
    host-planned oracle — the result is still bitwise exact and the
    backoff slept through the injected sleep fn."""
    eng, s, cfg = _engine()
    slept = []
    sched = ServeScheduler(
        eng, config=SchedulerConfig(backoff_base_s=0.01, backoff_cap_s=0.04,
                                    max_retries=3),
        sleep=slept.append)
    q = _data(6, seed=60)
    with FaultPlan().fail("sched.dispatch", times=2) as plan:
        t = sched.join_now(q)
    assert t.done and t.attempts == 3
    assert plan.fired["sched.dispatch"] == 3
    assert sched.stats.n_retries == 2
    assert slept == [0.01, 0.02]              # capped exponential backoff
    ref = knn_join(q, s, k=cfg.k, config=cfg)
    np.testing.assert_array_equal(t.distances, ref.distances)
    np.testing.assert_array_equal(t.indices, ref.indices)


def test_payload_upload_fault_recovered():
    """A device-OOM-on-upload fault (megastep payload rebuild) recovers
    via the host-planned retry path — bitwise again."""
    eng, s, cfg = _engine()
    eng.megastep_engine._payload = None       # force a rebuild
    sched = ServeScheduler(eng, sleep=lambda _s: None)
    q = _data(6, seed=61)
    with FaultPlan().fail("megastep.payload_upload", times=1) as plan:
        t = sched.join_now(q)
    assert t.done and plan.fired["megastep.payload_upload"] == 1
    ref = knn_join(q, s, k=cfg.k, config=cfg)
    np.testing.assert_array_equal(t.distances, ref.distances)
    np.testing.assert_array_equal(t.indices, ref.indices)


def test_fetch_fault_recovered():
    eng, s, cfg = _engine()
    sched = ServeScheduler(eng, sleep=lambda _s: None)
    q = _data(6, seed=62)
    with FaultPlan().fail("megastep.fetch", times=1):
        t = sched.join_now(q)
    assert t.done and t.attempts == 2
    ref = knn_join(q, s, k=cfg.k, config=cfg)
    np.testing.assert_array_equal(t.distances, ref.distances)


def test_permanent_fault_marks_failed_not_hung():
    eng, _, _ = _engine()
    sched = ServeScheduler(
        eng, config=SchedulerConfig(max_retries=2), sleep=lambda _s: None)
    t = sched.submit(_data(4, seed=63))
    boom = RuntimeError("wedged device")
    with FaultPlan().fail("sched.dispatch", times=99, exc=boom):
        sched.drain()
    assert t.status == "failed" and "wedged device" in t.reason
    assert sched.stats.n_failed == 1 and sched.queued_rows == 0


@pytest.mark.parametrize("max_inflight", [1, 2])
def test_device_step_error_propagates_not_served_by_oracle(
        monkeypatch, max_inflight):
    """A device step that fails to lower is a program error, not a
    transient: it fails its tickets and propagates out of the scheduler
    instead of being answered by the host-planned oracle."""
    from repro.core import megastep

    def refuses(*_a, **_kw):
        raise NotImplementedError("Unimplemented primitive in Pallas TPU "
                                  "lowering: rev")

    eng, _, _ = _engine()
    host_calls = []
    sched = ServeScheduler(
        eng, config=SchedulerConfig(max_inflight=max_inflight),
        host_join=lambda q, **kw: host_calls.append(q),
        sleep=lambda _s: None)
    monkeypatch.setattr(megastep, "_megastep", refuses)
    t = sched.submit(_data(4, seed=66))
    with pytest.raises(NotImplementedError, match="Pallas TPU lowering"):
        sched.drain()
    assert t.status == "failed" and "lowering" in t.reason
    assert not host_calls
    assert sched.stats.n_retries == 0 and sched.stats.n_failed == 1


def test_deadline_enforced_across_backoff():
    """A request that expires while the batch backs off between retries
    is shed, never re-dispatched — n_expired_dispatched stays 0."""
    eng, _, _ = _engine()
    vc = VirtualClock()
    sched = ServeScheduler(
        eng, config=SchedulerConfig(backoff_base_s=1.0, backoff_cap_s=1.0),
        clock=vc.now, sleep=vc.advance)     # backoff advances the clock
    t = sched.submit(_data(4, seed=64), deadline_s=0.5)
    with FaultPlan().fail("sched.dispatch", times=1):
        sched.drain()
    assert t.status == "shed" and t.reason == "deadline"
    assert t.attempts == 1                  # dispatched once, pre-fault
    assert sched.stats.n_expired_dispatched == 0


def test_submit_thread_safe_under_concurrent_consumer():
    eng, _, _ = _engine()
    sched = ServeScheduler(eng, config=SchedulerConfig(batch_rows=64))
    tickets, lock = [], threading.Lock()

    def producer(seed):
        for i in range(5):
            t = sched.submit(_data(7, seed=seed * 100 + i))
            with lock:
                tickets.append(t)

    sched.serve_forever()
    try:
        threads = [threading.Thread(target=producer, args=(s,))
                   for s in range(4)]
        for th in threads:
            th.start()
        for th in threads:
            th.join()
        deadline = 50.0
        import time as _time
        t0 = _time.monotonic()
        while sched.has_work and _time.monotonic() - t0 < deadline:
            _time.sleep(0.01)
    finally:
        sched.shutdown()
    assert len(tickets) == 20 and all(t.done for t in tickets)
    assert sched.stats.rows_completed == 140


def test_open_loop_overload_smoke():
    """2× overload through the virtual clock: goodput nonzero, nothing
    expired was ever dispatched, every degraded response carries a
    bound, and the report's accounting adds up."""
    eng, _, _ = _engine(n=400, quantized=True)
    vc = VirtualClock()
    sched = ServeScheduler(
        eng,
        config=SchedulerConfig(batch_rows=32, degrade_queued_rows=64,
                               shed_queued_rows=96, max_queued_rows=128,
                               default_deadline_s=0.05),
        clock=vc.now, sleep=vc.advance)
    rng = np.random.default_rng(5)
    # service cost model: each step advances the virtual clock by a
    # fixed per-batch cost via the measure hook (deterministic — no
    # wall-clock flakiness in CI)
    fake = iter(np.arange(1, 100000) * 0.004)
    times = bursty_times(2000.0, 0.5, rng, burst=4)   # 2× of 32/0.004/2
    arrivals = [Arrival(t=float(t), rows=_data(8, seed=200 + j),
                        priority=(Priority.BULK if j % 3 == 0
                                  else Priority.INTERACTIVE))
                for j, t in enumerate(times)]
    tickets = run_open_loop(sched, arrivals, vc,
                            measure=lambda: next(fake))
    rep = LoadReport.from_tickets(tickets, sched.stats)
    assert rep.n_requests == len(arrivals)
    assert (rep.n_completed + rep.n_shed + rep.n_rejected + rep.n_failed
            == rep.n_requests)
    assert rep.n_completed > 0 and rep.goodput_rows_s > 0
    assert rep.n_shed + rep.n_rejected > 0          # overload engaged
    assert rep.n_expired_dispatched == 0            # the hard invariant
    assert np.isfinite(rep.p50_s) and rep.p50_s <= rep.p99_s <= rep.p999_s
    for t in tickets:
        if t.done and t.degraded:
            assert 0.0 <= float(t.recall_bound.min()) <= 1.0
    assert 0.0 <= rep.recall_bound_min <= 1.0


def test_arrival_generators():
    rng = np.random.default_rng(0)
    p = poisson_times(100.0, 2.0, rng)
    assert p.size > 0 and (np.diff(p) >= 0).all() and p[-1] < 2.0
    # mean rate within 3 sigma of nominal
    assert abs(p.size - 200) < 3 * np.sqrt(200)
    b = bursty_times(100.0, 2.0, rng, burst=8)
    assert b.size % 8 == 0 and (np.diff(b) >= 0).all()
    assert poisson_times(0.0, 2.0, rng).size == 0


def test_scheduler_config_validation():
    with pytest.raises(ValueError):
        SchedulerConfig(batch_rows=0)
    with pytest.raises(ValueError):
        SchedulerConfig(degrade_queued_rows=100, shed_queued_rows=50)
    with pytest.raises(ValueError):
        SchedulerConfig(shed_queued_rows=5000, max_queued_rows=4096)
    eng, _, _ = _engine(n=100)
    sched = ServeScheduler(eng)
    with pytest.raises(ValueError):
        sched.submit(np.zeros((0, DIM), np.float32))


def test_fault_plan_arming():
    plan = FaultPlan().fail("x", times=1)
    with pytest.raises(InjectedFault):
        with plan:
            from repro.serve import faultinject
            faultinject.fire("x")
    # outside the with block sites are dead
    from repro.serve import faultinject
    faultinject.fire("x")
    with FaultPlan():
        with pytest.raises(RuntimeError):
            with FaultPlan():                  # double-arm rejected
                pass


def test_knn_logits_through_scheduler():
    """The kNN-LM path accepts a scheduler: same logits as the direct
    path when unloaded; a rejected batch degrades to the log floor."""
    from repro.serve import Datastore, KnnLMConfig, knn_logits

    rng = np.random.default_rng(9)
    keys = rng.normal(size=(400, DIM)).astype(np.float32)
    vals = rng.integers(0, 32, 400).astype(np.int32)
    store = Datastore.build(keys, vals, k=4, n_pivots=32, n_groups=4)
    kcfg = KnnLMConfig(k=4)
    q = rng.normal(size=(5, DIM)).astype(np.float32)
    direct = knn_logits(q, store, kcfg, vocab=32)
    sched = ServeScheduler.for_datastore(store)
    via = knn_logits(q, store, kcfg, vocab=32, scheduler=sched)
    np.testing.assert_array_equal(direct, via)
    # a scheduler that rejects everything -> LM-only fallback rows
    full = ServeScheduler.for_datastore(
        store, config=SchedulerConfig(max_queued_rows=2,
                                      degrade_queued_rows=1,
                                      shed_queued_rows=2))
    lg = knn_logits(q, store, kcfg, vocab=32, scheduler=full)
    np.testing.assert_allclose(lg, np.log(1e-9))
