"""Traffic is made from the seed alone, every seed offers the same work
over the same data, and every end-to-end number is taken over the whole
window and every request."""
import time

import numpy as np
import pytest

from bench import harness
from bench.loops import closed, open as open_loop, percentile

ONLINE = {"loop": "open", "arrivals": "poisson", "rate_per_s": 50.0,
          "rows_min": 1, "rows_max": 64, "master_seed": 7}


@pytest.mark.parametrize("arrivals", ["poisson", "bursty"])
def test_schedule_same_seed_same_traffic(arrivals):
    mix = dict(ONLINE, arrivals=arrivals, burst=8)
    a = open_loop.schedule(mix, 10.0, 2**31 + 5)
    b = open_loop.schedule(mix, 10.0, 2**31 + 5)
    c = open_loop.schedule(mix, 10.0, 6)
    assert all(np.array_equal(x, y) for x, y in zip(a, b))
    assert not np.array_equal(a[0], c[0])
    # another seed orders the same work: the same sizes, the same count
    assert np.array_equal(np.sort(a[1]), np.sort(c[1]))
    assert a[0].size == c[0].size


def test_schedule_shape():
    off, sizes = open_loop.schedule(ONLINE, 10.0, 1)
    assert off.size == 500 and np.all(np.diff(off) >= 0)
    assert 0 < off[0] and off[-1] < 10.0
    assert sizes.min() >= 1 and sizes.max() <= 64
    off, _ = open_loop.schedule(dict(ONLINE, arrivals="bursty", burst=8),
                                10.0, 1)
    assert np.all(off.reshape(-1, 8) == off.reshape(-1, 8)[:, :1])


def _ctx(mix, seed, data_seed=None):
    cfg = harness.load_json("configs", "sift1m-k10")
    cfg["data"]["n"] = 512
    if data_seed is not None:
        cfg["data"]["seed"] = data_seed
    return harness.make_context("t", seed, 2.0, cfg, mix)


CLOSED = ({"loop": "closed", "queries": "fresh", "batch_rows": 64,
           "cycle_batches": 4, "master_seed": 3},
          {"loop": "closed", "queries": "self", "order": "random",
           "batch_rows": 64, "cycle_batches": 4, "master_seed": 3},
          {"loop": "closed", "queries": "self", "order": "cells",
           "n_cells": 8, "batch_rows": 64, "cycle_batches": 4,
           "master_seed": 3})


def test_plans_deterministic_per_seed():
    mix = dict(ONLINE)
    loop = harness.load_module("loops", "open")
    a = loop.plan(_ctx(mix, 2**31 + 11))
    b = loop.plan(_ctx(mix, 2**31 + 11))
    assert all(np.array_equal(getattr(a, f), getattr(b, f))
               for f in ("offsets", "sizes", "rows", "warm_rows"))
    # another seed orders the same requests otherwise
    assert any(not np.array_equal(a.sizes, loop.plan(_ctx(mix, s)).sizes)
               for s in (12, 13, 14))


@pytest.mark.parametrize("mix", CLOSED, ids=["fresh", "random", "cells"])
def test_closed_every_seed_sends_the_same_batches(mix):
    a = closed.plan(_ctx(mix, 2**31 + 11))
    c = closed.plan(_ctx(mix, 12))
    assert np.array_equal(a.rows, c.rows)
    assert np.array_equal(a.warm_rows, c.warm_rows)
    assert a.rows.shape == (mix["cycle_batches"] * mix["batch_rows"], 128)
    # the warm-up batch is not one of the cycle's
    rows = {bytes(r) for r in a.rows}
    assert not any(bytes(r) in rows for r in a.warm_rows)


def test_spread_order_prefixes_cover_the_cycle():
    o = closed.spread_order(16)
    assert sorted(o) == list(range(16))
    assert sorted(o[:8]) == list(range(0, 16, 2))
    assert sorted(o[:4]) == [0, 4, 8, 12]
    assert sorted(closed.spread_order(5)) == list(range(5))


def test_cell_order_groups_nearby_rows():
    rng = np.random.default_rng(0)
    centers = rng.uniform(-50, 50, (8, 3))
    data = (centers[rng.integers(0, 8, 4000)]
            + rng.normal(size=(4000, 3))).astype(np.float32)
    seq = closed.by_cell(data, 8, np.random.default_rng(1))
    assert np.array_equal(np.sort(seq), np.arange(4000))

    def spread(order):      # a batch's spread, median over batches
        x = data[order].reshape(-1, 100, 3)
        return float(np.median(x.std(axis=1).mean(axis=1)))
    assert spread(seq) < 0.25 * spread(rng.permutation(4000))


def test_data_fixed_by_the_configuration_not_the_run():
    ctx = _ctx(ONLINE, 9)
    other_run = _ctx(ONLINE, 10)
    other_data = _ctx(ONLINE, 9, data_seed=77)
    assert np.array_equal(ctx.data, other_run.data)
    assert not np.array_equal(ctx.data, other_data.data)
    fx = harness.load_module("datagen", "forest_x10")
    p = {"n_base": 300, "dim": 10, "n_clusters": 32, "factor": 10}
    assert np.array_equal(fx.generate(p, 4), fx.generate(p, 4))
    assert fx.generate(p, 4).shape == (3000, 10)


def test_expansion_moves_each_value_along_the_frequency_order():
    fx = harness.load_module("datagen", "forest_x10")
    col = np.array([5, 5, 5, 1, 1, 9, 7, 7, 7, 7], np.float32)
    base = np.stack([col, col[::-1]], axis=1)
    out = fx.expand_dataset(base, 3)
    assert out.shape == (30, 2)
    # distinct values by ascending count (ties by value): 9, 1, 5, 7
    step = {9.0: 1.0, 1.0: 5.0, 5.0: 7.0, 7.0: 9.0}
    for t in (1, 2):
        want = base.copy()
        for _ in range(t):
            want = np.vectorize(step.get)(want)
        assert np.array_equal(out[10 * t:10 * (t + 1)], want)


def test_percentile_nearest_rank_over_all_values():
    v = list(range(1, 101))
    assert percentile(v, 50) == 50
    assert percentile(v, 99) == 99
    assert percentile(v, 100) == 100
    assert percentile([5.0], 99) == 5.0


class _Engine:
    """Answers every row with (row index, 0); each call takes ``cost``
    seconds."""

    def __init__(self, cost):
        self.cost = cost
        self.megastep_engine = None

    def join_batch(self, q, stats=None):
        time.sleep(self.cost)
        n = q.shape[0]
        return (np.zeros((n, 2), np.float32),
                np.tile(np.arange(2, dtype=np.int64), (n, 1)))


def test_closed_rows_per_s_counts_every_batch_to_the_last_completion():
    from bench.builders import System
    sys_ = System(engine=_Engine(0.05))
    p = closed.Plan(batch_rows=8, rows=np.zeros((80, 4), np.float32),
                    warm_rows=np.zeros((8, 4), np.float32))
    rec = closed.measure(sys_, p, 0.3, harness.span_factory(False))
    rows = sum(b["rows"] for b in rec.batches)
    assert rec.batches[-1]["end_s"] >= 0.3      # the last batch finished
    assert rec.values["rows_per_s"] == pytest.approx(
        rows / rec.batches[-1]["end_s"])
    assert rec.attempted == rows == rec.queries.shape[0]


def test_open_latency_from_due_time_over_every_request():
    from repro.serve import ServeScheduler
    from bench.builders import System
    eng = _Engine(0.02)
    sys_ = System(engine=eng, scheduler=lambda: ServeScheduler(eng))
    mix = dict(ONLINE, rate_per_s=40.0)
    off, sizes = open_loop.schedule(mix, 1.0, 3)
    p = open_loop.Plan(offsets=off, sizes=sizes,
                       rows=np.zeros((int(sizes.sum()), 4), np.float32),
                       warm_rows=np.zeros((496, 4), np.float32))
    rec = open_loop.measure(sys_, p, 1.0, harness.span_factory(False))
    assert rec.attempted == off.size == len(rec.tickets)
    lat = [t["completed_at"] - t["due"] for t in rec.tickets]
    assert min(lat) >= 0.02 - 1e-3
    assert rec.values["p50_ms"] == pytest.approx(1e3 * percentile(lat, 50))
    assert rec.values["p99_ms"] == pytest.approx(1e3 * percentile(lat, 99))
    assert rec.failed == 0 and rec.unanswered == 0


def test_open_failed_request_ranks_slowest():
    from repro.serve import ServeScheduler, SchedulerConfig
    from bench.builders import System
    eng = _Engine(0.3)
    cfg = SchedulerConfig(default_deadline_s=0.1)
    sys_ = System(engine=eng,
                  scheduler=lambda: ServeScheduler(eng, config=cfg))
    off = np.array([0.0, 0.01, 0.02, 0.03])
    sizes = np.array([1, 1, 1, 1])
    p = open_loop.Plan(offsets=off, sizes=sizes,
                       rows=np.zeros((4, 4), np.float32),
                       warm_rows=np.zeros((496, 4), np.float32))
    rec = open_loop.measure(sys_, p, 0.05, harness.span_factory(False))
    done = [t for t in rec.tickets if t["status"] == "done"]
    assert rec.failed == 4 - len(done) >= 1
    worst = max(t["completed_at"] - t["due"] for t in done)
    assert rec.values["p99_ms"] > 1e3 * worst
