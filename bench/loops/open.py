"""Open loop: requests arrive on a schedule fixed in advance, whether or
not the server keeps up, and go through ``ServeScheduler.submit`` to a
scheduler that serves in its own thread (``serve_forever``).

The schedule has ``round(rate_per_s * seconds)`` requests. Their gaps
and sizes are one multiset per mix, and the query rows one fixed list,
drawn from the mix's ``master_seed``; the run's seed only orders the
gaps and sizes, so every seed offers the same work. ``arrivals`` is ``poisson`` (exponential gaps) or
``bursty`` (bursts of ``burst`` back-to-back requests at exponential
epochs of rate ``rate_per_s / burst``). Each request has between
``rows_min`` and ``rows_max`` query rows, log-uniform.

Latency runs from a request's due time to the moment its results are
stored on its ticket. A request that is shed, rejected or failed, or
that has no answer a minute after the window closes, is counted in
``failed`` and ranks as slower than every completed request.
"""
from __future__ import annotations

import dataclasses
import time

import numpy as np

from bench.loops import Record, percentile

WAIT_AFTER_S = 60.0
WARM_ROWS = (16, 32, 64, 128, 256)


@dataclasses.dataclass
class Plan:
    offsets: np.ndarray       # due time of each request, s after start
    sizes: np.ndarray         # rows per request
    rows: np.ndarray          # all query rows, request after request
    warm_rows: np.ndarray


def schedule(mix: dict, seconds: float, order_seed):
    """(offsets, sizes) of the requests of one run."""
    rate = float(mix["rate_per_s"])
    n = max(1, int(round(rate * seconds)))
    master = np.random.default_rng(int(mix["master_seed"]))
    order = np.random.default_rng(order_seed)
    if mix["arrivals"] == "poisson":
        gaps = order.permutation(master.exponential(1.0, n))
        t = np.cumsum(gaps)
    elif mix["arrivals"] == "bursty":
        burst = int(mix["burst"])
        n_ep = max(1, n // burst)
        gaps = order.permutation(master.exponential(1.0, n_ep))
        t = np.repeat(np.cumsum(gaps), burst)
        n = t.size
    else:
        raise ValueError(f"unknown arrivals {mix['arrivals']!r}")
    # scaled so the last request is due just before the window closes
    offsets = t * (seconds / (t[-1] + t[-1] / t.size))
    lo, hi = int(mix["rows_min"]), int(mix["rows_max"])
    u = master.uniform(np.log(lo), np.log(hi + 1), n)
    sizes = order.permutation(np.clip(np.floor(np.exp(u)), lo, hi)
                              .astype(np.int64))
    return offsets, sizes


def plan(ctx) -> Plan:
    offsets, sizes = schedule(ctx.mix, ctx.seconds, ctx.subseed("order"))
    n_warm = sum(WARM_ROWS)
    q = ctx.datagen.queries(ctx.cfg["data"], int(sizes.sum()) + n_warm,
                            int(ctx.mix["master_seed"]))
    return Plan(offsets=offsets, sizes=sizes, rows=q[n_warm:],
                warm_rows=q[:n_warm])


def warm(system, p: Plan) -> None:
    """One request of each size that maps to its own padded bucket,
    through a scheduler of its own (the window's starts with clean
    counters)."""
    sched = system.scheduler()
    lo = 0
    for n in WARM_ROWS:
        t = sched.join_now(p.warm_rows[lo:lo + n], deadline_s=600.0)
        lo += n
        if t.status != "done":
            raise RuntimeError(f"warm-up request of {n} rows: {t.status} "
                               f"{t.reason}")


def measure(system, p: Plan, seconds: float, span) -> Record:
    sched = system.scheduler()
    starts = np.concatenate([[0], np.cumsum(p.sizes)[:-1]])
    tickets, late = [], []
    sched.serve_forever()
    try:
        with span("bench.window"):
            t0 = time.monotonic()
            for off, lo, n in zip(p.offsets, starts, p.sizes):
                due = t0 + off
                wait = due - time.monotonic()
                if wait > 0:
                    time.sleep(wait)
                late.append(time.monotonic() - due)
                tickets.append((due, sched.submit(p.rows[lo:lo + n],
                                                  arrival=due)))
            give_up = t0 + seconds + WAIT_AFTER_S
            pending = [t for _, t in tickets]
            while pending and time.monotonic() < give_up:
                time.sleep(0.005)
                pending = [t for t in pending if t.status == "queued"]
    finally:
        sched.shutdown(drain=False)
    snap = sched.snapshot()
    done = [(due, t) for due, t in tickets if t.status == "done"]
    unanswered = sum(t.status == "queued" for _, t in tickets)
    lat = [t.completed_at - due for due, t in done]
    worst = max([give_up - due for due, _ in tickets] + lat)
    all_lat = lat + [worst] * (len(tickets) - len(done))
    if done:
        order = [i for i, (_, t) in enumerate(tickets) if t.status == "done"]
        big = max(order, key=lambda i: tickets[i][1].n)
        done_starts = np.concatenate(
            [[0], np.cumsum([t.n for _, t in done])[:-1]])
        pos = order.index(big)
        must = np.arange(done_starts[pos], done_starts[pos] + done[pos][1].n)
        queries = np.concatenate([t.rows for _, t in done])
        dists = np.concatenate([t.distances for _, t in done])
        ids = np.concatenate([t.indices for _, t in done])
    else:
        must = np.zeros((0,), np.int64)
        queries = p.rows[:0]
        dists = np.zeros((0, 1), np.float32)
        ids = np.zeros((0, 1), np.int64)
    return Record(
        attempted=len(tickets), failed=len(tickets) - len(done),
        unanswered=int(unanswered), queries=queries, dists=dists, ids=ids,
        must=must,
        values={"p50_ms": 1e3 * percentile(all_lat, 50),
                "p99_ms": 1e3 * percentile(all_lat, 99)},
        tickets=[{"due": due, "dispatched_at": t.dispatched_at,
                  "completed_at": t.completed_at, "rows": t.n,
                  "status": t.status} for due, t in tickets],
        sched=dataclasses.asdict(snap),
        side={"generator_late_ms": {
            "p50": 1e3 * percentile(late, 50), "max": 1e3 * max(late)},
            "requests": len(tickets)})
