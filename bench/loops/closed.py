"""Closed loop: one client sends a batch of ``batch_rows`` query rows,
waits for the answers, and sends the next, back to back.

The window sends ``cycle_batches`` batches, fixed by the mix's
``master_seed`` and the configuration's data, in a fixed order, and
starts over when it has sent them all: every run does the same work, and
a window that ends inside a cycle ends at the same batch in every run of
one program (the run's seed draws the check's sample). The cycle's
batches go in ``spread_order``, so that part of a cycle is a sample of
the whole. ``queries`` is

* ``self``: rows of S (a self-join). With ``order`` ``random`` S is
  shuffled; with ``cells`` its rows are grouped by the nearest of
  ``n_cells`` reference rows drawn from S, as the paper's join groups R
  by Voronoi cell before it joins. S, so ordered, is cut into batches,
  and the cycle takes ``cycle_batches`` of them spread evenly over it.
* ``fresh``: rows drawn from the configuration's data generator.

``rows_per_s`` is every row answered over the time from the window's
start to the completion of the last batch; the batch in flight when the
window's time is up is finished and counted.
"""
from __future__ import annotations

import dataclasses
import time

import numpy as np

from bench.loops import Record

CHUNK = 65536


@dataclasses.dataclass
class Plan:
    batch_rows: int
    rows: np.ndarray          # the query rows, in the order they are sent
    warm_rows: np.ndarray


def by_cell(data: np.ndarray, n_cells: int, rng) -> np.ndarray:
    """Row indices of ``data`` grouped by the nearest of ``n_cells`` of
    its rows drawn by ``rng`` (squared L2 in float32: an order, not an
    answer), rows of one cell in an order drawn by ``rng``."""
    n = data.shape[0]
    ref = data[rng.choice(n, n_cells, replace=False)].astype(np.float32)
    # [x, 1] . [-2 ref, |ref|^2] = |x - ref|^2 - |x|^2, in one product
    w = np.concatenate([-2.0 * ref, (ref * ref).sum(1, keepdims=True)],
                       axis=1).T
    home = np.empty(n, np.int64)
    for lo in range(0, n, CHUNK):
        x = data[lo:lo + CHUNK].astype(np.float32)
        x = np.concatenate([x, np.ones((x.shape[0], 1), np.float32)], 1)
        home[lo:lo + CHUNK] = np.argmin(x @ w, axis=1)
    shuffled = rng.permutation(n)
    return shuffled[np.argsort(home[shuffled], kind="stable")]


def spread_order(m: int) -> np.ndarray:
    """0..m-1 by their bit-reversed binary fraction (0, 8, 4, 12, 2, ...
    for 16): every prefix of the order is spread evenly over the range,
    so the batches a window sends before it closes inside a cycle are
    spread over S whatever the program's speed."""
    def radical_inverse(i):
        f, w = 0.0, 0.5
        while i:
            f += w * (i & 1)
            i >>= 1
            w /= 2
        return f
    return np.array(sorted(range(m), key=radical_inverse), np.int64)


def plan(ctx) -> Plan:
    mix, b = ctx.mix, int(ctx.mix["batch_rows"])
    n_cycle = int(mix["cycle_batches"])
    fixed = np.random.default_rng(int(mix["master_seed"]))
    if mix["queries"] == "self":
        if mix["order"] == "random":
            seq = fixed.permutation(ctx.data.shape[0])
        elif mix["order"] == "cells":
            seq = by_cell(ctx.data, int(mix["n_cells"]), fixed)
        else:
            raise ValueError(f"unknown order {mix['order']!r}")
        n_b = seq.size // b
        if n_b < n_cycle + 1:
            raise ValueError(f"{n_b} batches of {b} rows, the cycle "
                             f"needs {n_cycle} and one to warm up")
        picks = np.linspace(0, n_b - 2, n_cycle).round().astype(np.int64)
        batches = seq[:n_b * b].reshape(n_b, b)
        cycle = ctx.data[batches[picks]]
        warm_rows = ctx.data[batches[n_b - 1]]
    elif mix["queries"] == "fresh":
        q = ctx.datagen.queries(ctx.cfg["data"], (n_cycle + 1) * b,
                                int(mix["master_seed"]))
        cycle = q[:n_cycle * b].reshape(n_cycle, b, -1)
        warm_rows = q[n_cycle * b:]
    else:
        raise ValueError(f"unknown queries {mix['queries']!r}")
    cycle = cycle[spread_order(n_cycle)]
    return Plan(batch_rows=b, rows=cycle.reshape(n_cycle * b, -1),
                warm_rows=warm_rows)


def warm(system, p: Plan) -> None:
    system.engine.join_batch(p.warm_rows)


def measure(system, p: Plan, seconds: float, span) -> Record:
    b, n = p.batch_rows, p.rows.shape[0]
    n_batches = n // b
    qs, ds, ids, batches = [], [], [], []
    t0 = time.perf_counter()
    i = 0
    with span("bench.window"):
        while True:
            lo = (i % n_batches) * b
            q = p.rows[lo:lo + b]
            t_s = time.perf_counter()
            d, ix = system.engine.join_batch(q)
            t_e = time.perf_counter()
            qs.append(q)
            ds.append(d)
            ids.append(ix)
            batches.append({"rows": int(q.shape[0]), "start_s": t_s - t0,
                            "end_s": t_e - t0})
            i += 1
            if t_e - t0 >= seconds:
                break
    rows = sum(x["rows"] for x in batches)
    elapsed = batches[-1]["end_s"]
    return Record(
        attempted=rows, failed=0, unanswered=0,
        queries=np.concatenate(qs), dists=np.concatenate(ds),
        ids=np.concatenate(ids), must=np.zeros((0,), np.int64),
        values={"rows_per_s": rows / elapsed, "window_s": elapsed},
        batches=batches, batch_queries=qs)
