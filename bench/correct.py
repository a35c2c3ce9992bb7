"""How ``correct`` is decided: answers of the timed path against the
plain reference of the configuration's metric.

Every number is checked against the limit the configuration's file
states under ``check``; a number above its limit makes the run not
correct. The numbers, for a sample of answered query rows:

* ``rank_gap``: the widest gap, over rows and ranks, between an answer
  and the exact (float64) k nearest distances of its row, over the exact
  k-th distance of that row (at least the sample's median k-th
  distance); an answer is read twice, once by the distance the program
  reported and once by the exact distance of the id it returned (ids
  sorted), so a wrong or swapped id shows even where its reported
  distance is right;
* ``bad_ids``: rows whose ids are out of range or repeated;
* ``unanswered``: requests due in the window that never got an answer
  (an explicit shed or rejection is the system's answer, and is counted
  under ``failed`` instead).
"""
from __future__ import annotations

import numpy as np

TINY = 1e-30


def gaps(q, s, d_prog, ids_prog, dref, exact_dists) -> dict:
    k = dref.shape[1]
    d_prog = np.asarray(d_prog, np.float64)[:, :k]
    ids_prog = np.asarray(ids_prog, np.int64)[:, :k]
    # each row's k-th distance, floored at the sample's median k-th
    # distance: a row with k exact duplicates has a k-th distance of 0
    scale = np.maximum(dref[:, -1:],
                       max(float(np.median(dref[:, -1])), TINY))
    in_range = (ids_prog >= 0) & (ids_prog < s.shape[0])
    srt = np.sort(ids_prog, axis=1)
    dup = (srt[:, 1:] == srt[:, :-1]).any(axis=1)
    bad = ~in_range.all(axis=1) | dup
    dist_gap = np.abs(d_prog - dref) / scale
    d_ids = np.sort(exact_dists(q, s, ids_prog), axis=1)
    id_gap = np.abs(d_ids - dref) / scale
    ok = ~bad
    worst_id = float(id_gap[ok].max()) if ok.any() else 0.0
    worst_dist = float(np.nan_to_num(dist_gap, nan=np.inf,
                                     posinf=1e300).max())
    return {"rank_gap": max(worst_dist, worst_id),
            "bad_ids": int(bad.sum())}


def verdict(numbers: dict, limits: dict) -> tuple[bool, dict]:
    """(correct, {name: {"value", "limit"}}) — a number missing from
    ``limits`` is an error, never a pass."""
    out, ok = {}, True
    for name, value in numbers.items():
        limit = limits[name]
        out[name] = {"value": value, "limit": limit}
        ok &= value <= limit
    return bool(ok), out


def sample_rows(n_rows: int, n: int, rng, must=()) -> np.ndarray:
    """Indices of up to ``n`` answered rows drawn from the seed, with
    ``must`` (the rows of the largest request) always in."""
    must = np.unique(np.asarray(must, np.int64))
    rest = np.setdiff1d(np.arange(n_rows), must)
    take = max(0, min(n - must.size, rest.size))
    pick = rng.choice(rest, take, replace=False) if take else rest[:0]
    return np.sort(np.concatenate([must, pick]))
