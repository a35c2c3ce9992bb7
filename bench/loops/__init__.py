"""Traffic loops, one module per ``loop`` kind of a traffic mix.

Each module exposes ``plan(ctx) -> plan`` (the traffic, made from the
seed during set-up), ``warm(system, plan)`` (every shape the window will
use), and ``measure(system, plan, seconds) -> Record``."""
from __future__ import annotations

import dataclasses
from typing import Optional

import numpy as np


@dataclasses.dataclass
class Record:
    """What one measured window produced.

    ``queries``/``dists``/``ids`` hold every answered row (the
    correctness sample is drawn from them); ``must`` indexes the rows of
    the largest request; ``values`` holds the end-to-end numbers the
    loop measured on the host clock."""

    attempted: int
    failed: int
    unanswered: int
    queries: np.ndarray
    dists: np.ndarray
    ids: np.ndarray
    must: np.ndarray
    values: dict
    batches: list = dataclasses.field(default_factory=list)
    batch_queries: list = dataclasses.field(default_factory=list)
    tickets: list = dataclasses.field(default_factory=list)
    sched: Optional[dict] = None
    side: dict = dataclasses.field(default_factory=dict)


def percentile(values, q: float) -> float:
    """Nearest-rank percentile over every value (no interpolation)."""
    v = np.sort(np.asarray(values, np.float64))
    if v.size == 0:
        raise ValueError("percentile of no values")
    rank = int(np.ceil(q / 100.0 * v.size)) - 1
    return float(v[min(max(rank, 0), v.size - 1)])
