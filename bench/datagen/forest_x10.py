"""Forest CoverType-like rows, expanded by the paper's section 6 method.

A copy of the program's ``repro.data.forest_like`` and a corrected one
of its ``repro.data.expand_dataset``, kept with the benchmark so that no
later change to the program can change the data a cell measures. The
rows are synthetic: CoverType's own rows cannot be had here, so the
cluster structure, and with it how far the Voronoi bounds prune, is the
generator's and not CoverType's.
"""
from __future__ import annotations

import numpy as np


def forest_like(n: int, dim: int, seed, n_clusters: int = 32) -> np.ndarray:
    """Clustered integer-valued features with per-dimension spread that
    decays, like CoverType's 10 integer attributes."""
    rng = np.random.default_rng(seed)
    dim_scale = 1.0 / (1.0 + 0.9 * np.arange(dim))
    centers = rng.uniform(0, 1000, (n_clusters, dim)) * dim_scale
    scales = rng.uniform(5, 60, (n_clusters, dim)) * dim_scale
    who = rng.integers(0, n_clusters, n)
    pts = centers[who] + rng.normal(size=(n, dim)) * scales[who]
    return np.round(pts).astype(np.float32)


def expand_dataset(data: np.ndarray, factor: int) -> np.ndarray:
    """Section 6's expansion: copy t of a row replaces each value by the
    value t places further in that dimension's list of distinct values,
    sorted by ascending frequency (ties by value), wrapping at its end.

    The program's ``repro.data.expand_dataset``, which this follows,
    looks a value's place up with ``searchsorted`` in the list sorted by
    frequency, which is not sorted by value; here the place is looked up
    by value and then read in the frequency order."""
    if factor <= 1:
        return data
    out = [data]
    ranks, orders = [], []
    for d in range(data.shape[1]):
        vals, inv, counts = np.unique(data[:, d], return_inverse=True,
                                      return_counts=True)
        by_freq = np.argsort(counts, kind="stable")
        place = np.empty_like(by_freq)
        place[by_freq] = np.arange(by_freq.size)
        ranks.append(place[inv])
        orders.append(vals[by_freq])
    for t in range(1, factor):
        new = np.empty_like(data)
        for d, (rank, srt) in enumerate(zip(ranks, orders)):
            new[:, d] = srt[(rank + t) % srt.size]
        out.append(new)
    return np.concatenate(out, axis=0)


def generate(params: dict, seed) -> np.ndarray:
    """The S rows of a configuration whose ``data.generator`` is
    ``forest_x10``: ``n_base`` rows, expanded ``factor`` times."""
    base = forest_like(int(params["n_base"]), int(params["dim"]), seed,
                       n_clusters=int(params["n_clusters"]))
    return expand_dataset(base, int(params["factor"]))
