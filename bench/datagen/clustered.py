"""Gaussian blobs around shared uniform centers in [-20, 20]^dim.

A copy of the program's ``repro.data.clustered_like`` kept with the
benchmark, so that no later change to the program can change the data a
cell measures. ``centers_seed`` is fixed, so keys and queries drawn with
different seeds share their cluster structure.
"""
from __future__ import annotations

import numpy as np


def clustered_like(n: int, dim: int, seed, *, n_centers: int = 16,
                   centers_seed: int = 42) -> np.ndarray:
    centers = np.random.default_rng(centers_seed).uniform(
        -20, 20, (n_centers, dim)).astype(np.float32)
    rng = np.random.default_rng(seed)
    who = rng.integers(0, n_centers, n)
    return (centers[who] + rng.normal(size=(n, dim))).astype(np.float32)


def generate(params: dict, seed) -> np.ndarray:
    """The S rows of a configuration whose ``data.generator`` is
    ``clustered``."""
    return clustered_like(int(params["n"]), int(params["dim"]), seed,
                          n_centers=int(params["n_centers"]),
                          centers_seed=int(params["centers_seed"]))


def queries(params: dict, n: int, seed) -> np.ndarray:
    """Fresh query rows from the same blobs as the keys."""
    return clustered_like(n, int(params["dim"]), seed,
                          n_centers=int(params["n_centers"]),
                          centers_seed=int(params["centers_seed"]))
