"""A kNN retrieval datastore: ``serve.Datastore.build`` over the keys,
served through its resident engine and ``ServeScheduler``."""
from __future__ import annotations

import numpy as np

from bench.builders import System


def build(cfg: dict, keys: np.ndarray, seed: int) -> System:
    from repro import serve

    b = cfg["build"]
    values = np.random.default_rng(seed).integers(
        0, int(b["n_values"]), keys.shape[0]).astype(np.int32)
    store = serve.Datastore.build(keys, values, k=int(cfg["k"]),
                                  n_pivots=int(b["n_pivots"]),
                                  n_groups=int(b["n_groups"]), seed=seed)
    return System(engine=store.engine(),
                  scheduler=lambda: serve.ServeScheduler.for_datastore(store))
