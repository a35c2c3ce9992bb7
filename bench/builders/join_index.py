"""An offline self-join: ``build_index`` over S, joined by a
``StreamJoinEngine`` on the fused megastep."""
from __future__ import annotations

import numpy as np

from bench.builders import System


def build(cfg: dict, s: np.ndarray, seed: int) -> System:
    from repro.core import JoinConfig, StreamJoinEngine, build_index

    b = cfg["build"]
    jc = JoinConfig(k=int(cfg["k"]), n_pivots=int(b["n_pivots"]),
                    n_groups=int(b["n_groups"]), seed=seed)
    return System(engine=StreamJoinEngine(build_index(s, jc), jc,
                                          megastep=True))
