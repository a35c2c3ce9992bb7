"""Pivot selection strategies (paper §4.1).

All three strategies from the paper are implemented. They run on the
"master node" (host) over a sample, exactly as the paper prescribes —
selection cost must not scale with |R|.

The distance computations are vectorized jnp so the same code JITs on
TPU for large samples, but they gracefully run on host numpy inputs too.
"""
from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp

__all__ = ["select_pivots", "pairwise_sqdist"]


def pairwise_sqdist(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """Squared L2 distances (na, nb).  ``-2ab`` term hits the MXU on TPU."""
    a2 = jnp.sum(a * a, axis=-1, keepdims=True)       # (na, 1)
    b2 = jnp.sum(b * b, axis=-1, keepdims=True).T      # (1, nb)
    d2 = a2 + b2 - 2.0 * jnp.matmul(a, b.T,
                                    precision=jax.lax.Precision.HIGHEST)
    return jnp.maximum(d2, 0.0)


def _sample(data: np.ndarray, n: int, rng: np.random.Generator) -> np.ndarray:
    if data.shape[0] <= n:
        return np.asarray(data)
    idx = rng.choice(data.shape[0], size=n, replace=False)
    return np.asarray(data[idx])


def _random_selection(data, m, *, n_sets, rng):
    """Paper: draw T random candidate sets, keep the one with max total
    pairwise distance (a spread heuristic).

    All T candidate sets are scored in one batched device call (a
    single (T, m, m) einsum + one fetch) instead of T sequential
    pairwise-distance round-trips — same rng draw order, same argmax,
    ~T× fewer host↔device syncs on the build/seal path.
    """
    cands = np.stack([_sample(data, m, rng).astype(np.float32)
                      for _ in range(max(1, n_sets))])        # (T, m, dim)
    c = jnp.asarray(cands)
    n2 = jnp.sum(c * c, axis=-1)                              # (T, m)
    d2 = n2[:, :, None] + n2[:, None, :] \
        - 2.0 * jnp.einsum("tmd,tnd->tmn", c, c,
                           precision=jax.lax.Precision.HIGHEST)
    scores = jnp.sqrt(jnp.maximum(d2, 0.0)).sum(axis=(1, 2))  # (T,)
    return cands[int(np.argmax(np.asarray(scores)))]


def _farthest_selection(data, m, *, sample, rng):
    """Iterative farthest-point: maximize sum of distance to chosen pivots."""
    pts = _sample(data, sample, rng).astype(np.float32)
    first = int(rng.integers(pts.shape[0]))
    chosen = [first]
    # running sum of distances from each candidate to the chosen set
    acc = np.sqrt(
        np.asarray(pairwise_sqdist(jnp.asarray(pts), jnp.asarray(pts[first : first + 1])))
    )[:, 0]
    for _ in range(1, m):
        acc[chosen] = -np.inf  # never re-pick
        nxt = int(np.argmax(acc))
        chosen.append(nxt)
        acc = np.where(
            np.isneginf(acc), acc,
            acc + np.sqrt(np.asarray(
                pairwise_sqdist(jnp.asarray(pts), jnp.asarray(pts[nxt : nxt + 1]))))[:, 0],
        )
    return pts[np.asarray(chosen)]


def _kmeans_selection(data, m, *, sample, rng, iters: int = 10):
    """k-means on a sample; cluster centers become pivots."""
    pts = jnp.asarray(_sample(data, sample, rng).astype(np.float32))
    init_idx = rng.choice(pts.shape[0], size=m, replace=False)
    centers = pts[jnp.asarray(init_idx)]

    @jax.jit
    def step(centers):
        d2 = pairwise_sqdist(pts, centers)                  # (n, m)
        assign = jnp.argmin(d2, axis=1)
        one_hot = jax.nn.one_hot(assign, m, dtype=pts.dtype)  # (n, m)
        sums = jnp.matmul(one_hot.T, pts,                   # (m, dim)
                          precision=jax.lax.Precision.HIGHEST)
        cnts = one_hot.sum(axis=0)[:, None]                 # (m, 1)
        # empty cluster keeps its previous center
        return jnp.where(cnts > 0, sums / jnp.maximum(cnts, 1.0), centers)

    for _ in range(iters):
        centers = step(centers)
    return np.asarray(centers)


def select_pivots(
    data: np.ndarray,
    m: int,
    strategy: str = "random",
    *,
    sample: int = 4096,
    n_sets: int = 8,
    seed: int = 0,
) -> np.ndarray:
    """Select ``m`` pivots from ``data`` using a paper §4.1 strategy."""
    data = np.asarray(data)
    if m > data.shape[0]:
        raise ValueError(f"cannot select {m} pivots from {data.shape[0]} objects")
    rng = np.random.default_rng(seed)
    if strategy == "random":
        out = _random_selection(data, m, n_sets=n_sets, rng=rng)
    elif strategy == "farthest":
        out = _farthest_selection(data, m, sample=max(sample, m), rng=rng)
    elif strategy == "kmeans":
        out = _kmeans_selection(data, m, sample=max(sample, m), rng=rng)
    else:
        raise ValueError(f"unknown pivot strategy {strategy!r}")
    return np.ascontiguousarray(out, dtype=np.float32)
