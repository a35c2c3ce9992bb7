"""Plain reference of exact Euclidean k nearest neighbours.

Imports nothing of the program. ``truth`` finds a shortlist of
candidates per query on the device (float32, ``precision=HIGHEST``,
rows centred on the mean of S), then ranks the shortlist on the host in
float64 and returns the k smallest distances. A shortlist of
``SHORTLIST`` rows is far wider than the float32 error of the
candidate search, so the k distances are the exact ones.

``control`` is the same search put in the program's place at the
nearest lower precision, ``high``: three bf16 passes, written out here
so that it computes the same on every backend. Its distances are those
that search computes; nothing is re-ranked in float64.
"""
from __future__ import annotations

import functools

import numpy as np

SHORTLIST = 64
BLOCK_S = 32768
BLOCK_Q = 1024


def _dot(a, b, mode: str):
    import jax
    import jax.numpy as jnp

    dn = (((1,), (1,)), ((), ()))
    if mode == "highest":
        return jax.lax.dot_general(a, b, dn,
                                   precision=jax.lax.Precision.HIGHEST,
                                   preferred_element_type=jnp.float32)
    # bf16_3x: hi*hi + hi*lo + lo*hi, hi and lo rounded to bf16 by
    # reduce_precision (a convert pair may be folded away by XLA), each
    # product of two bf16 values exact in float32
    def split(x):
        hi = jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)
        lo = jax.lax.reduce_precision(x - hi, exponent_bits=8,
                                      mantissa_bits=7)
        return hi, lo

    (ah, al), (bh, bl) = split(a), split(b)
    f = functools.partial(jax.lax.dot_general, dimension_numbers=dn,
                          precision=jax.lax.Precision.DEFAULT,
                          preferred_element_type=jnp.float32)
    return f(ah, bh) + (f(ah, bl) + f(al, bh))


@functools.lru_cache(maxsize=None)
def _search_fn(n_blocks: int, block: int, width: int, mode: str):
    import jax
    import jax.numpy as jnp

    @jax.jit
    def search(q, s_blocks, s_norms):
        qn = jnp.sum(q * q, axis=1)

        def body(carry, xs):
            cd, ci = carry
            sb, sn, base = xs
            d2 = qn[:, None] + sn[None, :] - 2.0 * _dot(q, sb, mode)
            neg, p = jax.lax.top_k(-d2, width)
            allv = jnp.concatenate([cd, -neg], axis=1)
            alli = jnp.concatenate([ci, base + p], axis=1)
            neg, p = jax.lax.top_k(-allv, width)
            return (-neg, jnp.take_along_axis(alli, p, axis=1)), None

        carry = (jnp.full((q.shape[0], width), jnp.inf, jnp.float32),
                 jnp.full((q.shape[0], width), -1, jnp.int32))
        bases = jnp.arange(n_blocks, dtype=jnp.int32) * block
        (d, i), _ = jax.lax.scan(body, carry, (s_blocks, s_norms, bases))
        return d, i

    return search


class Searcher:
    """S uploaded once, centred and cut into blocks; queries searched in
    blocks of ``BLOCK_Q`` rows."""

    def __init__(self, s: np.ndarray, block: int = BLOCK_S):
        import jax.numpy as jnp

        self.s = np.ascontiguousarray(s, np.float32)
        n, dim = self.s.shape
        self.center = self.s.mean(axis=0, dtype=np.float64).astype(
            np.float32)
        block = min(block, 1 << max(0, (n - 1).bit_length()))
        nb = -(-n // block)
        sc = np.zeros((nb * block, dim), np.float32)
        sc[:n] = self.s - self.center
        norms = np.full((nb * block,), np.inf, np.float32)
        norms[:n] = np.einsum("ij,ij->i", sc[:n], sc[:n])
        self.blocks = jnp.asarray(sc.reshape(nb, block, dim))
        self.norms = jnp.asarray(norms.reshape(nb, block))
        self.n_blocks, self.block = nb, block

    def search(self, q: np.ndarray, width: int, mode: str):
        fn = _search_fn(self.n_blocks, self.block, width, mode)
        qc = np.ascontiguousarray(q, np.float32) - self.center
        ds, ids = [], []
        for lo in range(0, qc.shape[0], BLOCK_Q):
            d, i = fn(qc[lo:lo + BLOCK_Q], self.blocks, self.norms)
            ds.append(np.asarray(d))
            ids.append(np.asarray(i))
        return np.concatenate(ds), np.concatenate(ids).astype(np.int64)


def exact_dists(q: np.ndarray, s: np.ndarray, ids: np.ndarray
                ) -> np.ndarray:
    """float64 distances of each query to the given rows of S (inf where
    an id is out of range)."""
    ok = (ids >= 0) & (ids < s.shape[0])
    rows = s[np.where(ok, ids, 0)].astype(np.float64)
    diff = rows - np.asarray(q, np.float64)[:, None, :]
    d = np.sqrt(np.einsum("qkd,qkd->qk", diff, diff))
    return np.where(ok, d, np.inf)


def truth(searcher: Searcher, q: np.ndarray, k: int) -> np.ndarray:
    """(Q, k) float64 exact k smallest distances, ascending."""
    _, cand = searcher.search(q, max(SHORTLIST, k), "highest")
    d = np.sort(exact_dists(q, searcher.s, cand), axis=1)
    return d[:, :k]


def control(searcher: Searcher, q: np.ndarray, k: int):
    """(dists, ids) of the search at ``high`` precision, as it computes
    them."""
    d2, ids = searcher.search(q, k, "high")
    return np.sqrt(np.maximum(d2, 0.0)).astype(np.float32), ids
