"""Pallas TPU kernel: nearest-pivot assignment (PGBJ phase-1 hot loop).

Fuses the paper's job-1 map: for each object tile, distances to every
pivot tile (MXU) with a running (min, argmin) in VMEM — one pass over the
data, no materialized (n, M) distance matrix in HBM.

Grid: ``(n_tiles, m_tiles)`` — pivots minor, so the running min persists
per data tile and flushes on the last pivot step.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .distance_topk import pl_scratch

__all__ = ["assign_kernel", "assign_pallas"]


def assign_kernel(
    x_ref, p_ref, pid_ref, dist_ref, min_d, min_i,
    *, m: int, bp: int, mp_tiles: int,
):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        min_d[...] = jnp.full_like(min_d, jnp.inf)
        min_i[...] = jnp.full_like(min_i, -1)

    x = x_ref[...].astype(jnp.float32)                    # (bm, d)
    p = p_ref[...].astype(jnp.float32)                    # (bp, d)
    d2 = (jnp.sum(x * x, axis=1, keepdims=True)
          + jnp.sum(p * p, axis=1)[None, :]
          - 2.0 * jax.lax.dot_general(
              x, p, (((1,), (1,)), ((), ())),
              precision=jax.lax.Precision.HIGHEST,
              preferred_element_type=jnp.float32))
    d2 = jnp.maximum(d2, 0.0)
    gid = j * bp + jax.lax.broadcasted_iota(jnp.int32, d2.shape, 1)
    d2 = jnp.where(gid < m, d2, jnp.inf)                  # mask pivot padding
    tile_min = jnp.min(d2, axis=1)
    tile_arg = jnp.argmin(d2, axis=1).astype(jnp.int32) + j * bp
    better = tile_min < min_d[..., 0]
    min_i[..., 0] = jnp.where(better, tile_arg, min_i[..., 0])
    min_d[..., 0] = jnp.where(better, tile_min, min_d[..., 0])

    @pl.when(j == mp_tiles - 1)
    def _flush():
        pid_ref[..., 0] = min_i[..., 0]
        dist_ref[..., 0] = jnp.sqrt(min_d[..., 0])


def assign_pallas(
    x: jnp.ndarray,
    pivots: jnp.ndarray,
    *,
    bm: int = 256,
    bp: int = 512,
    interpret: bool = False,
):
    """(part_id (n,), dist (n,)) — nearest pivot per row of x."""
    n, d = x.shape
    m, _ = pivots.shape
    n_tiles = -(-n // bm)
    mp_tiles = -(-m // bp)
    x_pad = jnp.pad(x, ((0, n_tiles * bm - n), (0, 0)))
    p_pad = jnp.pad(pivots, ((0, mp_tiles * bp - m), (0, 0)))
    kernel = functools.partial(assign_kernel, m=m, bp=bp, mp_tiles=mp_tiles)
    pid, dist = pl.pallas_call(
        kernel,
        grid=(n_tiles, mp_tiles),
        in_specs=[
            pl.BlockSpec((bm, d), lambda i, j: (i, 0)),
            pl.BlockSpec((bp, d), lambda i, j: (j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bm, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((bm, 1), lambda i, j: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n_tiles * bm, 1), jnp.int32),
            jax.ShapeDtypeStruct((n_tiles * bm, 1), jnp.float32),
        ],
        scratch_shapes=[
            pl_scratch((bm, 1), jnp.float32),
            pl_scratch((bm, 1), jnp.int32),
        ],
        interpret=interpret,
    )(x_pad, p_pad)
    return pid[:n, 0], dist[:n, 0]
