"""Pallas TPU kernels: tiled pairwise L2 + streaming top-k.

This is the PGBJ reducer hot loop (Algorithm 3, lines 16-25) as fused
kernels: the `-2 R Sᵀ` contraction runs on the MXU; a per-row running
top-k lives in VMEM scratch across the S grid dimension as a *sorted
run* (see kernels.sorted_merge); the paper's pruning rules (Cor. 1 /
Thm 2 evaluated at tile granularity — DESIGN.md §2.1) enter two ways:

* ``distance_topk_pallas`` — dense ``(nr_tiles, ns_tiles)`` grid with an
  optional per-tile visit mask. ``pl.when`` elides a pruned tile's
  *compute* but its HBM→VMEM stream still runs.

* ``distance_topk_gather_pallas`` — pruned-schedule execution. The grid
  is ``(nr_tiles, max_visits)`` and the S-tile index of each step is read
  from a scalar-prefetched compacted schedule (core.schedule), so pruned
  tiles are **never DMA'd**: skipped tiles cost zero bytes and zero
  FLOPs. Schedule rows are padded by repeating their last entry — an
  unchanged block index means the pipeline re-uses the resident VMEM
  block instead of issuing a new copy.

  The optional ``alive`` row mask (float32, >0 = live) serves the fused
  megastep (core.megastep): the schedule may concatenate the tile ranges
  of *several* index segments, and the per-query running top-k then
  carries across segment boundaries in VMEM scratch — one launch per
  micro-batch instead of one per segment, with no per-segment (n, k)
  runs round-tripping through HBM. Tombstoned rows and per-segment
  padding rows arrive with ``alive == 0`` and are masked to +inf
  *before* selection, so the flushed run is the exact top-k over live
  rows only.

Both kernels fold a tile only where it can change the run: a compare
and a reduction over the tile against each row's running kp-th distance
gate the sort and the merge (``_merge_tile``). A step the gate skips
leaves the scratch bitwise as the merge would have, so the gate is exact
and needs no switch; each kernel counts, per R tile, the steps that
merged (its third output).

VMEM budget per step (bm=128, bn=512, d≤128, k≤64, f32):
  R tile 64 KiB + S tile 256 KiB + dist tile 256 KiB + scratch 2·32 KiB
  + sort temporaries ≈ 1 MiB  — comfortably inside the ~16 MiB/core VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .sorted_merge import merge_sorted_runs, next_pow2, tile_topk

__all__ = [
    "distance_topk_kernel", "distance_topk_pallas",
    "distance_topk_gather_kernel", "distance_topk_gather_pallas",
]


def _sq_dists(r_ref, s_ref):
    """(bm, bn) squared L2 distances between the resident tiles. The
    contraction is full f32 (HIGHEST): the MXU's default one-pass bf16
    would make the top-k select the wrong rows."""
    r = r_ref[...].astype(jnp.float32)                    # (bm, d)
    s = s_ref[...].astype(jnp.float32)                    # (bn, d)
    d2 = (jnp.sum(r * r, axis=1, keepdims=True)
          + jnp.sum(s * s, axis=1)[None, :]
          - 2.0 * jax.lax.dot_general(
              r, s, (((1,), (1,)), ((), ())),
              precision=jax.lax.Precision.HIGHEST,
              preferred_element_type=jnp.float32))
    return jnp.maximum(d2, 0.0)


def _start_run(scratch_d, scratch_i, merged_ref, i, *, bm: int, n_valid):
    """Open R tile ``i``'s running run: +inf on rows below ``n_valid``,
    −inf on the padding rows past it. A padding row's run then never has
    a candidate under its kp-th entry, so it never holds the merge gate
    open; its flushed entries are (nan, −1), which callers drop."""
    row = i * bm + jax.lax.broadcasted_iota(jnp.int32, scratch_d.shape, 0)
    scratch_d[...] = jnp.where(row < n_valid, jnp.inf, -jnp.inf)
    scratch_i[...] = jnp.full_like(scratch_i, -1)
    merged_ref[i] = 0


def _merge_tile(scratch_d, scratch_i, merged_ref, i, d2, gid, kp: int):
    """Fold one tile of candidates into the running sorted kp-run.

    The tile is sorted and merged only where some candidate lies strictly
    under its row's running kp-th distance. Otherwise the merge would
    return the run unchanged, ids included (``merge_sorted_runs`` keeps
    the running run on ties and its stages never swap ties), so the step
    skips the sort and leaves the scratch as it is. The gate is a compare
    and an int max over the tile, not ``min(d2 − kth)``: inf − inf is
    nan. ``merged_ref[i]`` counts the steps that merged."""
    kth = scratch_d[:, kp - 1:kp]                          # (bm, 1)

    @pl.when(jnp.max((d2 < kth).astype(jnp.int32)) > 0)
    def _merge():
        td, ti = tile_topk(d2, jnp.broadcast_to(gid, d2.shape), kp)
        scratch_d[...], scratch_i[...] = merge_sorted_runs(
            scratch_d[...], scratch_i[...], td, ti)
        merged_ref[i] += 1


def _flush(out_d_ref, out_i_ref, scratch_d, scratch_i, k: int):
    out_d_ref[...] = jnp.sqrt(scratch_d[...][:, :k])
    out_i_ref[...] = scratch_i[...][:, :k]


def _merged_spec():
    """The (nr_tiles,) int32 merged-step count: one SMEM block holding
    the whole array, resident for the whole grid."""
    from jax.experimental.pallas import tpu as pltpu
    return pl.BlockSpec(memory_space=pltpu.SMEM)


def distance_topk_kernel(
    # refs:
    r_ref, s_ref, mask_ref, out_d_ref, out_i_ref, merged_ref,
    scratch_d, scratch_i,
    *, k: int, kp: int, n_r: int, n_s: int, bm: int, bn: int,
    ns_tiles: int,
):
    """One (R tile, S tile) grid step of the dense (masked) kernel."""
    i = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        _start_run(scratch_d, scratch_i, merged_ref, i, bm=bm, n_valid=n_r)

    visit = mask_ref[...][0, 0] != 0

    @pl.when(visit)
    def _compute():
        d2 = _sq_dists(r_ref, s_ref)
        # mask S padding rows (global id >= n_s)
        gid = j * bn + jax.lax.broadcasted_iota(jnp.int32, (1, bn), 1)
        d2 = jnp.where(gid < n_s, d2, jnp.inf)
        _merge_tile(scratch_d, scratch_i, merged_ref, i, d2, gid, kp)

    @pl.when(j == ns_tiles - 1)
    def _out():
        _flush(out_d_ref, out_i_ref, scratch_d, scratch_i, k)


def distance_topk_pallas(
    r: jnp.ndarray,
    s: jnp.ndarray,
    k: int,
    *,
    visit_mask: jnp.ndarray | None = None,
    bm: int = 128,
    bn: int = 512,
    interpret: bool = False,
):
    """k nearest rows of ``s`` for each row of ``r``: ``(dists ascending,
    ids, merged)``, where ``merged`` (nr_tiles,) int32 counts each R
    tile's steps that sorted and merged a tile (see ``_merge_tile``).

    visit_mask: optional (nr_tiles, ns_tiles) int8 — tiles proved
    irrelevant by the PGBJ bounds are never computed (their DMA still
    streams; use ``distance_topk_gather_pallas`` to skip the load too).
    """
    n_r, d = r.shape
    n_s, _ = s.shape
    nr_tiles = -(-n_r // bm)
    ns_tiles = -(-n_s // bn)
    kp = next_pow2(k)
    r_pad = jnp.pad(r, ((0, nr_tiles * bm - n_r), (0, 0)))
    s_pad = jnp.pad(s, ((0, ns_tiles * bn - n_s), (0, 0)))
    if visit_mask is None:
        visit_mask = jnp.ones((nr_tiles, ns_tiles), jnp.int8)
    # one (1, 1) block per step must cover the array's last two dims
    visit_mask = visit_mask.astype(jnp.int32).reshape(nr_tiles, ns_tiles, 1, 1)

    kernel = functools.partial(
        distance_topk_kernel, k=k, kp=kp, n_r=n_r, n_s=n_s, bm=bm, bn=bn,
        ns_tiles=ns_tiles)
    out_d, out_i, merged = pl.pallas_call(
        kernel,
        grid=(nr_tiles, ns_tiles),
        in_specs=[
            pl.BlockSpec((bm, d), lambda i, j: (i, 0)),
            pl.BlockSpec((bn, d), lambda i, j: (j, 0)),
            pl.BlockSpec((None, None, 1, 1), lambda i, j: (i, j, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bm, k), lambda i, j: (i, 0)),
            pl.BlockSpec((bm, k), lambda i, j: (i, 0)),
            _merged_spec(),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((nr_tiles * bm, k), jnp.float32),
            jax.ShapeDtypeStruct((nr_tiles * bm, k), jnp.int32),
            jax.ShapeDtypeStruct((nr_tiles,), jnp.int32),
        ],
        scratch_shapes=[
            pl_scratch((bm, kp), jnp.float32),
            pl_scratch((bm, kp), jnp.int32),
        ],
        interpret=interpret,
        name="distance_topk",
    )(r_pad, s_pad, visit_mask)
    return out_d[:n_r], out_i[:n_r], merged


def distance_topk_gather_kernel(
    # scalar-prefetch refs, then tensor refs:
    sched_ref, cnt_ref, nv_ref, r_ref, s_ref, *refs,
    alive: bool, k: int, kp: int, n_s: int, bm: int, bn: int,
    max_visits: int,
):
    """One (R tile, visit slot) step of the pruned-schedule kernel.

    ``s_ref`` already holds the tile the schedule names for this slot —
    the BlockSpec index map reads ``sched_ref`` before the body runs, so
    only scheduled tiles ever cross HBM→VMEM. ``nv_ref[0]`` is the number
    of valid R rows; rows past it are bucket padding.

    With ``alive`` the first of ``refs`` is the scheduled tile's (1, bn)
    float32 liveness mask — the megastep's in-VMEM cross-segment scan
    step (tombstones and per-segment padding are 0); masked rows are
    +inf *before* the sorted-run fold, so the carried VMEM run is always
    the exact top-k over live rows seen so far.
    """
    alive_ref, refs = (refs[0], refs[1:]) if alive else (None, refs)
    out_d_ref, out_i_ref, merged_ref, scratch_d, scratch_i = refs
    i = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        _start_run(scratch_d, scratch_i, merged_ref, i, bm=bm,
                   n_valid=nv_ref[0])

    @pl.when(j < cnt_ref[i])
    def _compute():
        tile = sched_ref[i, j]
        d2 = _sq_dists(r_ref, s_ref)
        gid = tile * bn + jax.lax.broadcasted_iota(jnp.int32, (1, bn), 1)
        live = gid < n_s
        if alive_ref is not None:
            live = (alive_ref[...] > 0.0) & live
        d2 = jnp.where(live, d2, jnp.inf)
        _merge_tile(scratch_d, scratch_i, merged_ref, i, d2, gid, kp)

    @pl.when(j == max_visits - 1)
    def _out():
        _flush(out_d_ref, out_i_ref, scratch_d, scratch_i, k)


def distance_topk_gather_pallas(
    r: jnp.ndarray,
    s: jnp.ndarray,
    k: int,
    schedule: jnp.ndarray,
    counts: jnp.ndarray,
    *,
    alive: jnp.ndarray | None = None,
    n_valid: jnp.ndarray | int | None = None,
    bm: int = 128,
    bn: int = 512,
    interpret: bool = False,
):
    """Pruned-schedule top-k: each R tile visits only its scheduled S tiles.

    schedule: (nr_tiles, max_visits) int32 S-tile indices, rows padded by
              repeating the last valid entry (core.schedule.TileSchedule).
    counts:   (nr_tiles,) int32 — number of real entries per row.
    alive:    optional (n_s,) float32 row-liveness mask (>0 = live). Used
              by the megastep to mask tombstoned rows and per-segment
              padding inside a concatenated multi-segment layout; rows
              with ``alive == 0`` can never enter the top-k.
    n_valid:  optional (traced) count of leading rows of ``r`` that are
              real; the rows past it (a padded bucket's tail) return
              (nan, −1). Defaults to every row.

    Returns ``(dists, ids, merged)``; ``merged`` (nr_tiles,) int32 counts
    each R tile's steps that sorted and merged a tile (``_merge_tile``).
    Ids are row indices into ``s`` as laid out here; callers that sorted S
    for tile coherence translate back through their permutation.
    """
    from jax.experimental.pallas import tpu as pltpu

    n_r, d = r.shape
    n_s, _ = s.shape
    nr_tiles = -(-n_r // bm)
    ns_tiles = -(-n_s // bn)
    if schedule.shape[0] != nr_tiles:
        raise ValueError(
            f"schedule has {schedule.shape[0]} rows for {nr_tiles} R tiles "
            f"(bm={bm})")
    max_visits = schedule.shape[1]
    kp = next_pow2(k)
    r_pad = jnp.pad(r, ((0, nr_tiles * bm - n_r), (0, 0)))
    s_pad = jnp.pad(s, ((0, ns_tiles * bn - n_s), (0, 0)))
    nv = jnp.reshape(jnp.minimum(n_r if n_valid is None else n_valid, n_r),
                     (1,)).astype(jnp.int32)

    kernel = functools.partial(
        distance_topk_gather_kernel, alive=alive is not None, k=k, kp=kp,
        n_s=n_s, bm=bm, bn=bn, max_visits=max_visits)
    in_specs = [
        pl.BlockSpec((bm, d), lambda i, j, sched, cnt, nv: (i, 0)),
        pl.BlockSpec((bn, d), lambda i, j, sched, cnt, nv: (sched[i, j], 0)),
    ]
    args = [schedule.astype(jnp.int32), counts.astype(jnp.int32), nv,
            r_pad, s_pad]
    if alive is not None:
        # (ns_tiles, 1, bn) so each block covers the array's last two
        # dims whole — Mosaic refuses a (1, bn) block of an (ns_tiles, bn)
        # array (the sublane dim must be a multiple of 8 or the full dim)
        alive_pad = jnp.pad(alive.astype(jnp.float32),
                            (0, ns_tiles * bn - n_s)).reshape(ns_tiles, 1, bn)
        in_specs.append(pl.BlockSpec(
            (None, 1, bn),
            lambda i, j, sched, cnt, nv: (sched[i, j], 0, 0)))
        args.append(alive_pad)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(nr_tiles, max_visits),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((bm, k), lambda i, j, sched, cnt, nv: (i, 0)),
            pl.BlockSpec((bm, k), lambda i, j, sched, cnt, nv: (i, 0)),
            _merged_spec(),
        ],
        scratch_shapes=[
            pl_scratch((bm, kp), jnp.float32),
            pl_scratch((bm, kp), jnp.int32),
        ],
    )
    out_d, out_i, merged = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((nr_tiles * bm, k), jnp.float32),
            jax.ShapeDtypeStruct((nr_tiles * bm, k), jnp.int32),
            jax.ShapeDtypeStruct((nr_tiles,), jnp.int32),
        ],
        interpret=interpret,
        name="gather_topk",
    )(*args)
    return out_d[:n_r], out_i[:n_r], merged


def pl_scratch(shape, dtype):
    """VMEM scratch allocation that also works in interpret mode."""
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.VMEM(shape, dtype)
