"""Pallas int8 coarse-scan kernel: schedule-driven shortlist selection
over quantized S tiles.

The quantized tier's phase-1 kernel (see `repro.quant`): queries and S
rows arrive as symmetric int8 codes, the ``-2 Q Sᵀ`` contraction runs as
an int8 dot with **int32 accumulation**, and one float32 rescale per
(query tile, S tile) step recovers coarse squared distances. The
selection key per candidate is the *certified lower bound*

    lb = max(d_coarse − (ε_s + ε_q + ε_num), 0)

where ε_s / ε_q are the stored per-row / per-query reconstruction-error
bounds (`repro.quant.quantize`) and ε_num = δ / max(d_coarse, √δ) with
δ = NUM_DELTA_REL·(‖q̂‖² + ‖ŝ‖²) dominates the float32 rescale/sqrt
rounding (see the NUM_DELTA_REL comment below for the derivation; the
bound is tight ≈ δ/d for d ≫ √δ and exactly √δ at d = 0). Candidates whose
lower bound already exceeds the query's θ are masked: that is the
paper's pruning rule with the threshold *inflated by ε*, so a true
neighbor (d ≤ θ) can never be dropped — its lb ≤ d ≤ θ.

Like `distance_topk_gather_pallas`, the grid is (R tile, visit slot)
with the S-tile index scalar-prefetched from a compacted schedule:
pruned tiles are never DMA'd, and the tiles that *are* streamed move
int8 — 4× fewer bytes than the fp32 gather kernel. The running
shortlist (an ascending sorted mp-run of (lb, row) pairs) lives in VMEM
scratch across the whole concatenated multi-segment schedule.

The kernel returns a *shortlist*, not a result: `repro.quant.engine`
re-ranks the shortlisted rows with exact fp32 canonical distances and
certifies per query that the exclusion was sound. The jnp oracle is
`kernels.ref.quant_coarse_topk_ref` (dense, same rescale formula).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .distance_topk import pl_scratch
from .sorted_merge import merge_sorted_runs, tile_topk

__all__ = ["quant_coarse_gather_kernel", "quant_coarse_gather_pallas",
           "coarse_lb_tile"]

# float32 rounding allowance of the rescale + sqrt (see coarse_lb_tile):
# |d2_f32 − d2_exact| ≤ δ = NUM_DELTA_REL·(‖q̂‖² + ‖ŝ‖²) — the int8 dot
# and the squared norms are exact in int32, so only ~5 fp32 ops round,
# each against a term of at most 2(‖q̂‖²+‖ŝ‖²); 2e-6 ≈ 16 ulp is a 3×
# margin over that. In distance space the error is then at most
# δ / max(d, √δ) (tight for d ≫ √δ, √δ exactly at d = 0).
NUM_DELTA_REL = 2e-6
NUM_TOL_ABS = 1e-7


def coarse_lb_tile(qi, qscale, qeps, si, sscale, seps, *,
                   f32_dot: bool = False):
    """Certified per-pair lower bounds for one (query, S) code tile.

    qi (bm, dim) int8, qscale/qeps (bm,) f32; si (bn, dim) int8,
    sscale a scalar f32 (one tile — the kernel/scan form) or a (bn,)
    per-row vector (several tiles fused into one call — the dense
    oracle's form), seps (bn,) f32. Returns (bm, bn) float32
    ``max(d_coarse − ε_total, 0)`` — shared verbatim by the Pallas body,
    the dense jnp oracle and the engine's scan twin, so every impl keys
    its shortlist on the same certified bound.

    ``f32_dot`` computes the int8 contraction in float32 instead of
    int32. This is **exact, bit-for-bit the int32 path**, whenever
    ``dim · 127² < 2²⁴`` (every partial sum is an integer below the f32
    exact-integer ceiling, under any accumulation order) — the CPU refs
    use it because XLA lowers a float32 matmul to the fast BLAS gemm
    while an int8→int32 dot falls back to a naive loop. The Pallas TPU
    body keeps the int32 form: there the int8 MXU dot *is* the fast
    path. Callers asking for f32 beyond the exactness ceiling get the
    int32 form back silently (correctness over speed).
    """
    dim = qi.shape[1]
    if f32_dot and dim * 127 * 127 < 2 ** 24:
        qf = qi.astype(jnp.float32)
        sf = si.astype(jnp.float32)
        c = jax.lax.dot_general(qf, sf, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        af = jnp.sum(jnp.square(qf), axis=1)                   # (bm,)
        bf = jnp.sum(jnp.square(sf), axis=1)                   # (bn,)
    else:
        c = jax.lax.dot_general(qi, si, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.int32)
        a = jnp.sum(jnp.square(qi.astype(jnp.int32)), axis=1)  # (bm,)
        b = jnp.sum(jnp.square(si.astype(jnp.int32)), axis=1)  # (bn,)
        af = a.astype(jnp.float32)
        bf = b.astype(jnp.float32)
    q2 = (qscale * qscale) * af                                # ‖q̂‖²
    s2 = (sscale * sscale) * bf                                # ‖ŝ‖²  (bn,)
    d2 = (q2[:, None] + s2[None, :]
          - 2.0 * (qscale[:, None] * sscale) * c.astype(jnp.float32))
    dc = jnp.sqrt(jnp.maximum(d2, 0.0))
    delta = NUM_DELTA_REL * (q2[:, None] + s2[None, :])
    eps_num = delta / jnp.maximum(dc, jnp.sqrt(delta))
    eps_t = seps[None, :] + qeps[:, None] + eps_num + NUM_TOL_ABS
    return jnp.maximum(dc - eps_t, 0.0)


def quant_coarse_gather_kernel(
    # scalar-prefetch refs, then tensor refs:
    sched_ref, cnt_ref, qi_ref, qsc_ref, qeps_ref, th_ref,
    si_ref, ssc_ref, seps_ref, alive_ref, out_lb_ref, out_pos_ref,
    scratch_d, scratch_i,
    *, mp: int, bn: int, max_visits: int,
):
    """One (R tile, visit slot) step: int8 dot → int32 → rescale → fold
    the tile's certified lower bounds into the running sorted mp-run.

    ``si_ref``/``ssc_ref``/``seps_ref``/``alive_ref`` already hold the
    tile the schedule names for this slot (scalar-prefetch index maps),
    so pruned tiles cost zero bytes and zero FLOPs. ``alive`` is the
    *only* row mask — the quantizer's tile-padded layout must ship
    padding rows with ``alive == 0`` (the engine's liveness mask, built
    from ``gids >= 0``, already does).
    """
    i = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        scratch_d[...] = jnp.full_like(scratch_d, jnp.inf)
        scratch_i[...] = jnp.full_like(scratch_i, -1)

    @pl.when(j < cnt_ref[i])
    def _compute():
        tile = sched_ref[i, j]
        lb = coarse_lb_tile(
            qi_ref[...], qsc_ref[...][:, 0], qeps_ref[...][:, 0],
            si_ref[...], ssc_ref[0, 0], seps_ref[...][0])
        gid = tile * bn + jax.lax.broadcasted_iota(jnp.int32, (1, bn), 1)
        # liveness (covers tombstones AND tile padding) + the ε-inflated
        # θ prune (lb ≤ θ keeps every true neighbor: its lb lower-bounds
        # a distance that is ≤ θ)
        keep = (alive_ref[...] > 0.0) & (lb <= th_ref[...])
        lb = jnp.where(keep, lb, jnp.inf)
        td, ti = tile_topk(lb, jnp.broadcast_to(gid, lb.shape), mp)
        scratch_d[...], scratch_i[...] = merge_sorted_runs(
            scratch_d[...], scratch_i[...], td, ti)

    @pl.when(j == max_visits - 1)
    def _flush():
        lbr = scratch_d[...]
        out_lb_ref[...] = lbr
        out_pos_ref[...] = jnp.where(jnp.isfinite(lbr), scratch_i[...], -1)


def quant_coarse_gather_pallas(
    qi: jnp.ndarray,          # (n_r, dim) int8 query codes
    qscale: jnp.ndarray,      # (n_r,) f32
    qeps: jnp.ndarray,        # (n_r,) f32
    theta: jnp.ndarray,       # (n_r,) f32 — ε-inflatable prune threshold
    si: jnp.ndarray,          # (n_s, dim) int8 S codes (tile-padded)
    sscale: jnp.ndarray,      # (ns_tiles,) f32 per-tile scales
    seps: jnp.ndarray,        # (n_s,) f16/f32 per-row error bounds
    alive: jnp.ndarray,       # (n_s,) f32 liveness (>0 = live)
    mp: int,
    schedule: jnp.ndarray,    # (nr_tiles, max_visits) int32
    counts: jnp.ndarray,      # (nr_tiles,) int32
    *,
    bm: int = 128,
    bn: int = 512,
    interpret: bool = False,
):
    """Coarse int8 shortlist: ascending (lb (n_r, mp), pos (n_r, mp)).

    ``pos`` indexes rows of ``si`` (the packed multi-segment layout);
    slots that never saw a live candidate are (-1, +inf). ``mp`` must be
    a power of two. S-side operands must already be padded to whole
    ``bn`` tiles (the quantizer's layout).
    """
    from jax.experimental.pallas import tpu as pltpu

    n_r, d = qi.shape
    n_s = si.shape[0]
    nr_tiles = -(-n_r // bm)
    ns_tiles = n_s // bn
    if ns_tiles * bn != n_s:
        raise ValueError(f"quantized S must be tile-padded: {n_s} % {bn}")
    if schedule.shape[0] != nr_tiles:
        raise ValueError(
            f"schedule has {schedule.shape[0]} rows for {nr_tiles} R tiles")
    if mp & (mp - 1):
        raise ValueError(f"mp must be a power of two, got {mp}")
    max_visits = schedule.shape[1]

    pad_r = nr_tiles * bm - n_r
    qi_p = jnp.pad(qi, ((0, pad_r), (0, 0)))
    col = lambda x, fill: jnp.pad(                      # noqa: E731
        x.astype(jnp.float32), (0, pad_r),
        constant_values=fill).reshape(nr_tiles * bm, 1)
    # padding queries: θ = -inf schedules/keeps nothing
    qsc_p = col(qscale, 1.0)
    qeps_p = col(qeps, 0.0)
    th_p = col(theta, -jnp.inf)
    # per-tile operands as (ns_tiles, 1, ·): each scheduled block then
    # covers the array's last two dims whole, as Mosaic requires. ε is
    # widened to f32 here (exact from f16): Mosaic cannot load a
    # one-row block of a 16-bit array.
    ssc3 = sscale.astype(jnp.float32).reshape(ns_tiles, 1, 1)
    seps3 = seps.astype(jnp.float32).reshape(ns_tiles, 1, bn)
    alive3 = alive.astype(jnp.float32).reshape(ns_tiles, 1, bn)
    tile_spec = lambda w: pl.BlockSpec(                 # noqa: E731
        (None, 1, w), lambda i, j, sched, cnt: (sched[i, j], 0, 0))

    kernel = functools.partial(
        quant_coarse_gather_kernel, mp=mp, bn=bn, max_visits=max_visits)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(nr_tiles, max_visits),
        in_specs=[
            pl.BlockSpec((bm, d), lambda i, j, sched, cnt: (i, 0)),
            pl.BlockSpec((bm, 1), lambda i, j, sched, cnt: (i, 0)),
            pl.BlockSpec((bm, 1), lambda i, j, sched, cnt: (i, 0)),
            pl.BlockSpec((bm, 1), lambda i, j, sched, cnt: (i, 0)),
            pl.BlockSpec((bn, d), lambda i, j, sched, cnt: (sched[i, j], 0)),
            tile_spec(1),
            tile_spec(bn),
            tile_spec(bn),
        ],
        out_specs=[
            pl.BlockSpec((bm, mp), lambda i, j, sched, cnt: (i, 0)),
            pl.BlockSpec((bm, mp), lambda i, j, sched, cnt: (i, 0)),
        ],
        scratch_shapes=[
            pl_scratch((bm, mp), jnp.float32),
            pl_scratch((bm, mp), jnp.int32),
        ],
    )
    out_lb, out_pos = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((nr_tiles * bm, mp), jnp.float32),
            jax.ShapeDtypeStruct((nr_tiles * bm, mp), jnp.int32),
        ],
        interpret=interpret,
        name="quant_coarse_topk",
    )(schedule.astype(jnp.int32), counts.astype(jnp.int32),
      qi_p, qsc_p, qeps_p, th_p, si, ssc3, seps3, alive3)
    return out_lb[:n_r], out_pos[:n_r]
