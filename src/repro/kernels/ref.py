"""Pure-jnp oracles for every Pallas kernel (the allclose references)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["distance_topk_ref", "distance_topk_gather_ref",
           "quant_coarse_topk_ref", "quant_coarse_sched_ref",
           "assign_ref", "flash_attention_ref"]


def distance_topk_ref(r: jnp.ndarray, s: jnp.ndarray, k: int):
    """Exact k smallest L2 distances of each r row over s rows.

    Returns (dists (nr, k) ascending true distances, ids (nr, k) int32).
    """
    r = r.astype(jnp.float32)
    s = s.astype(jnp.float32)
    d2 = (jnp.sum(r * r, 1)[:, None] + jnp.sum(s * s, 1)[None, :]
          - 2.0 * jnp.matmul(r, s.T, precision=jax.lax.Precision.HIGHEST))
    d2 = jnp.maximum(d2, 0.0)
    neg, idx = jax.lax.top_k(-d2, k)
    return jnp.sqrt(-neg), idx.astype(jnp.int32)


def distance_topk_gather_ref(
    r: jnp.ndarray, s: jnp.ndarray, k: int,
    schedule: jnp.ndarray, counts: jnp.ndarray, *, bm: int, bn: int,
    alive: jnp.ndarray | None = None,
):
    """Oracle for the pruned-schedule kernel: mask unscheduled tiles.

    Computes the dense distance matrix, then restricts each R tile's
    candidate columns to the S tiles its schedule row names — the same
    candidate set ``distance_topk_gather_pallas`` ever sees. ``alive``
    (optional (n_s,) float32, >0 = live) additionally masks tombstoned /
    per-segment-padding rows, mirroring the kernel's megastep mask.
    """
    r = r.astype(jnp.float32)
    s = s.astype(jnp.float32)
    n_r, n_s = r.shape[0], s.shape[0]
    nr_tiles = -(-n_r // bm)
    ns_tiles = -(-n_s // bn)
    # (nr_tiles, ns_tiles) allowed mask from the compacted schedule
    slot = jnp.arange(schedule.shape[1])[None, :, None]          # (1, V, 1)
    hit = (schedule[:, :, None] == jnp.arange(ns_tiles)[None, None, :])
    allowed = jnp.any(hit & (slot < counts[:, None, None]), axis=1)
    row_tile = jnp.arange(n_r) // bm
    col_tile = jnp.arange(n_s) // bn
    mask = allowed[row_tile][:, col_tile]                        # (n_r, n_s)
    if alive is not None:
        mask = mask & (alive.astype(jnp.float32) > 0.0)[None, :]
    d2 = (jnp.sum(r * r, 1)[:, None] + jnp.sum(s * s, 1)[None, :]
          - 2.0 * jnp.matmul(r, s.T, precision=jax.lax.Precision.HIGHEST))
    d2 = jnp.where(mask, jnp.maximum(d2, 0.0), jnp.inf)
    neg, idx = jax.lax.top_k(-d2, k)
    return jnp.sqrt(-neg), idx.astype(jnp.int32)


def quant_coarse_topk_ref(
    qi: jnp.ndarray, qscale: jnp.ndarray, qeps: jnp.ndarray,
    theta: jnp.ndarray, si: jnp.ndarray, sscale: jnp.ndarray,
    seps: jnp.ndarray, alive: jnp.ndarray, mp: int, *, bn: int,
):
    """Oracle for the int8 coarse-scan kernel (`kernels.quant_topk`):
    dense certified-lower-bound matrix + top-mp selection.

    Same rescale formula (int8 dot → int32 → f32 rescale → ε-inflated
    lower bound, see `quant_topk.coarse_lb_tile`) over *all* S rows —
    a candidate superset of any schedule, which is fine: the quantized
    tier's exactness rests on the shortlist's re-rank + certification,
    not on which sound shortlist an impl picks. ``sscale`` is per tile
    ((n_s // bn,)); ``theta`` is the per-query ε-inflatable prune
    threshold; ``alive`` masks tombstones/padding. Returns ascending
    (lb (n, mp), pos (n, mp)); empty slots are (+inf, -1).
    """
    from .quant_topk import coarse_lb_tile

    # the kernel's exact bound formula over all tiles fused into one
    # call: coarse_lb_tile takes the per-tile scales as a per-row
    # vector, so the int8 contraction stays a single matmul. f32_dot:
    # bit-identical to the int32 form (exact-integer f32 sums) but hits
    # the BLAS gemm on CPU instead of a scalar int32 loop
    lb = coarse_lb_tile(
        qi, qscale, qeps, si,
        jnp.repeat(sscale.astype(jnp.float32), bn),
        seps.astype(jnp.float32), f32_dot=True)
    keep = (alive.astype(jnp.float32) > 0.0)[None, :] \
        & (lb <= theta[:, None])
    lb = jnp.where(keep, lb, jnp.inf)
    mp_eff = min(mp, lb.shape[-1])     # shortlist wider than S: take all
    neg, pos = jax.lax.top_k(-lb, mp_eff)
    lb_run = -neg
    pos = jnp.where(jnp.isfinite(lb_run), pos, -1).astype(jnp.int32)
    if mp_eff < mp:
        pad = ((0, 0), (0, mp - mp_eff))
        lb_run = jnp.pad(lb_run, pad, constant_values=jnp.inf)
        pos = jnp.pad(pos, pad, constant_values=-1)
    return lb_run, pos


def quant_coarse_sched_ref(
    qi: jnp.ndarray, qscale: jnp.ndarray, qeps: jnp.ndarray,
    theta: jnp.ndarray, si: jnp.ndarray, sscale: jnp.ndarray,
    seps: jnp.ndarray, alive: jnp.ndarray, mp: int,
    schedule: jnp.ndarray, counts: jnp.ndarray, *, bm: int, bn: int,
):
    """Schedule-driven scan twin of the int8 coarse kernel: the same
    visit list, the same per-tile `coarse_lb_tile` rescale, the same
    carried sorted mp-run — the CPU validation path for the quantized
    tier's in-jit schedule consumption (mirrors the fp32 megastep's
    ``ref_sched``). Query operands must already be padded to whole
    ``bm`` tiles (the engine's bucketing guarantees it)."""
    from .quant_topk import coarse_lb_tile
    from .sorted_merge import merge_sorted_runs, tile_topk

    n_r = qi.shape[0]
    nr_tiles = n_r // bm
    ns_tiles = si.shape[0] // bn
    dim = qi.shape[1]
    q3 = qi.reshape(nr_tiles, bm, dim)
    qs3 = qscale.reshape(nr_tiles, bm)
    qe3 = qeps.reshape(nr_tiles, bm)
    th3 = theta.reshape(nr_tiles, bm)
    s3 = si.reshape(ns_tiles, bn, dim)
    seps3 = seps.astype(jnp.float32).reshape(ns_tiles, bn)
    alive3 = alive.astype(jnp.float32).reshape(ns_tiles, bn)
    lb_of_tile = jax.vmap(
        lambda a, b, c, d, e, f: coarse_lb_tile(a, b, c, d, e, f,
                                                f32_dot=True))

    def body(carry, xs):
        cd, ci = carry
        tile_idx, j = xs                          # (nr_tiles,), ()
        lb = lb_of_tile(q3, qs3, qe3, s3[tile_idx],
                        sscale[tile_idx], seps3[tile_idx])
        pos = tile_idx[:, None] * bn + jnp.arange(bn)[None, :]
        keep = ((j < counts)[:, None, None]
                & (alive3[tile_idx][:, None, :] > 0.0)
                & (lb <= th3[..., None]))
        lb = jnp.where(keep, lb, jnp.inf)
        td, ti = tile_topk(
            lb, jnp.broadcast_to(pos[:, None, :], lb.shape), mp)
        return merge_sorted_runs(cd, ci, td, ti), None

    carry0 = (jnp.full((nr_tiles, bm, mp), jnp.inf, jnp.float32),
              jnp.full((nr_tiles, bm, mp), -1, jnp.int32))
    (cd, ci), _ = jax.lax.scan(
        body, carry0,
        (schedule.T, jnp.arange(schedule.shape[1], dtype=jnp.int32)))
    lb_run = cd.reshape(n_r, mp)
    pos = ci.reshape(n_r, mp)
    return lb_run, jnp.where(jnp.isfinite(lb_run), pos, -1)


def assign_ref(x: jnp.ndarray, pivots: jnp.ndarray):
    """Nearest pivot per row: (part_id int32, true distance f32)."""
    x = x.astype(jnp.float32)
    p = pivots.astype(jnp.float32)
    d2 = (jnp.sum(x * x, 1)[:, None] + jnp.sum(p * p, 1)[None, :]
          - 2.0 * jnp.matmul(x, p.T, precision=jax.lax.Precision.HIGHEST))
    d2 = jnp.maximum(d2, 0.0)
    pid = jnp.argmin(d2, axis=1).astype(jnp.int32)
    return pid, jnp.sqrt(jnp.take_along_axis(d2, pid[:, None], 1))[:, 0]


def flash_attention_ref(
    q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
    *, causal: bool = True, window: int | None = None,
    scale: float | None = None,
):
    """Reference attention. q (b, nq, h, d); k/v (b, nk, kvh, d).

    GQA: h must be a multiple of kvh; kv heads are repeated.
    ``window``: local attention — query i sees keys in (i-window, i].
    """
    b, nq, h, d = q.shape
    _, nk, kvh, _ = k.shape
    scale = scale if scale is not None else 1.0 / (d ** 0.5)
    rep = h // kvh
    k = jnp.repeat(k, rep, axis=2)
    v = jnp.repeat(v, rep, axis=2)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
    qi = jnp.arange(nq)[:, None] + (nk - nq)   # align to right edge (decode)
    ki = jnp.arange(nk)[None, :]
    mask = jnp.ones((nq, nk), bool)
    if causal:
        mask &= ki <= qi
    if window is not None:
        mask &= ki > qi - window
    logits = jnp.where(mask[None, None], logits, -jnp.inf)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs.astype(v.dtype), v)
    return out
