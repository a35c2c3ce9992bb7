"""Sorted-run top-k primitives shared by the Pallas kernels and the XLA
scan reducer (core.distributed).

The paper's reducer keeps a priority queue per query (Algorithm 3, line
18). The previous TPU replacement was iterative extract-min — O(k·(k+t))
VPU work per (R tile, S tile) step with an argmin reduction per extracted
element. Here the running top-k is instead maintained as a *sorted run*:

* ``tile_topk``  — bitonic full sort of the tile's candidate columns
  (once per tile), then slice the smallest ``kp``;
* ``merge_sorted_runs`` — odd-even/bitonic merge of two ascending k-runs
  in log2(2k) compare-exchange stages.

Per tile the cost drops to O(t·log²t + k·log k) fully-vectorized
min/max/where ops. Everything below is expressed as jnp ops on a fixed
(bm, n) shape — no gather, no sort primitive, no data-dependent control
flow — so the same code runs inside a Mosaic kernel body, under
``interpret=True``, and in a plain ``jax.lax.scan`` on any backend.

Compare-exchange uses the XOR-partner formulation: the partner of lane
``x`` at distance ``dist`` is ``x ^ dist``, materialized with two lane
rolls and a select (roll lowers to slice+concatenate, which Mosaic
supports on the lane dimension). The run reversal a bitonic merge needs
is the same permutation composed over every bit (``x ^ (n-1)``), so no
``rev`` primitive — which Mosaic does not lower — appears anywhere.

Id payloads: every primitive accepts the id argument either as a single
int array or as a **tuple of arrays** permuted in lockstep with the
distances. The tuple form is how wide ids travel through the network —
jnp arrays are int32 under default JAX config, so a 64-bit row id is
carried as a (hi, lo) int32 pair (see ``core.stream.StreamJoinState``)
instead of being silently truncated.

Consumers: the Pallas tile kernels (`kernels.distance_topk`) fold each
tile through ``tile_topk`` + ``merge_sorted_runs`` in VMEM scratch; the
fused megastep (`core.megastep`) carries the same sorted run across a
*concatenated multi-segment* schedule — one scan/launch instead of one
per segment — and dedup-merges its carried device stream state with
``merge_sorted_runs_unique``; the host ``StreamJoinState`` uses the same
unique merge for revisited query slots.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["next_pow2", "bitonic_sort", "tile_topk", "merge_sorted_runs",
           "mask_duplicate_ids", "merge_sorted_runs_unique",
           "tree_merge_runs"]


def next_pow2(n: int) -> int:
    return 1 << max(0, (int(n) - 1).bit_length())


def _lane_iota(shape, ndim):
    return jax.lax.broadcasted_iota(jnp.int32, shape, ndim - 1)


def _as_tuple(i):
    return i if isinstance(i, tuple) else (i,)


def _like(i, parts):
    return parts if isinstance(i, tuple) else parts[0]


def _xor_lanes(x, dist: int):
    """``x`` with lane ``l`` moved to lane ``l ^ dist`` (``dist`` a power
    of two): two lane rolls and a select."""
    bitc = (_lane_iota(x.shape, x.ndim) & dist) == 0
    return jnp.where(bitc, jnp.roll(x, -dist, axis=-1),
                     jnp.roll(x, dist, axis=-1))


def _reverse_lanes(x):
    """``jnp.flip(x, -1)`` for a pow2 last axis, as log2(n) XOR-partner
    permutations (``l ^ (n-1)`` is the reversal)."""
    dist = 1
    while dist < x.shape[-1]:
        x = _xor_lanes(x, dist)
        dist *= 2
    return x


def _cmp_swap(d, i, dist: int, asc):
    """One compare-exchange stage over XOR-partners at ``dist`` lanes.

    ``asc`` is a bool array broadcastable against ``d`` giving the sort
    direction of each lane's enclosing bitonic block. Ties never swap, so
    duplicate distances keep their original ids. ``i`` is one id array or
    a tuple of id arrays permuted together.
    """
    bitc = (_lane_iota(d.shape, d.ndim) & dist) == 0
    p_d = _xor_lanes(d, dist)
    ids = _as_tuple(i)
    p_ids = tuple(_xor_lanes(x, dist) for x in ids)
    # lane keeps the min of its pair where its low/high position agrees
    # with the block's direction. Plain boolean algebra: Mosaic cannot
    # lower a select between boolean vectors.
    keep_min = bitc == asc
    take = (keep_min & (d > p_d)) | (~keep_min & (p_d > d))
    out = tuple(jnp.where(take, p, x) for p, x in zip(p_ids, ids))
    return jnp.where(take, p_d, d), _like(i, out)


def bitonic_sort(d, i):
    """Sort ``d`` ascending along the last axis, permuting ``i`` alongside.

    Last-axis length must be a power of two (pad with +inf first).
    Stages are unrolled at trace time: ½·log²n compare-exchanges.
    """
    n = d.shape[-1]
    assert n & (n - 1) == 0, f"bitonic_sort needs pow2 width, got {n}"
    log_n = n.bit_length() - 1
    lanes = _lane_iota(d.shape, d.ndim)
    for s in range(1, log_n + 1):
        asc = ((lanes >> s) & 1) == 0      # final stage: all ascending
        for dist in (1 << p for p in range(s - 1, -1, -1)):
            d, i = _cmp_swap(d, i, dist, asc)
    return d, i


def _pad_cols(d, i, width: int):
    pad = width - d.shape[-1]
    if pad <= 0:
        return d, i
    cfg = [(0, 0)] * (d.ndim - 1) + [(0, pad)]
    return (jnp.pad(d, cfg, constant_values=jnp.inf),
            jnp.pad(i, cfg, constant_values=-1))


def tile_topk(d, i, kp: int):
    """Smallest ``kp`` of each row as an ascending sorted run.

    ``kp`` must be a power of two; columns are +inf-padded up to a power
    of two if needed. Returns (bm, kp) distances/ids.
    """
    assert kp & (kp - 1) == 0, f"tile_topk needs pow2 kp, got {kp}"
    d, i = _pad_cols(d, i, max(next_pow2(d.shape[-1]), kp))
    d, i = bitonic_sort(d, i)
    return d[..., :kp], i[..., :kp]


def merge_sorted_runs(ad, ai, bd, bi):
    """Merge two ascending runs of equal pow2 length; keep the smallest.

    ``concat(A, reverse(B))`` is bitonic; its first half-cleaner stage
    (lane ``x`` against lane ``x + k``) leaves the smallest k in the low
    half, still bitonic, and log2(k) more stages sort it. Only that low
    half is computed: the lane-wise min of ``A`` and ``reverse(B)`` (ties
    keep A), then the in-half stages. Ids may be single arrays or
    matching tuples of arrays.
    """
    kp = ad.shape[-1]
    assert kp == bd.shape[-1] and kp & (kp - 1) == 0
    rd = _reverse_lanes(bd)
    take = ad > rd
    d = jnp.where(take, rd, ad)
    i = _like(ai, tuple(
        jnp.where(take, _reverse_lanes(b), a)
        for a, b in zip(_as_tuple(ai), _as_tuple(bi))))
    dist = kp // 2
    while dist >= 1:
        d, i = _cmp_swap(d, i, dist, True)
        dist //= 2
    return d, i


def mask_duplicate_ids(ad, ai, bd, bi):
    """Suppress B-run entries whose id already appears in the A run.

    An id that occurs in both runs references the same underlying row, so
    both copies carry the same distance in this codebase (every engine
    reports ``metrics.canonical_topk`` distances, a pure function of the
    (query, row) pair); A absorbs the elementwise-min of its duplicates'
    distances anyway so the smaller value survives even if a caller feeds
    diverging copies, and B's copy is demoted to (+inf, -1) so the merge
    can never return the same row twice. Padding lanes (id -1, +inf) are
    "duplicates" of each other by this rule, which is a no-op. O(k²)
    fully-vectorized compares — tuple ids match on every component.
    """
    ais, bis = _as_tuple(ai), _as_tuple(bi)
    eq = None
    for a, b in zip(ais, bis):
        e = a[..., :, None] == b[..., None, :]       # (..., ka, kb)
        eq = e if eq is None else eq & e
    ad = jnp.minimum(
        ad, jnp.min(jnp.where(eq, bd[..., None, :], jnp.inf), axis=-1))
    b_dup = jnp.any(eq, axis=-2)
    bd = jnp.where(b_dup, jnp.inf, bd)
    bis = tuple(jnp.where(b_dup, -1, x) for x in bis)
    return ad, ai, bd, _like(bi, bis)


def merge_sorted_runs_unique(ad, ai, bd, bi):
    """Top-k merge with id dedup: a row present in both runs (the same
    query slot revisited with overlapping candidate sets — the
    multi-segment / re-query-after-compaction path) contributes one
    entry, at its smaller distance, instead of occupying two top-k slots.

    Dedup masking punches +inf holes into the middle of the runs, so the
    bitonic precondition of the cheap odd-even merge no longer holds;
    the merged order is re-established with a full bitonic sort of the
    concatenation — ½·log²(2k) stages instead of log2(2k), paid only on
    the streaming-state path, never inside the tile kernels.
    """
    ad, ai, bd, bi = mask_duplicate_ids(ad, ai, bd, bi)
    kp = ad.shape[-1]
    assert kp == bd.shape[-1] and kp & (kp - 1) == 0
    d = jnp.concatenate([ad, bd], axis=-1)
    i = _like(ai, tuple(
        jnp.concatenate([a, b], axis=-1)
        for a, b in zip(_as_tuple(ai), _as_tuple(bi))))
    d, i = bitonic_sort(d, i)
    return d[..., :kp], _like(ai, tuple(
        x[..., :kp] for x in _as_tuple(i)))


def tree_merge_runs(runs, *, unique: bool = False):
    """Fold N ascending ``(d, ids)`` runs into one through a balanced
    pairwise merge tree — ⌈log2 N⌉ rounds of `merge_sorted_runs`.

    This is the sharded megastep's reduction (`core.sharded`): each mesh
    shard contributes its exact per-shard top-kp run, and because rows
    live on exactly one shard the runs are id-disjoint, so the cheap
    odd-even merge suffices — padding lanes (+inf, id −1) just sink to
    the tail. Pass ``unique=True`` when the runs may overlap (carried
    stream states); that routes each fold through the dedup merge
    instead. All runs must share the same pow2 width; ids may be single
    arrays or lockstep tuples.

    The fold is subset-stable: merging any non-empty *subset* of
    id-disjoint runs yields exactly the top-k restricted to that
    subset's rows (the degraded-coverage serving path merges only the
    surviving shards' runs — pinned by the property tests).
    """
    assert runs, "tree_merge_runs needs at least one run"
    widths = {int(d.shape[-1]) for d, _ in runs}
    if len(widths) != 1:
        raise ValueError(
            f"tree_merge_runs needs equal-width runs, got widths "
            f"{sorted(widths)} — pad every run to one pow2 width first")
    fold = merge_sorted_runs_unique if unique else merge_sorted_runs
    runs = list(runs)
    while len(runs) > 1:
        nxt = []
        for a in range(0, len(runs) - 1, 2):
            (ad, ai), (bd, bi) = runs[a], runs[a + 1]
            nxt.append(fold(ad, ai, bd, bi))
        if len(runs) % 2:
            nxt.append(runs[-1])
        runs = nxt
    return runs[0]
