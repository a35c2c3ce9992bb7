"""Fused device-resident query megastep — one jitted pass per micro-batch.

The split planner (core.index) made per-batch planning cheap; this module
makes it *disappear from the host entirely*. One jitted function runs, per
R micro-batch and with no host round-trip in steady state:

1. **assign** — query→pivot distances + home partitions for every live
   index segment (shared with the schedule bounds);
2. **bounds** — a per-query kNN-radius θ from the union of all segments'
   T_S pivot-kNN lists (Thm 3 evaluated at the query), widened by the
   live tombstone count so masking dead rows can never starve the top-k;
3. **schedule** — Cor. 1 / Thm 2 lowered to jnp (`core.schedule.
   visit_mask_jnp`) per segment, concatenated over the segments' tile
   ranges and prefix-compacted with segment-sum ranks + a flat scatter
   (`compact_visits_jnp`) — same shapes every call, so it traces once;
4. **gather top-k** — the scalar-prefetch Pallas kernel
   (`kernels.distance_topk.distance_topk_gather_pallas`, alive-masked) on
   TPU, or its schedule-driven `lax.scan` twin here on CPU. The running
   per-query top-k is carried across the *whole concatenated schedule* in
   VMEM scratch (scan carry on CPU), so multi-segment fan-out is one
   launch and per-segment runs never round-trip through HBM;
5. **merge** — canonical distance recompute (`metrics.canonical_gathered`
   — bitwise the same graph the host path's `gathered_dist` runs),
   global-id mapping as (hi, lo) int32 pairs, the canonical stable
   re-sort, and optionally an odd-even dedup merge with a carried
   device-resident stream state (`kernels.sorted_merge.
   merge_sorted_runs_unique`).

Ragged batch sizes are padded to power-of-two buckets and the compiled
megastep is cached per (bucket, k, segment-structure) — jax.jit's cache
keyed by the static metadata — so steady-state serving never recompiles
and never re-plans: three identical ragged batches cost one trace
(`trace_count` lets tests pin this).

Exactness: the scheduled candidate set is a superset of the true live
top-k (θ is a sound union-level radius bound: the (k + dead)-th smallest
of the per-row upper bounds dominates the k-th nearest live row), the
selection over it is exact, and the reported distances are the canonical
per-pair values — so the megastep is bitwise-identical (distances and
int64 ids, up to float-tie ordering) to the host-planned reference path
it shadows. The host engines stay untouched as the oracle.
"""
from __future__ import annotations

import dataclasses
import functools
import threading
import time
from typing import Optional

import jax
import numpy as np

from repro import obs

from .metrics import canonical_gathered
from .schedule import compact_visits_jnp, visit_mask_jnp
from .types import JoinConfig, JoinStats

__all__ = ["MegastepEngine", "JoinHandle", "trace_count"]

_TRACE_COUNT = 0


def trace_count() -> int:
    """Number of megastep traces (== jit cache misses) this process has
    paid. Steady-state serving must not grow this — pinned by tests."""
    return _TRACE_COUNT


def _bump_trace() -> None:
    """Called from inside a jitted megastep body: runs at trace time only,
    so every execution after the first is invisible to `trace_count`.
    Shared with the quantized tier's fused megastep (repro.quant.engine)."""
    global _TRACE_COUNT
    _TRACE_COUNT += 1


def _next_pow2(n: int) -> int:
    return 1 << max(0, (int(n) - 1).bit_length())


# ---------------------------------------------------------------------------
# the jitted megastep


def _assign_bounds_schedule(q, n_valid, dead_total, segs, center, *,
                            k: int, bm: int, metric: str,
                            n_finite_total: int, seg_meta: tuple,
                            primary: int):
    """Stages 1–3 of the megastep (assign → union θ → compacted tile
    schedule), shared — called inside a jit — by the fp32 megastep and
    the quantized tier's coarse pass (`repro.quant.engine`), so both
    consume the identical schedule/θ graph.

    Returns ``(qs, qcs, valid_s, perm, inv, th_q, sched, cnt)``: the
    home-partition-sorted queries (raw and center-relative), their
    validity mask, the sort permutation and its inverse, the per-query
    θ (−inf on padding rows), and the compacted concatenated visit
    schedule with its per-R-tile counts.
    """
    import jax.numpy as jnp

    b = q.shape[0]

    # ---- 1. assignment against every segment's pivots (shared with the
    # schedule bounds: the same (B, M) distance matrix feeds both)
    with jax.named_scope("assign"):
        valid_q = jnp.arange(b) < n_valid
        qc = q - center[None, :]
        qps, homes = [], []
        for g, (m, kk, _) in enumerate(seg_meta):
            pc = segs[g]["pivots_c"]
            d2 = (jnp.sum(qc * qc, 1)[:, None]
                  + jnp.sum(pc * pc, 1)[None, :]
                  - 2.0 * jax.lax.dot_general(
                      qc, pc, (((1,), (1,)), ((), ())),
                      precision=jax.lax.Precision.HIGHEST,
                      preferred_element_type=jnp.float32))
            d2 = jnp.maximum(d2, 0.0)
            qps.append(jnp.sqrt(d2))
            homes.append(jnp.argmin(d2, axis=1).astype(jnp.int32))

        # sort queries by the primary (largest) segment's home partition
        # so R tiles are partition-coherent — the layout the tile bounds
        # bite on; padding rows sort last. Undone on the way out via
        # ``inv``.
        m_primary = seg_meta[primary][0]
        sort_key = jnp.where(valid_q, homes[primary], m_primary)
        perm = jnp.argsort(sort_key, stable=True)
        inv = jnp.argsort(perm)
        qs = q[perm]
        qcs = qc[perm]
        valid_s = valid_q[perm]
        qps = [qp[perm] for qp in qps]
        homes = [h[perm] for h in homes]

    # ---- 2. union θ: k-th (+ dead widening) smallest upper bound over
    # every segment's pivot-kNN candidates (Thm 3 at the query, exact for
    # the union top-k; see module docstring)
    with jax.named_scope("bounds"):
        ubs = [(qps[g][:, :, None] + segs[g]["knn"][None, :, :kk]
                ).reshape(b, m * kk)
               for g, (m, kk, _) in enumerate(seg_meta)]
        ub = jnp.concatenate(ubs, axis=1)
        c_total = ub.shape[1]
        # capped order statistic instead of a full sort (XLA sort is the
        # slow op here): bounds for up to w_cap − k tombstones stay
        # tight, beyond that θ degrades to +inf (visit everything — still
        # exact; compaction is overdue anyway at that point)
        w_cap = min(c_total, max(2 * k, 64))
        small = -jax.lax.top_k(-ub, w_cap)[0]        # ascending smallest
        dead = jnp.maximum(dead_total.astype(jnp.int32), 0)
        j = k - 1 + dead
        idx = jnp.broadcast_to(jnp.minimum(j, w_cap - 1), (b, 1))
        th = jnp.take_along_axis(small, idx, axis=1)[:, 0]
        fits = ((k + dead) <= n_finite_total) & (j < w_cap)
        th = jnp.where(fits, th, jnp.inf)      # no valid bound: visit all
        th_q = jnp.where(valid_s, th, -jnp.inf)  # padding: schedule none

    # ---- 3. per-segment visit masks, concatenated + prefix-compacted
    with jax.named_scope("schedule"):
        visits = [visit_mask_jnp(qps[g], homes[g], th_q, valid_s,
                                 segs[g]["pivd"], segs[g]["sd_min"],
                                 segs[g]["sd_max"], segs[g]["present"],
                                 bm=bm, metric=metric)
                  for g in range(len(seg_meta))]
        sched, cnt = compact_visits_jnp(jnp.concatenate(visits, axis=1))
    return qs, qcs, valid_s, perm, inv, th_q, sched, cnt


def _gather_topk_run(qs, qcs, valid_s, sched, cnt, tiles, *,
                     k: int, bm: int, bn: int, metric: str, dim: int,
                     impl: str):
    """Stage 4 of the megastep: gather-top-kp over the (possibly
    per-shard) compacted schedule. Factored out of `_megastep` so the
    sharded engine (`core.sharded`) can run the identical graph inside a
    ``shard_map`` body against one shard's tiles. The run keeps
    kp ≥ k candidates so the canonical re-rank resolves the rank-k
    boundary with exact distances, not the selection metric's fp noise.

    Returns ``(d_run, pos, valid_sel, merged)``: the ascending
    selection-metric run, packed-row positions (−1 = empty slot), the
    validity mask, and the (nr_tiles,) int32 count of each R tile's
    visited steps that merged a tile (the kernel's gate,
    `kernels.distance_topk._merge_tile`; None for the dense "ref", which
    walks no schedule). Padding rows start their run at −inf, so they
    never hold the gate open and their entries fail ``valid_sel``.
    """
    import jax.numpy as jnp

    from repro.kernels.sorted_merge import merge_sorted_runs, next_pow2

    b = qs.shape[0]
    nr_tiles = b // bm
    kp = next_pow2(k)
    center = tiles["center"]
    t_total = sched.shape[1]

    if impl in ("pallas", "pallas_interpret"):
        from repro.kernels.distance_topk import distance_topk_gather_pallas
        d_run, pos, merged = distance_topk_gather_pallas(
            qs, tiles["s"], kp, sched, cnt, alive=tiles["alive"],
            n_valid=jnp.sum(valid_s, dtype=jnp.int32),
            bm=bm, bn=bn, interpret=impl == "pallas_interpret")
        valid_sel = (pos >= 0) & jnp.isfinite(d_run)
    elif impl == "ref_sched":
        # schedule-driven scan twin of the Pallas kernel: same visit
        # list, same carried sorted run — the CPU validation path for
        # the in-jit schedule consumption
        s_tiles = tiles["s"].reshape(t_total, bn, dim)
        alive_t = tiles["alive"].reshape(t_total, bn)
        q3 = qcs.reshape(nr_tiles, bm, dim)
        q3n = jnp.sum(q3 * q3, axis=-1)
        kt = min(kp, bn)

        def body(carry, xs):
            cd, ci, merged = carry
            tile_idx, j = xs
            st = s_tiles[tile_idx] - center[None, None, :]
            al = alive_t[tile_idx]                       # (nr_tiles, bn)
            d2 = (q3n[..., None] + jnp.sum(st * st, -1)[:, None, :]
                  - 2.0 * jnp.einsum("abd,acd->abc", q3, st,
                                     precision=jax.lax.Precision.HIGHEST))
            d2 = jnp.maximum(d2, 0.0)
            live = ((j < cnt)[:, None, None]) & (al[:, None, :] > 0.0)
            d2 = jnp.where(live, d2, jnp.inf)
            pos_row = tile_idx[:, None] * bn + jnp.arange(bn)[None, :]
            neg, ii = jax.lax.top_k(-d2, kt)
            td = -neg
            ti = jnp.take_along_axis(
                jnp.broadcast_to(pos_row[:, None, :], d2.shape), ii, axis=2)
            if kt < kp:
                padc = [(0, 0)] * 2 + [(0, kp - kt)]
                td = jnp.pad(td, padc, constant_values=jnp.inf)
                ti = jnp.pad(ti, padc, constant_values=-1)
            # the kernel's gate: a step merges where some candidate is
            # strictly under its row's running kp-th distance
            beats = jnp.any(d2 < cd[..., kp - 1:kp], axis=(1, 2))
            cd, ci = merge_sorted_runs(cd, ci, td, ti)
            return (cd, ci, merged + beats.astype(jnp.int32)), None

        # padding rows start at −inf, as in the kernel (`_start_run`)
        run0 = jnp.where(valid_s, jnp.inf, -jnp.inf).astype(jnp.float32)
        carry0 = (jnp.broadcast_to(run0.reshape(nr_tiles, bm, 1),
                                   (nr_tiles, bm, kp)),
                  jnp.full((nr_tiles, bm, kp), -1, jnp.int32),
                  jnp.zeros((nr_tiles,), jnp.int32))
        (cd, ci, merged), _ = jax.lax.scan(
            body, carry0,
            (sched.T, jnp.arange(t_total, dtype=jnp.int32)))
        d_run = cd.reshape(b, kp)
        pos = ci.reshape(b, kp)
        valid_sel = (pos >= 0) & jnp.isfinite(d_run)
    else:
        # "ref": dense alive-masked selection — one gemm + one top_k. On
        # CPU the scan/kernel's per-slot pruning cannot elide FLOPs (the
        # schedule width is static), so the dense form is strictly
        # faster; XLA dead-code-eliminates the unused schedule here. The
        # TPU path and ref_sched consume it for real.
        sc = tiles["s"] - center[None, :]
        d2 = (jnp.sum(qcs * qcs, 1)[:, None] + jnp.sum(sc * sc, 1)[None, :]
              - 2.0 * jax.lax.dot_general(
                  qcs, sc, (((1,), (1,)), ((), ())),
                  precision=jax.lax.Precision.HIGHEST,
                  preferred_element_type=jnp.float32))
        d2 = jnp.where(tiles["alive"][None, :] > 0.0,
                       jnp.maximum(d2, 0.0), jnp.inf)
        neg, pos = jax.lax.top_k(-d2, kp)
        d_run = -neg
        valid_sel = (pos >= 0) & jnp.isfinite(d_run)
        merged = None
    return d_run, pos, valid_sel, merged


def _canonical_runs(qs, tiles, pos, valid_sel, metric: str, take: int):
    """Stage-5 head of the megastep: canonical distance recompute over
    the gathered kp-run + global-id mapping + the stable exact re-sort,
    keeping the best ``take`` columns as an ascending sorted run.
    ``take=k`` is the single-device output; the sharded engine keeps the
    full ``take=kp`` run so the in-mesh tree merge sees every column.
    Returns ``(d_can, hi, lo)`` in schedule-sorted query order."""
    import jax.numpy as jnp

    pos_c = jnp.clip(pos, 0, tiles["s"].shape[0] - 1)
    neigh = tiles["s"][pos_c]                               # (b, kp, dim)
    d_can = canonical_gathered(qs, neigh, metric)
    d_can = jnp.where(valid_sel, d_can, jnp.inf)
    hi = jnp.where(valid_sel, tiles["id_hi"][pos_c], -1)
    lo = jnp.where(valid_sel, tiles["id_lo"][pos_c], -1)
    order = jnp.argsort(d_can, axis=1, stable=True)
    d_can = jnp.take_along_axis(d_can, order, axis=1)[:, :take]
    hi = jnp.take_along_axis(hi, order, axis=1)[:, :take]
    lo = jnp.take_along_axis(lo, order, axis=1)[:, :take]
    return d_can, hi, lo


@functools.partial(
    jax.jit,
    static_argnames=("k", "bm", "bn", "metric", "dim", "n_finite_total",
                     "seg_meta", "primary", "impl"))
def _megastep(q, n_valid, dead_total, segs, tiles, state, *,
              k: int, bm: int, bn: int, metric: str, dim: int,
              n_finite_total: int, seg_meta: tuple, primary: int,
              impl: str):
    """assign → bounds → schedule → gather-top-k → merge, one trace.

    ``q`` (B, dim) bucket-padded queries; ``n_valid`` traced scalar;
    ``dead_total`` traced tombstone count; ``segs`` a tuple of per-segment
    device dicts; ``tiles`` the concatenated device S-side; ``state`` an
    optional carried (d, id_hi, id_lo) device run to dedup-merge into.
    ``seg_meta`` is the static per-segment (M, kk, ns_tiles) signature —
    part of the jit cache key, so a changed segment structure retraces
    while steady-state batches hit the cache.
    """
    _bump_trace()              # runs at trace time only == jit cache miss

    import jax.numpy as jnp

    from repro.kernels.sorted_merge import merge_sorted_runs_unique, \
        next_pow2

    kp = next_pow2(k)
    center = tiles["center"]
    qs, qcs, valid_s, perm, inv, th_q, sched, cnt = _assign_bounds_schedule(
        q, n_valid, dead_total, segs, center, k=k, bm=bm, metric=metric,
        n_finite_total=n_finite_total, seg_meta=seg_meta, primary=primary)

    with jax.named_scope("gather_topk"):
        d_run, pos, valid_sel, _ = _gather_topk_run(
            qs, qcs, valid_s, sched, cnt, tiles, k=k, bm=bm, bn=bn,
            metric=metric, dim=dim, impl=impl)

    # ---- 5. canonical distances + global ids + stable re-sort (the
    # exact re-rank over the kp-run) + optional carried-state merge
    with jax.named_scope("canonical"):
        d_can, hi, lo = _canonical_runs(qs, tiles, pos, valid_sel, metric,
                                        k)
        d_can, hi, lo = d_can[inv], hi[inv], lo[inv]

    if state is not None:
        with jax.named_scope("merge"):
            sd, shi, slo = state
            pad = ((0, 0), (0, kp - k))
            md, (mhi, mlo) = merge_sorted_runs_unique(
                jnp.pad(sd, pad, constant_values=jnp.inf),
                (jnp.pad(shi, pad, constant_values=-1),
                 jnp.pad(slo, pad, constant_values=-1)),
                jnp.pad(d_can, pad, constant_values=jnp.inf),
                (jnp.pad(hi, pad, constant_values=-1),
                 jnp.pad(lo, pad, constant_values=-1)))
            d_can, hi, lo = md[:, :k], mhi[:, :k], mlo[:, :k]
    return d_can, hi, lo


@functools.partial(
    jax.jit,
    static_argnames=("k", "bm", "metric", "n_finite_total", "seg_meta",
                     "primary"))
def _visit_count(q, n_valid, dead_total, segs, center, **kw):
    """Scheduled (R tile, S tile) visits of stages 1–3, summed."""
    import jax.numpy as jnp
    cnt = _assign_bounds_schedule(q, n_valid, dead_total, segs, center,
                                  **kw)[-1]
    return jnp.sum(cnt)


@functools.partial(
    jax.jit,
    static_argnames=("k", "bm", "bn", "metric", "dim", "n_finite_total",
                     "seg_meta", "primary", "impl"))
def _gather_count(q, n_valid, dead_total, segs, tiles, *, k: int, bm: int,
                  bn: int, metric: str, dim: int, impl: str, **kw):
    """Stages 1–4 of the megastep: ``(merged, visited)`` steps, summed
    over the batch's R tiles."""
    import jax.numpy as jnp
    qs, qcs, valid_s, _, _, _, sched, cnt = _assign_bounds_schedule(
        q, n_valid, dead_total, segs, tiles["center"], k=k, bm=bm,
        metric=metric, **kw)
    merged = _gather_topk_run(qs, qcs, valid_s, sched, cnt, tiles, k=k,
                              bm=bm, bn=bn, metric=metric, dim=dim,
                              impl=impl)[-1]
    return jnp.sum(merged), jnp.sum(cnt)


# ---------------------------------------------------------------------------
# device-resident index payload


@dataclasses.dataclass
class _Payload:
    """Everything the jitted megastep consumes, already on device."""

    segs: tuple           # per-segment dicts of jnp arrays
    tiles: dict           # concatenated: s, alive, id_hi, id_lo, center
    dead_total: object    # () int32 device scalar
    n_dead: int           # the same tombstone count, host-side
    seg_meta: tuple       # static ((M, kk, ns_tiles), ...)
    dim: int
    n_finite_total: int
    primary: int


@dataclasses.dataclass
class JoinHandle:
    """An in-flight batch: device futures from :meth:`MegastepEngine.
    dispatch`, redeemed by :meth:`MegastepEngine.finalize`.

    JAX dispatches jitted calls asynchronously (on CPU too), so the
    arrays in ``dev`` are futures — the device computes while the host
    does other work (the serving scheduler's double-buffered dispatch
    overlaps batch N's finalize with batch N+1's dispatch through exactly
    this split). ``kind`` routes finalize: the fp32 megastep ("mega"),
    the quantized tier's fused resident path ("quant_resident") and its
    low-memory host-gather fallback ("quant_host"); ``q`` keeps the
    original query rows only where finalize may need a host-side
    fallback re-run (the quantized certification paths).
    """

    kind: str
    n: int
    dev: tuple = ()
    q: Optional[np.ndarray] = None


def _in_sorted(ids: np.ndarray, sorted_ids: np.ndarray) -> np.ndarray:
    if sorted_ids.size == 0:
        return np.zeros(ids.shape, bool)
    pos = np.clip(np.searchsorted(sorted_ids, ids), 0, sorted_ids.size - 1)
    return sorted_ids[pos] == ids


class MegastepEngine:
    """Bucketed, compile-cached driver of the fused query megastep.

    Holds the index's device-resident artifacts (packed rows, per-tile
    Thm-2 stats, pivot geometry, pivot-kNN lists, liveness mask) and
    re-uploads them only when the index version changes; every
    ``join_batch`` in between is one upload (the queries), one jitted
    call, one fetch. Accepts a build-once ``SIndex`` or a mutable
    segmented ``core.segments.MutableIndex`` — all live segments
    (including the unsealed write buffer, viewed through an ephemeral
    delta index) fan through a single concatenated-schedule kernel
    launch. L2 only: the megastep's fused bound math is the Euclidean
    Cor. 1 / Thm 2 lowering; other metrics stay on the host engines.

    Cost model: a mutation (insert/seal/delete/compact) bumps the index
    version, and the next batch pays a host-side payload rebuild +
    re-upload (O(|S|) concat; per-segment geometry is cached, so only
    changed segments recompute). Insert-heavy streams should size
    ``seal_threshold`` so queries between mutations amortize the
    refresh — the steady state between mutations transfers nothing.
    """

    # the join_batch path splits into an async dispatch() + finalize()
    # pair — what StreamJoinEngine.can_dispatch and the serving
    # scheduler's double-buffered mode key on
    can_dispatch = True

    def __init__(self, index, config: Optional[JoinConfig] = None, *,
                 bucket_min: int = 16, impl: Optional[str] = None):
        self.index = index
        self.config = config or index.config
        if self.config.metric != "l2":
            raise ValueError(
                f"megastep supports metric='l2' only, got "
                f"{self.config.metric!r}; use the host-planned engines")
        if impl not in (None, "pallas", "pallas_interpret", "ref",
                        "ref_sched"):
            raise ValueError(f"unknown megastep impl {impl!r}")
        self.impl = impl           # None = auto (pallas on TPU, ref here)
        self.bucket_min = max(1, int(bucket_min))
        # tile shapes actually used on device. Defaults follow the config;
        # the quantized tier overrides them from its measured tuning table
        # (repro.quant.autotune) after super().__init__.
        self._bn = int(self.config.tile_s)
        self._bm_cap = 1 << (int(self.config.tile_r).bit_length() - 1)
        # the quantized subclass (repro.quant.engine) keeps the fp32 rows
        # host-side and uploads int8 codes instead — 4× less HBM resident
        # — and resolves global ids host-side, so it skips the (hi, lo)
        # id upload too
        self._upload_fp32 = True
        self._upload_ids = True
        self._struct = None        # (skey, struct dict)
        self._payload = None       # (vkey, _Payload)
        self._seg_cache: dict = {}
        # payload rebuilds read multi-field index state (segments,
        # tombstones, version); a mutation racing that read could cache
        # a torn payload under a *valid* version key. Owners that mutate
        # the index concurrently (serve.Datastore) point this at the
        # same lock their mutations hold, making rebuild and mutation
        # mutually exclusive. Reentrant so an owner already holding it
        # can query.
        self.refresh_lock: threading.RLock = threading.RLock()

    @property
    def resolved_impl(self) -> str:
        """The step's top-k implementation: the one forced at
        construction, else the compiled Pallas kernel on TPU and the
        dense jnp reference elsewhere."""
        from repro.kernels import ops
        return self.impl or ("pallas" if ops.use_pallas() else "ref")

    # ---- bucketing

    def bucket_for(self, n: int) -> int:
        return _next_pow2(max(self.bucket_min, n))

    # ---- device payload lifecycle

    def _index_parts(self):
        from .segments import MutableIndex
        if isinstance(self.index, MutableIndex):
            segs = [(si, off) for si, off in self.index.segment_snapshot()
                    if si.n_s > 0]
            return (segs, self.index.tombstones_sorted(),
                    ("mut", id(self.index), self.index.version))
        return ([(self.index, 0)], np.zeros((0,), np.int64),
                ("static", id(self.index)))

    def _refresh(self) -> _Payload:
        import jax.numpy as jnp

        from repro.serve import faultinject

        with self.refresh_lock:
            segs, tomb, vkey = self._index_parts()
            vkey = self._payload_key(vkey)
            if self._payload is not None and self._payload[0] == vkey:
                return self._payload[1]
            if not segs:
                raise ValueError("megastep over an empty index")
            with obs.span("megastep.refresh", n_segments=len(segs),
                          n_tombstones=int(tomb.size)):
                obs.metrics.REGISTRY.counter(
                    "megastep_payload_refresh_total").inc()
                # fault hook: a failure here simulates a device OOM on
                # the payload (re)upload — nothing is cached, the next
                # call rebuilds from scratch
                faultinject.fire("megastep.payload_upload")
                bn = self._bn
                k = self.config.k
                skey = (tuple(id(si) for si, _ in segs), bn, k)
                if self._struct is None or self._struct[0] != skey:
                    self._struct = (skey, self._build_struct(segs, bn, k))
                st = self._struct[1]
                # liveness + tombstone count change per index version;
                # the rows, geometry and tile stats above change only
                # with the structure
                alive = self._alive_mask(st, tomb)
                payload = _Payload(
                    segs=self._segs_for_view(st),
                    tiles=dict(st["tiles_dev"],
                               alive=self._put_alive(alive)),
                    dead_total=self._put_rep(np.int32(tomb.size)),
                    n_dead=int(tomb.size),
                    seg_meta=st["seg_meta"], dim=st["dim"],
                    n_finite_total=st["n_finite_total"],
                    primary=st["primary"])
                self._payload = (vkey, payload)
                return payload

    # serving-view hooks: the sharded engines (core.sharded) key the
    # cached payload on shard health, mask rows not served under the
    # current owner view, and gate per-shard `present` to owned
    # partitions. The single-device engine has exactly one view.

    def _payload_key(self, vkey):
        return vkey

    def _alive_mask(self, st, tomb) -> np.ndarray:
        return (st["gids"] >= 0) & ~_in_sorted(st["gids"], tomb)

    def _segs_for_view(self, st):
        return st["segs_dev"]

    # device-placement hooks: the single-device engine just uploads; the
    # sharded engine (core.sharded) overrides these with mesh shardings
    # so liveness lands shard-partitioned and scalars land replicated

    def _put_alive(self, alive: np.ndarray):
        import jax.numpy as jnp
        return jnp.asarray(alive.astype(np.float32))

    def _put_rep(self, x):
        import jax.numpy as jnp
        return jnp.asarray(x)

    def _build_struct(self, segs, bn: int, k: int) -> dict:
        import jax.numpy as jnp

        live_ids = set(id(si) for si, _ in segs)
        self._seg_cache = {key: v for key, v in self._seg_cache.items()
                           if key[0] in live_ids}
        dim = segs[0][0].dim
        rows_parts, gid_parts = [], []
        seg_meta, segs_dev = [], []
        n_finite_total = 0
        sizes = []
        for si, off in segs:
            key = (id(si), bn)
            ent = self._seg_cache.get(key)
            if ent is None:
                ns_tiles = max(1, -(-si.n_s // bn))
                pad = ns_tiles * bn - si.n_s
                rows = np.pad(si.s_sorted, ((0, pad), (0, 0)))
                gids_local = np.pad(si.s_ids_sorted, (0, pad),
                                    constant_values=-1)
                sd_min, sd_max, present = si.tile_stats(bn)
                ent = dict(
                    si=si, ns_tiles=ns_tiles, rows=rows,
                    gids_local=gids_local, pivots=si.pivots,
                    knn_np=si.t_s.knn_dists,
                    pivd=jnp.asarray(si.pivd.astype(np.float32)),
                    knn=jnp.asarray(si.t_s.knn_dists.astype(np.float32)),
                    sd_min=jnp.asarray(sd_min), sd_max=jnp.asarray(sd_max),
                    present=jnp.asarray(present))
                self._seg_cache[key] = ent
            kk = min(k, ent["knn_np"].shape[1])
            n_finite = int(np.isfinite(ent["knn_np"][:, :kk]).sum())
            n_finite_total += n_finite
            seg_meta.append((si.n_pivots, kk, ent["ns_tiles"]))
            rows_parts.append(ent["rows"])
            gid_parts.append(np.where(ent["gids_local"] >= 0,
                                      ent["gids_local"] + off, -1))
            sizes.append(si.n_s)
        rows_all = np.concatenate(rows_parts, axis=0)
        gids = np.concatenate(gid_parts)
        # one shared center for the selection math: distances stay
        # comparable across segments and the ‖x‖²·eps cancellation noise
        # shrinks to O(spread²·eps) (see metrics.cmp_dist)
        n_real = sum(sizes)
        center = (rows_all[gids >= 0].mean(axis=0, dtype=np.float64)
                  .astype(np.float32) if n_real else
                  np.zeros((dim,), np.float32))
        for si, off in segs:
            ent = self._seg_cache[(id(si), bn)]
            segs_dev.append(dict(
                pivots_c=jnp.asarray(ent["pivots"] - center[None, :]),
                pivd=ent["pivd"], knn=ent["knn"], sd_min=ent["sd_min"],
                sd_max=ent["sd_max"], present=ent["present"]))
        tiles_dev = dict(center=jnp.asarray(center))
        if self._upload_ids:
            hi = (gids >> 32).astype(np.int32)
            lo = (gids & np.int64(0xFFFFFFFF)).astype(np.uint32) \
                .view(np.int32)
            tiles_dev["id_hi"] = jnp.asarray(hi)
            tiles_dev["id_lo"] = jnp.asarray(lo)
        if self._upload_fp32:
            tiles_dev["s"] = jnp.asarray(rows_all)
        return dict(
            segs_dev=tuple(segs_dev),
            tiles_dev=tiles_dev,
            # the packed fp32 rows, host-side: only the quantized tier
            # needs them (its exact re-rank gathers shortlists from here
            # instead of HBM) — the fp32 engine must not pin a second
            # full host copy of the index
            rows_host=None if self._upload_fp32 else rows_all,
            gids=gids, seg_meta=tuple(seg_meta), dim=dim,
            n_finite_total=n_finite_total,
            primary=int(np.argmax(sizes)))

    # ---- query API

    def enqueue(self, queries: np.ndarray):
        """Pad one micro-batch to its bucket and upload: returns device
        ``(q, n_valid)`` ready for :meth:`join_batch_device`. This is the
        only host→device transfer a steady-state batch performs."""
        q = np.ascontiguousarray(queries, np.float32)
        import jax.numpy as jnp
        n = q.shape[0]
        bucket = self.bucket_for(n)
        with obs.span("megastep.enqueue", rows=n, bucket=bucket):
            if bucket != n:
                q = np.pad(q, ((0, bucket - n), (0, 0)))
            return jnp.asarray(q), jnp.asarray(np.int32(n))

    def join_batch_device(self, q_dev, n_valid_dev, *, state=None):
        """The zero-host-transfer steady-state call: device-padded
        queries in, device ``(dists, id_hi, id_lo)`` out — one jitted
        megastep, nothing fetched, nothing re-uploaded (the index payload
        is already resident; refresh only re-uploads after a mutation).
        ``state`` optionally carries a previous (dists, id_hi, id_lo) run
        for the same query slots; it is dedup-merged on device.
        """
        payload = self._refresh()
        bucket = int(q_dev.shape[0])
        # largest power of two <= tile_r, so pow2 buckets always reshape
        bm = min(bucket, self._bm_cap)
        impl = self.resolved_impl
        # span timing = host launch bracket of the one fused call, with
        # host-known attrs only — nothing here fetches or blocks on the
        # device (the zero-steady-state-sync invariant); the stages inside
        # are named scopes of the device program
        with obs.span("megastep.device_step", bucket=bucket, bm=bm,
                      bn=self._bn, k=self.config.k, impl=impl,
                      n_segments=len(payload.seg_meta)) as sp:
            out = _megastep(
                q_dev, n_valid_dev, payload.dead_total, payload.segs,
                payload.tiles, state,
                k=self.config.k, bm=bm, bn=self._bn,
                metric=self.config.metric, dim=payload.dim,
                n_finite_total=payload.n_finite_total,
                seg_meta=payload.seg_meta, primary=payload.primary,
                impl=impl)
            sp.set(outcome="launched")
            return out

    def tile_counts(self, queries: np.ndarray) -> tuple[int, int]:
        """``(visited, total)`` (R tile, S tile) pairs in the schedule
        the megastep builds for one batch — the paper's pruning count.
        The fused step keeps its schedule on the device and reports no
        counts (that would cost a sync per batch), so this runs stages
        1–3 alone and fetches one scalar."""
        q = self._validated_queries(queries)
        payload = self._refresh()
        qd, nv = self.enqueue(q)
        bm = min(int(qd.shape[0]), self._bm_cap)
        visited = _visit_count(
            qd, nv, payload.dead_total, payload.segs,
            payload.tiles["center"], k=self.config.k, bm=bm,
            metric=self.config.metric,
            n_finite_total=payload.n_finite_total,
            seg_meta=payload.seg_meta, primary=payload.primary)
        total = -(-q.shape[0] // bm) * sum(t for _, _, t in payload.seg_meta)
        return int(visited), total

    def gather_counts(self, queries: np.ndarray) -> tuple[int, int]:
        """``(merged, visited)`` (R tile, S tile) steps of the gather
        top-k for one batch: the visited steps of its schedule, and those
        in which some row had a candidate strictly under its running
        kp-th distance, so the kernel sorted and merged the tile (the
        others leave the run as it was). Like :meth:`tile_counts` it runs
        a program of its own and fetches two scalars, on demand only.
        The dense "ref" walks no schedule; it counts with "ref_sched"."""
        q = self._validated_queries(queries)
        payload = self._refresh()
        qd, nv = self.enqueue(q)
        bm = min(int(qd.shape[0]), self._bm_cap)
        impl = self.resolved_impl
        merged, visited = _gather_count(
            qd, nv, payload.dead_total, payload.segs, payload.tiles,
            k=self.config.k, bm=bm, bn=self._bn, metric=self.config.metric,
            dim=payload.dim, n_finite_total=payload.n_finite_total,
            seg_meta=payload.seg_meta, primary=payload.primary,
            impl="ref_sched" if impl == "ref" else impl)
        return int(merged), int(visited)

    def _validated_queries(self, queries: np.ndarray):
        q = np.ascontiguousarray(queries, np.float32)
        if self.config.k > self.index.n_s:
            raise ValueError(f"k={self.config.k} > |S|={self.index.n_s}")
        return q

    def dispatch(self, queries: np.ndarray, *,
                 stats: Optional[JoinStats] = None) -> JoinHandle:
        """The async half of :meth:`join_batch`: validate → refresh the
        resident payload → enqueue → launch the jitted megastep. Returns
        a :class:`JoinHandle` without blocking on the device result —
        redeem it with :meth:`finalize`. The serving scheduler uses this
        split to overlap batch N's fetch/split with batch N+1's device
        pass (double-buffered dispatch)."""
        with obs.span("megastep.dispatch", rows=len(queries)):
            q = self._validated_queries(queries)
            n = q.shape[0]
            if n == 0:
                return JoinHandle(kind="empty", n=0)
            payload = self._refresh()
            if stats is not None:
                stats.n_r += n
                stats.n_s = max(stats.n_s, self.index.n_s)
                stats.n_segments = len(payload.seg_meta)
                stats.n_tombstones = payload.n_dead
                stats.pivot_pairs_computed += n * sum(
                    m for m, _, _ in payload.seg_meta)
            qd, nv = self.enqueue(q)
            d, hi, lo = self.join_batch_device(qd, nv)
            return JoinHandle(kind="mega", n=n, dev=(d, hi, lo))

    def finalize(self, handle: JoinHandle, *,
                 stats: Optional[JoinStats] = None
                 ) -> tuple[np.ndarray, np.ndarray]:
        """Block on a dispatched batch and return ``(dists, int64 ids)``
        — the synchronous tail of :meth:`join_batch`."""
        k = self.config.k
        if handle.kind == "empty":
            return (np.zeros((0, k), np.float32),
                    np.full((0, k), -1, np.int64))
        if handle.kind != "mega":
            raise ValueError(f"cannot finalize handle kind {handle.kind!r}")
        from repro.serve import faultinject
        # the fetch below is the one boundary that synchronizes anyway —
        # bracketing it costs no extra sync, and its wall time is the
        # device-step completion time. Where a tracer reads it, ``.wait``
        # blocks on the buffers the copies would wait on anyway; ``.copy``
        # is the transfer and the id assembly
        t0 = time.perf_counter()
        with obs.span("megastep.fetch", rows=handle.n):
            faultinject.fire("megastep.fetch")     # simulated lost fetch
            n = handle.n
            d, hi, lo = handle.dev
            if obs.enabled():
                with obs.span("megastep.fetch.wait"):
                    jax.block_until_ready(handle.dev)
            with obs.span("megastep.fetch.copy"):
                d = np.asarray(d)[:n]
                ids = ((np.asarray(hi, np.int64) << 32)
                       | (np.asarray(lo, np.int64)
                          & np.int64(0xFFFFFFFF)))[:n]
        obs.metrics.REGISTRY.histogram("megastep_finalize_s") \
            .observe(time.perf_counter() - t0)
        return np.ascontiguousarray(d), np.ascontiguousarray(ids)

    def join_batch(
        self, queries: np.ndarray, *, stats: Optional[JoinStats] = None,
    ) -> tuple[np.ndarray, np.ndarray]:
        """(dists, int64 global ids) for one micro-batch — numpy in/out.
        enqueue → one fused device pass → fetch; bitwise-identical to the
        host-planned path over the same index. Exactly ``finalize(
        dispatch(q))`` — the scheduler calls the halves separately."""
        return self.finalize(self.dispatch(queries, stats=stats),
                             stats=stats)
