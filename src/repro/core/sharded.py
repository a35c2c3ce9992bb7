"""Sharded megastep: one logical datastore across a JAX device mesh.

The fused megastep (`core.megastep`) applies the paper's Cor. 1 / Thm 2
mapper-side filtering on exactly one device, so the resident payload —
even ~3.7× smaller via int8 — caps the datastore at one HBM. This module
re-expresses the paper's shuffle as **mesh partitioning**: pivot groups
are assigned to shards by the §5 geometric grouping
(`SIndex.shard_packing`), each shard holds only its groups' packed rows
(+ int8 twins + ε bounds) and their Thm-2 tile stats, and the whole
assign → θ → schedule → gather-top-k → exact-re-rank body runs SPMD
inside ``jax.shard_map``:

* **θ is global, schedules are per shard.** Every shard carries the
  replicated pivot geometry and T_S pivot-kNN lists of *all* segments,
  so `megastep._assign_bounds_schedule` computes the identical union-θ
  on every shard (Thm 3 over the union candidate set — bitwise the
  single-device value). Its visit masks, though, are evaluated against
  the shard's own tile stats: partitions a shard doesn't own are never
  ``present``, so the compacted schedule visits only local tiles — the
  paper's per-reducer pruning, reborn per shard.
* **Only final k-runs cross the mesh.** Each shard's gather-top-kp run
  is exactly re-ranked with canonical distances *locally*, then the
  (kp-wide) sorted runs are all-gathered and folded through the
  id-disjoint tree merge (`kernels.sorted_merge.tree_merge_runs`) —
  never raw candidates, never row payloads. For the quantized tier the
  per-shard certification lower bound is combined with ``lax.pmin`` so
  the usual per-query soundness certificate covers rows *any* shard
  coarse-pruned.
* **Zero steady-state host syncs, per shard.** Every payload piece —
  including the tombstone-count scalar and the enqueued queries — is
  committed to the mesh (replicated or shard-partitioned) at
  enqueue/refresh time, so the steady state runs entirely under
  ``jax.transfer_guard("disallow")``, exactly like the single-device
  engine it is bitwise-equal to.

Exactness under sharding: the merged union of per-shard exact top-kp
runs contains the true top-k (each true neighbor lives on exactly one
shard and survives that shard's θ-schedule superset + exact re-rank;
a row a shard drops at rank > kp has exact distance ≥ that shard's
k-th ≥ the merged k-th). Shard count therefore never changes the
output — pinned by the shard-invariance tests.

**Fault tolerance.** The exactness argument above holds for *any*
assignment that serves each partition on exactly one shard — which is
what makes failover bitwise. `SIndex.shard_packing(r=...)` places each
pivot group on a primary plus ``r−1`` backup shards (every replica the
same pivot-sorted packed slice); a :class:`ShardHealth` tracker — fed
by the ``sharded.*`` fault-injection sites and by bounded attempt
timeouts — picks a per-partition serving *owner view*
(`ShardPacking.owner_view`). Failover is a host-side mask swap: the
``alive`` mask keeps only owner-served rows (masked rows canonicalize
to (+inf, −1) exactly like padding, so output bits cannot move) and
``present`` is gated so schedules skip standby tiles; the resident row
payload never re-uploads. With no live replica the surviving shards'
runs still merge through `tree_merge_runs` and every query carries a
*sound* certified recall bound (see `_sharded_megastep`); `recover()`
rebuilds and re-uploads the full payload behind ``refresh_lock``
without blocking serving.
"""
from __future__ import annotations

import functools
import threading
from typing import Optional

import jax
import numpy as np

from repro import obs

from .megastep import (JoinHandle, MegastepEngine, _assign_bounds_schedule,
                       _bump_trace, _canonical_runs, _gather_topk_run)
from .types import JoinConfig, JoinStats

__all__ = ["ShardHealth", "ShardedMegastepEngine"]


class ShardHealth:
    """Thread-safe failed-shard tracker for one sharded engine.

    ``mark_failed`` records a failed shard and bumps ``generation``;
    the engine's payload cache keys on the generation, so the next
    ``_refresh`` rebuilds the *serving view* (owner failover masks)
    without re-uploading resident rows. ``reset`` restores full health
    (recovery). Timeouts with no attributable shard only count — the
    view can't change without knowing whom to evict."""

    def __init__(self, n_shards: int):
        self.n_shards = int(n_shards)
        self._lock = threading.Lock()
        self._failed: set = set()
        self.generation = 0
        self.n_faults = 0
        self.n_timeouts = 0

    @property
    def failed(self) -> frozenset:
        with self._lock:
            return frozenset(self._failed)

    def mark_failed(self, shard: Optional[int]) -> bool:
        """Record a shard failure; True iff it newly changed the view."""
        with self._lock:
            self.n_faults += 1
            if shard is None:
                return False
            shard = int(shard)
            if not (0 <= shard < self.n_shards) or shard in self._failed:
                return False
            self._failed.add(shard)
            self.generation += 1
            return True

    def note_timeout(self) -> None:
        with self._lock:
            self.n_timeouts += 1

    def reset(self) -> None:
        with self._lock:
            self._failed.clear()
            self.generation += 1

# per-segment geometry keys that are shard-partitioned (leading shard
# axis); everything else in a segment dict is replicated
_SEG_SHARDED = ("sd_min", "sd_max", "present")
# tile-payload keys that are replicated; everything else (rows, ids,
# liveness, int8 twins) is shard-partitioned on its leading axis
_TILES_REP = ("center",)


def _mesh_specs(segs, tiles):
    """PartitionSpecs matching the sharded payload layout: per-shard
    arrays split on their leading axis over the "shard" mesh axis,
    geometry/scalars replicated."""
    from jax.sharding import PartitionSpec as P
    seg_specs = tuple(
        {key: (P("shard") if key in _SEG_SHARDED else P())
         for key in sd}
        for sd in segs)
    tile_specs = {key: (P() if key in _TILES_REP else P("shard"))
                  for key in tiles}
    return seg_specs, tile_specs


def _strip_shard(segs, tiles):
    """Inside the shard_map body the partitioned arrays arrive with a
    leading shard axis of extent 1 — strip it so the payload has exactly
    the single-device shapes the shared megastep stages expect."""
    segs = tuple(
        {key: (val[0] if key in _SEG_SHARDED else val)
         for key, val in sd.items()}
        for sd in segs)
    tiles = {key: (val if key in _TILES_REP else val[0])
             for key, val in tiles.items()}
    return segs, tiles


@functools.partial(
    jax.jit,
    static_argnames=("mesh", "n_shards", "k", "bm", "bn", "metric", "dim",
                     "n_finite_total", "seg_meta", "primary", "impl"))
def _sharded_megastep(q, n_valid, dead_total, segs, tiles, state, *,
                      mesh, n_shards: int, k: int, bm: int, bn: int,
                      metric: str, dim: int, n_finite_total: int,
                      seg_meta: tuple, primary: int, impl: str):
    """The fp32 megastep under shard_map: per-shard schedule + gather +
    exact re-rank, all-gather of the final kp-runs, in-mesh tree merge.
    Bitwise the single-device `megastep._megastep` for any shard count.

    Returns ``(d, id_hi, id_lo, lm)``: the fourth output is the
    per-query certified degraded-coverage bound (+inf when the serving
    view covers every pivot group — the healthy case). Degraded views
    pass per-segment ``uncovered`` masks + T_S ``upper`` bounds in the
    seg dicts; soundness of the certificate: every row of an uncovered
    group p is ≥ max(d(q, pivot_p) − U(p), 0) away (triangle inequality
    on the pivot), and θ upper-bounds the distance of anything a visit
    schedule pruned — so a reported neighbor with d ≤ lm =
    min(min_p lb_p, θ) is provably in the true global top-k.
    """
    _bump_trace()

    import jax.numpy as jnp

    from repro.kernels.sorted_merge import (merge_sorted_runs_unique,
                                            next_pow2, tree_merge_runs)
    from jax.sharding import PartitionSpec as P

    kp = next_pow2(k)
    seg_specs, tile_specs = _mesh_specs(segs, tiles)

    @jax.shard_map(mesh=mesh,
                   in_specs=(P(), P(), P(), seg_specs, tile_specs),
                   # all_gather + tree merge leaves every shard holding
                   # the identical final run — replicated in value, which
                   # the static VMA check can't see (same pattern as
                   # distributed.distributed_phase1)
                   out_specs=(P(), P(), P(), P()), check_vma=False)
    def body(q, n_valid, dead_total, segs, tiles):
        segs, tiles = _strip_shard(segs, tiles)
        # θ below is computed from the replicated union T_S lists —
        # identical on every shard; the visit masks see only this
        # shard's tile stats, so the compacted schedule is local
        qs, qcs, valid_s, _perm, inv, th_q, sched, cnt = \
            _assign_bounds_schedule(
                q, n_valid, dead_total, segs, tiles["center"], k=k, bm=bm,
                metric=metric, n_finite_total=n_finite_total,
                seg_meta=seg_meta, primary=primary)
        d_run, pos, valid_sel, _ = _gather_topk_run(
            qs, qcs, valid_s, sched, cnt, tiles, k=k, bm=bm, bn=bn,
            metric=metric, dim=dim, impl=impl)
        # keep the full kp run: the cross-shard merge must see every
        # column to resolve the global rank-k boundary exactly
        d_can, hi, lo = _canonical_runs(qs, tiles, pos, valid_sel,
                                        metric, kp)
        # degraded-coverage certificate (replicated math — every shard
        # computes the identical bound from the replicated geometry);
        # healthy views carry no "uncovered" key and get a constant +inf
        lm = jnp.full((q.shape[0],), jnp.inf, jnp.float32)
        if any("uncovered" in sd for sd in segs):
            any_u = jnp.zeros((), bool)
            lb_min = jnp.full((q.shape[0],), jnp.inf, jnp.float32)
            for g in range(len(seg_meta)):
                sd = segs[g]
                if "uncovered" not in sd:
                    continue
                pc = sd["pivots_c"]
                d2 = (jnp.sum(qcs * qcs, axis=1)[:, None]
                      + jnp.sum(pc * pc, axis=1)[None, :]
                      - 2.0 * jnp.matmul(
                          qcs, pc.T, precision=jax.lax.Precision.HIGHEST))
                dqp = jnp.sqrt(jnp.maximum(d2, 0.0))
                lb = jnp.maximum(
                    dqp - sd["upper"][None, :].astype(jnp.float32), 0.0)
                lb = jnp.where(sd["uncovered"][None, :], lb, jnp.inf)
                lb_min = jnp.minimum(lb_min, jnp.min(lb, axis=1))
                any_u = any_u | jnp.any(sd["uncovered"])
            # the θ cap is load-bearing: a covered row the schedule
            # θ-pruned could be closer than a counted neighbor, so only
            # d ≤ θ neighbors can claim a provable global rank
            lm = jnp.where(any_u, jnp.minimum(lb_min, th_q), jnp.inf)
        d_can, hi, lo, lm = d_can[inv], hi[inv], lo[inv], lm[inv]
        if n_shards > 1:
            gd = jax.lax.all_gather(d_can, "shard")
            ghi = jax.lax.all_gather(hi, "shard")
            glo = jax.lax.all_gather(lo, "shard")
            d_can, (hi, lo) = tree_merge_runs(
                [(gd[j], (ghi[j], glo[j])) for j in range(n_shards)])
        return d_can[:, :k], hi[:, :k], lo[:, :k], lm

    d, hi, lo, lm = body(q, n_valid, dead_total, segs, tiles)

    if state is not None:
        sd, shi, slo = state[:3]
        if len(state) > 3:
            # min of two sound per-query bounds is sound
            lm = jnp.minimum(lm, state[3])
        pad = ((0, 0), (0, kp - k))
        md, (mhi, mlo) = merge_sorted_runs_unique(
            jnp.pad(sd, pad, constant_values=jnp.inf),
            (jnp.pad(shi, pad, constant_values=-1),
             jnp.pad(slo, pad, constant_values=-1)),
            jnp.pad(d, pad, constant_values=jnp.inf),
            (jnp.pad(hi, pad, constant_values=-1),
             jnp.pad(lo, pad, constant_values=-1)))
        d, hi, lo = md[:, :k], mhi[:, :k], mlo[:, :k]
    return d, hi, lo, lm


class _ShardedPayloadMixin:
    """Shared mesh/payload machinery of the sharded engines: mesh
    construction, replicated/partitioned device placement, and the
    shard-laid-out `_build_struct` both the fp32 and quantized sharded
    engines consume. Mixed in *before* the single-device engine so its
    placement hooks and payload build win the MRO."""

    def _init_mesh(self, n_shards, mesh) -> None:
        if mesh is not None:
            if "shard" not in mesh.axis_names:
                raise ValueError(
                    f"sharded megastep needs a mesh with a 'shard' axis, "
                    f"got axes {mesh.axis_names}")
            self.mesh = mesh
            self.n_shards = int(mesh.shape["shard"])
            if n_shards is not None and int(n_shards) != self.n_shards:
                raise ValueError(
                    f"n_shards={n_shards} disagrees with the mesh's "
                    f"'shard' extent {self.n_shards}")
            self._init_health()
            return
        avail = len(jax.devices())
        n_shards = avail if n_shards is None else int(n_shards)
        if n_shards < 1:
            raise ValueError(f"n_shards must be >= 1, got {n_shards}")
        if n_shards > avail:
            raise ValueError(
                f"n_shards={n_shards} exceeds the {avail} visible "
                f"device(s); for a simulated mesh set "
                f"XLA_FLAGS=--xla_force_host_platform_device_count="
                f"{n_shards} before importing jax")
        self.mesh = jax.make_mesh(
            (n_shards,), ("shard",),
            axis_types=(jax.sharding.AxisType.Auto,))
        self.n_shards = n_shards
        self._init_health()

    def _init_health(self) -> None:
        # shard-failure state shared by every sharded engine. The quant
        # engine never wires the fault sites, so its health stays clean
        # and the view fast paths below are identity for it; the fp32
        # engine overrides replication/attempt_timeout from its ctor.
        self.health = ShardHealth(self.n_shards)
        self.replication = 1
        self.attempt_timeout: Optional[float] = None
        self._attempt_pool = None
        self._cov_cache = None
        self._recover_lock = threading.Lock()

    # ---- device placement: commit everything to the mesh so the jit
    # over sharded args never sees a single-device-committed array (that
    # raises "incompatible devices") and the steady state never moves a
    # byte — both replicated and partitioned pieces land at refresh /
    # enqueue time, outside any transfer guard

    def _put_rep(self, x):
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        return jax.device_put(jnp.asarray(x),
                              NamedSharding(self.mesh, P()))

    def _put_shard(self, x):
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.serve import faultinject
        # fault hook: a ShardFault here simulates a device lost while
        # its partitioned payload slice was being committed to the mesh
        faultinject.fire("sharded.shard_upload")
        return jax.device_put(np.ascontiguousarray(x),
                              NamedSharding(self.mesh, P("shard")))

    def _put_alive(self, alive: np.ndarray):
        return self._put_shard(alive.astype(np.float32))

    def enqueue(self, queries: np.ndarray):
        q = np.ascontiguousarray(queries, np.float32)
        n = q.shape[0]
        bucket = self.bucket_for(n)
        if bucket != n:
            q = np.pad(q, ((0, bucket - n), (0, 0)))
        return self._put_rep(q), self._put_rep(np.int32(n))

    def dispatch(self, queries, *, stats=None):
        if stats is not None:
            stats.n_shards = self.n_shards
        return super().dispatch(queries, stats=stats)

    def nbytes_per_shard(self, *,
                         quantized: Optional[bool] = None) -> np.ndarray:
        """Resident row-payload bytes per shard, summed over live
        segments — the per-device HBM figure `SIndex.nbytes_resident(
        n_shards=...)` reports the max of (see `index.ShardPacking`)."""
        segs, _, _ = self._index_parts()
        out = np.zeros((self.n_shards,), np.int64)
        for si, _ in segs:
            qz = ((si.config.quantize != "none")
                  if quantized is None else quantized)
            sp = si.shard_packing(self.n_shards, self._bn,
                                  r=self.replication)
            out += sp.nbytes_per_shard(quantized=qz)
        return out

    # ---- the shard-laid-out payload

    def _build_struct(self, segs, bn: int, k: int) -> dict:
        n_sh = self.n_shards
        r = self.replication
        live_ids = set(id(si) for si, _ in segs)
        self._seg_cache = {key: v for key, v in self._seg_cache.items()
                           if key[0] in live_ids}
        dim = segs[0][0].dim
        quant = getattr(self, "mode", "fp32") == "int8"
        seg_meta = []
        n_finite_total = 0
        sizes = []
        packs = []
        for si, off in segs:
            key = (id(si), bn, n_sh, r)
            ent = self._seg_cache.get(key)
            if ent is None:
                ent = dict(si=si, sp=si.shard_packing(n_sh, bn, r=r),
                           knn_np=si.t_s.knn_dists)
                self._seg_cache[key] = ent
            sp = ent["sp"]
            kk = min(k, ent["knn_np"].shape[1])
            n_finite_total += int(np.isfinite(ent["knn_np"][:, :kk]).sum())
            seg_meta.append((si.n_pivots, kk, sp.tiles_per_shard))
            sizes.append(si.n_s)
            packs.append((si, off, sp))
        # the selection center must be bitwise the single-device one:
        # same rows, same (segment, partition, dist) order, same f64
        # mean — sharding must not perturb the selection metric
        all_rows = (np.concatenate([si.s_sorted for si, _, _ in packs])
                    if sum(sizes) else np.zeros((0, dim), np.float32))
        center = (all_rows.mean(axis=0, dtype=np.float64)
                  .astype(np.float32) if all_rows.shape[0] else
                  np.zeros((dim,), np.float32))
        segs_dev = []
        for si, off, sp in packs:
            segs_dev.append(dict(
                pivots_c=self._put_rep(si.pivots - center[None, :]),
                pivd=self._put_rep(si.pivd.astype(np.float32)),
                knn=self._put_rep(si.t_s.knn_dists.astype(np.float32)),
                # T_S per-partition upper bounds, replicated: the
                # degraded-coverage certificate reads them in-body
                upper=self._put_rep(si.t_s.upper.astype(np.float32)),
                sd_min=self._put_shard(sp.sd_min),
                sd_max=self._put_shard(sp.sd_max),
                present=self._put_shard(sp.present)))
        rows_all = np.concatenate([sp.rows for _, _, sp in packs], axis=1)
        gids = np.concatenate(
            [np.where(sp.gids_local >= 0, sp.gids_local + off, -1)
             for _, off, sp in packs], axis=1)
        hi = (gids >> 32).astype(np.int32)
        lo = (gids & np.int64(0xFFFFFFFF)).astype(np.uint32).view(np.int32)
        tiles_dev = dict(center=self._put_rep(center),
                         id_hi=self._put_shard(hi),
                         id_lo=self._put_shard(lo),
                         s=self._put_shard(rows_all))
        if quant:
            sqs, scs, eps = zip(*(sp.ensure_quant()
                                  for _, _, sp in packs))
            tiles_dev["sq"] = self._put_shard(np.concatenate(sqs, axis=1))
            tiles_dev["sscale"] = self._put_shard(
                np.concatenate(scs, axis=1))
            tiles_dev["seps"] = self._put_shard(np.concatenate(eps, axis=1))
        return dict(
            segs_dev=tuple(segs_dev), tiles_dev=tiles_dev, rows_host=None,
            gids=gids, seg_meta=tuple(seg_meta), dim=dim,
            n_finite_total=n_finite_total, primary=int(np.argmax(sizes)),
            # host-side packings, for the health-driven serving views
            packs_sp=tuple(sp for _, _, sp in packs))

    # ---- serving view (failover): the payload cache keys on shard
    # health, and the alive/present masks follow the owner view. With
    # r=1 and full health (the quant engines always, the fp32 engine in
    # steady state) every hook is identity — bitwise and free.

    def _payload_key(self, vkey):
        return vkey + ("health", self.health.generation)

    def _view_packs(self, st):
        failed = self.health.failed
        return [(sp, sp.owner_view(failed)) for sp in st["packs_sp"]]

    def _alive_mask(self, st, tomb) -> np.ndarray:
        alive = super()._alive_mask(st, tomb)
        if self.replication == 1 and not self.health.failed:
            return alive
        mask = np.concatenate(
            [sp.serve_mask(owner) for sp, owner in self._view_packs(st)],
            axis=1)
        return alive & mask

    def _segs_for_view(self, st):
        if self.replication == 1 and not self.health.failed:
            return st["segs_dev"]
        out = []
        for base, (sp, owner) in zip(st["segs_dev"], self._view_packs(st)):
            sd = dict(base)
            sd["present"] = self._put_shard(sp.present_view(owner))
            sd["uncovered"] = self._put_rep(sp.uncovered_parts(owner))
            out.append(sd)
        return tuple(out)

    # ---- the sharded device call

    def _sharded_fp32_call(self, q_dev, n_valid_dev, state=None):
        return self._mega_call(self._refresh(), q_dev, n_valid_dev, state)

    def _mega_call(self, payload, q_dev, n_valid_dev, state=None):
        """The lock-free tail of the sharded fp32 call: launch the SPMD
        megastep against an already-refreshed payload. Split out so a
        timeout-bounded attempt thread never re-enters refresh_lock."""
        bucket = int(q_dev.shape[0])
        bm = min(bucket, self._bm_cap)
        impl = self.resolved_impl
        return _sharded_megastep(
            q_dev, n_valid_dev, payload.dead_total, payload.segs,
            payload.tiles, state, mesh=self.mesh, n_shards=self.n_shards,
            k=self.config.k, bm=bm, bn=self._bn,
            metric=self.config.metric, dim=payload.dim,
            n_finite_total=payload.n_finite_total,
            seg_meta=payload.seg_meta, primary=payload.primary, impl=impl)


class ShardedMegastepEngine(_ShardedPayloadMixin, MegastepEngine):
    """`MegastepEngine` over a 1-D "shard" mesh: the same dispatch() /
    finalize() surface and the same bitwise output, with the index
    payload partitioned across shards by `SIndex.shard_packing` and the
    megastep running SPMD (see module docstring).

    ``n_shards=None`` spans every visible device; pass an explicit
    ``mesh`` (with a "shard" axis) to co-locate with other meshes.

    ``replication=r`` places every pivot group on a primary plus r-1
    backup shards (`SIndex.shard_packing(r=...)`). On a detected shard
    failure (a :class:`~repro.serve.faultinject.ShardFault` from a
    ``sharded.*`` site, or a bounded ``attempt_timeout`` expiring) the
    engine marks the shard failed and raises
    :class:`~repro.serve.faultinject.ShardFailedError`; the next attempt
    serves the updated owner view — bitwise-identical while every
    populated group keeps a live replica, certified degraded coverage
    (per-query ``rb`` from :meth:`finalize_covered`) once groups are
    lost. :meth:`recover` re-uploads and re-admits failed shards in the
    background without blocking serving.
    """

    def __init__(self, index, config: Optional[JoinConfig] = None, *,
                 n_shards: Optional[int] = None, mesh=None,
                 bucket_min: int = 16, impl: Optional[str] = None,
                 replication: int = 1,
                 attempt_timeout: Optional[float] = None):
        self._init_mesh(n_shards, mesh)
        replication = int(replication)
        if replication < 1:
            raise ValueError(f"replication must be >= 1, got {replication}")
        self.replication = min(replication, self.n_shards)
        self.attempt_timeout = (float(attempt_timeout)
                                if attempt_timeout else None)
        super().__init__(index, config, bucket_min=bucket_min, impl=impl)

    def join_batch_device(self, q_dev, n_valid_dev, *, state=None):
        return self._sharded_fp32_call(q_dev, n_valid_dev, state)

    # ---- failure handling

    def _shard_failed(self, fault):
        """Record a failed shard and convert the fault into the
        retriable :class:`ShardFailedError` (the caller's next attempt
        runs on the updated owner view)."""
        from repro.serve.faultinject import ShardFailedError
        shard = getattr(fault, "shard", None)
        self.health.mark_failed(shard)
        self._cov_cache = None
        # the remask: the serving view just changed — the next refresh
        # rebuilds owner-failover masks keyed on this generation
        obs.event("sharded.failover_remask", shard=shard,
                  generation=self.health.generation,
                  n_failed=len(self.health.failed))
        reg = obs.metrics.REGISTRY
        reg.counter("shard_failover_total").inc()
        reg.gauge("shard_failed").set(len(self.health.failed))
        reg.gauge("shard_generation").set(self.health.generation)
        return ShardFailedError(
            shard, f"shard {shard} failed "
                   f"({len(self.health.failed)}/{self.n_shards} down): "
                   f"{fault}")

    def _bounded_attempt(self, fn, what: str):
        """Run one device attempt under ``attempt_timeout`` so a hung
        shard/collective surfaces as a :class:`ShardFailedError` instead
        of hanging ``serve_forever()``. ``fn`` must not take
        ``refresh_lock`` (the caller thread may already hold it via
        ``Datastore``'s serialize-under-lock path — refresh therefore
        always runs in the caller thread, never here)."""
        timeout = self.attempt_timeout
        if not timeout:
            return fn()
        import concurrent.futures as cf
        if self._attempt_pool is None:
            self._attempt_pool = cf.ThreadPoolExecutor(
                max_workers=4, thread_name_prefix="shard-attempt")
        fut = self._attempt_pool.submit(fn)
        try:
            return fut.result(timeout=timeout)
        except cf.TimeoutError:
            from repro.serve.faultinject import ShardFailedError
            fut.cancel()
            self.health.note_timeout()
            obs.metrics.REGISTRY.counter("shard_timeout_total").inc()
            raise ShardFailedError(
                None, f"{what} exceeded attempt_timeout={timeout}s "
                      f"(hung shard or collective)") from None

    # ---- coverage certification

    def _coverage(self):
        segs, _, _ = self._index_parts()
        ck = (tuple(id(si) for si, _ in segs), self.health.generation)
        if self._cov_cache is not None and self._cov_cache[0] == ck:
            return self._cov_cache[1]
        failed = self.health.failed
        total = covered = 0
        any_unc = False
        for si, _ in segs:
            sp = si.shard_packing(self.n_shards, self._bn,
                                  r=self.replication)
            owner = sp.owner_view(failed)
            pc = sp.partition_counts()
            total += int(pc.sum())
            covered += int(pc[owner >= 0].sum())
            any_unc = any_unc or bool(sp.uncovered_parts(owner).any())
        out = ((covered / total) if total else 1.0, any_unc)
        self._cov_cache = (ck, out)
        return out

    @property
    def coverage_degraded(self) -> bool:
        """True when some populated pivot group has no live replica —
        results carry sound per-query recall bounds < 1 instead of the
        bitwise-exactness guarantee."""
        if not self.health.failed:
            return False
        return self._coverage()[1]

    def coverage_fraction(self) -> float:
        """Certified fraction of resident S rows in covered groups."""
        if not self.health.failed:
            return 1.0
        return self._coverage()[0]

    # ---- query API (failover-aware dispatch/finalize)

    def dispatch(self, queries: np.ndarray, *,
                 stats: Optional[JoinStats] = None) -> JoinHandle:
        from repro.serve import faultinject
        q = self._validated_queries(queries)
        n = q.shape[0]
        if stats is not None:
            stats.n_shards = self.n_shards
            stats.n_failed_shards = len(self.health.failed)
        if n == 0:
            return JoinHandle(kind="empty", n=0)
        try:
            # refresh (payload rebuild under refresh_lock) stays in the
            # caller thread: Datastore points refresh_lock at the lock
            # its mutations hold, and a bounded-attempt pool thread
            # taking it could deadlock against a caller holding it
            payload = self._refresh()
            if stats is not None:
                stats.n_r += n
                stats.n_s = max(stats.n_s, self.index.n_s)
                stats.n_segments = len(payload.seg_meta)
                stats.n_tombstones = payload.n_dead
                stats.pivot_pairs_computed += n * sum(
                    m for m, _, _ in payload.seg_meta)
            qd, nv = self.enqueue(q)

            def launch():
                # fault hook: a shard dying mid-stream, at launch
                faultinject.fire("sharded.shard_compute")
                return self._mega_call(payload, qd, nv, None)

            d, hi, lo, lm = self._bounded_attempt(
                launch, "sharded dispatch")
        except faultinject.ShardFault as e:
            raise self._shard_failed(e) from e
        return JoinHandle(kind="sharded", n=n, dev=(d, hi, lo, lm))

    def finalize(self, handle: JoinHandle, *,
                 stats: Optional[JoinStats] = None
                 ) -> tuple[np.ndarray, np.ndarray]:
        d, ids, _rb = self.finalize_covered(handle, stats=stats)
        return d, ids

    def finalize_covered(self, handle: JoinHandle, *,
                         stats: Optional[JoinStats] = None):
        """:meth:`finalize` + the per-query certified recall lower bound
        ``rb`` (shape ``(n,)`` float32, 1.0 everywhere on a healthy
        mesh): reported neighbor j of query q is provably in the global
        top-k iff ``d_j <= lm_q`` (see the lm certificate in
        ``_sharded_megastep``), so at least ``rb*k`` of the k reported
        neighbors are true global kNN."""
        from repro.serve import faultinject
        k = self.config.k
        if handle.kind == "empty":
            return (np.zeros((0, k), np.float32),
                    np.full((0, k), -1, np.int64),
                    np.ones((0,), np.float32))
        if handle.kind != "sharded":
            raise ValueError(f"cannot finalize handle kind {handle.kind!r}")
        n = handle.n

        def fetch():
            faultinject.fire("megastep.fetch")   # simulated lost fetch
            dd, hh, ll, lmv = handle.dev
            # fault hook over the fetched cross-shard merge result: a
            # .fail is a poisoned all-gather; a sleeping .transform is a
            # hung one, which attempt_timeout must bound
            dd = faultinject.cross("sharded.collective", dd)
            return (np.asarray(dd), np.asarray(hh), np.asarray(ll),
                    np.asarray(lmv))

        try:
            # the cross-shard tree-merge result lands here — this fetch
            # synchronizes anyway, so the span costs no extra sync
            with obs.span("sharded.collective", rows=n,
                          n_shards=self.n_shards,
                          generation=self.health.generation,
                          n_failed=len(self.health.failed)) as sp:
                d, hi, lo, lm = self._bounded_attempt(
                    fetch, "sharded finalize")
                sp.set(outcome="merged")
        except faultinject.ShardFault as e:
            raise self._shard_failed(e) from e
        d = np.ascontiguousarray(d[:n])
        ids = ((hi.astype(np.int64) << 32)
               | (lo.astype(np.int64) & np.int64(0xFFFFFFFF)))[:n]
        lm = lm[:n]
        rb = ((d <= lm[:, None]).sum(axis=1) / k).astype(np.float32)
        if stats is not None and n and self.coverage_degraded:
            stats.n_degraded += n
            stats.recall_bound = min(stats.recall_bound, float(rb.min()))
            stats.coverage_bound = min(stats.coverage_bound,
                                       self.coverage_fraction())
        return d, np.ascontiguousarray(ids), rb

    def join_batch(
        self, queries: np.ndarray, *, stats: Optional[JoinStats] = None,
    ) -> tuple[np.ndarray, np.ndarray]:
        d, ids, _rb = self.join_batch_covered(queries, stats=stats)
        return d, ids

    def join_batch_covered(self, queries: np.ndarray, *,
                           stats: Optional[JoinStats] = None):
        """:meth:`join_batch` + per-query certified recall bounds, with
        bounded internal failover: a :class:`ShardFailedError` re-enters
        on the updated owner view, at most once per shard (the serving
        scheduler instead catches the error itself so it can re-check
        deadlines at the failover instant)."""
        from repro.serve.faultinject import ShardFailedError
        last = None
        for _ in range(self.n_shards + 1):
            try:
                return self.finalize_covered(
                    self.dispatch(queries, stats=stats), stats=stats)
            except ShardFailedError as e:
                last = e
                continue
        raise last

    # ---- background recovery

    def recover(self, *, wait: bool = True):
        """Re-admit failed shards: rebuild + re-upload the full
        shard-partitioned payload, swap it in under ``refresh_lock``,
        and reset health — serving keeps answering on the degraded view
        while the upload runs. ``wait=False`` returns the daemon thread
        doing the work; ``wait=True`` blocks until recovered."""
        if wait:
            self._recover_work()
            return None
        t = threading.Thread(target=self._recover_work,
                             name="shard-recover", daemon=True)
        t.start()
        return t

    def _recover_work(self) -> None:
        with self._recover_lock:
            if not self.health.failed:
                return
            with self.refresh_lock:
                segs, _, _ = self._index_parts()
            if not segs:
                with self.refresh_lock:
                    self.health.reset()
                    self._payload = None
                    self._cov_cache = None
                return
            bn, k = self._bn, self.config.k
            # the expensive half — re-uploading every shard's slice —
            # runs outside refresh_lock so serving never blocks on it
            with obs.span("sharded.recover", n_shards=self.n_shards,
                          n_failed=len(self.health.failed)):
                st = self._build_struct(segs, bn, k)
                skey = (tuple(id(si) for si, _ in segs), bn, k)
                with self.refresh_lock:
                    self._struct = (skey, st)
                    self.health.reset()
                    self._payload = None
                    self._cov_cache = None
            reg = obs.metrics.REGISTRY
            reg.counter("shard_recover_total").inc()
            reg.gauge("shard_failed").set(0)
            reg.gauge("shard_generation").set(self.health.generation)
