"""Two-tier quantized query engine: int8 coarse scan → exact fp32
re-rank, bitwise-identical to the fp32 oracle.

The fused megastep (core.megastep) made the per-batch serving path
compute-lean; this engine makes it *memory*-lean — and, with the
device-resident re-rank + measured tuning table below, latency-lean
too. The device-resident index payload holds int8 codes + per-tile
scales + per-row error bounds ε (≈ 4× fewer resident bytes,
`SIndex.nbytes_resident`), and each batch runs:

1. **plan** (shared jit graph with the fp32 megastep —
   `core.megastep._assign_bounds_schedule`): assignment, union θ
   (tombstone-widened), compacted Cor. 1 / Thm 2 tile schedule. All of
   it uses *exact* pivot geometry, so no ε enters here.
2. **coarse int8 scan** (`kernels.quant_topk`, jnp twin
   `kernels.ref.quant_coarse_topk_ref`): over the scheduled tiles only,
   int8 dot → int32 accumulate → fp32 rescale. Selection key per row is
   the certified lower bound ``lb = max(d_coarse − ε_total, 0)`` with
   ``ε_total = ε_s + ε_q + ε_num``; candidates with ``lb > θ`` are
   masked — θ effectively *inflated by ε*, so a true neighbor
   (distance ≤ θ ⇒ lb ≤ θ) is never pruned. The smallest
   ``mp = pow2(k + slack)`` lower bounds survive as the shortlist.
3. **exact re-rank**: the shortlisted rows are gathered from the fp32
   packed rows and re-ranked through the canonical distance graph —
   the *same* graph every other engine reports — so the quantized path
   emits the exact bits the oracle does. Two variants:

   * **device-resident** (the default when HBM allows): the fp32 rows
     stay resident next to the int8 codes and the gather + re-rank is
     fused into the same jitted megastep (`metrics.canonical_gathered`
     → pos-masked +inf → stable argsort — stage 5 of the fp32 megastep
     verbatim), restoring the zero-steady-state-host-sync invariant
     the host-gather round-trip had given up;
   * **host-gather** (the low-memory fallback, ``resident=False``):
     the fp32 rows stay host-side and the shortlist round-trips
     through `metrics.canonical_topk` — the original 4×-smaller-HBM
     trade.
4. **certification**: per query, let L = the mp-th (largest) shortlist
   lower bound (+inf if the shortlist wasn't filled) and τ̂ = the k-th
   smallest exact re-ranked distance. Every coarse candidate *outside*
   the shortlist has lb ≥ L; if ``L ≥ τ̂`` no excluded row can beat the
   reported k-th neighbor, so the result is provably the true top-k.
   The (rare — adversarial near-ties at the shortlist boundary) queries
   that fail re-run through the fp32 host oracle
   (`JoinStats.n_quant_fallback` counts them). Exactness is therefore
   **unconditional**, not probabilistic.

Whether the coarse pass runs *at all* is a measured decision, not a
hope: at construction the engine looks its ``(backend, dim, |S|, k)``
shape up in the tuning table (`repro.quant.autotune`, regenerated by
``python -m benchmarks.tune_quant``). A cell tuned to ``mode="fp32"``
means the int8 scan measured slower than the plain fp32 megastep on
this backend (true today for the CPU refs on small dims) — the engine
then runs the exact fp32 scan outright instead of silently losing
wall-clock, and every query is trivially certified. A cell tuned to
``mode="int8"`` carries the measured shortlist size ``mp`` and tile
shapes. An explicit ``slack``/``config.quant_slack ≥ 0`` or a forced
``impl=`` overrides the table and pins classic int8 behavior.

The bitwise contract carries the same caveat as every other engine pair
in this codebase (see `core.segments`): when *distinct* rows tie at
exactly the same float32 canonical distance, which tied id is reported
(or their order) may differ from the oracle's — the distances
themselves are still bitwise-equal, and both answers are exact kNN
sets; only the tie-break differs (here: shortlist order vs the oracle
engine's selection order).

Soundness of the lower bound (the ε lemma, hypothesis-tested in
tests/test_quant.py): with ŝ = code·scale and q̂ the int8-quantized
query, the triangle inequality gives |d(q̂, ŝ) − d(q, s)| ≤
‖q − q̂‖ + ‖s − ŝ‖ ≤ ε_q + ε_s, and ε_num (see `kernels.quant_topk`)
dominates the float32 rescale rounding of d(q̂, ŝ) itself.
"""
from __future__ import annotations

import functools
import os
from typing import Optional

import jax
import numpy as np

from repro import obs
from repro.core.megastep import (MegastepEngine, JoinHandle, _bump_trace,
                                 _assign_bounds_schedule)
from repro.core.metrics import canonical_gathered, canonical_topk
from repro.core.sharded import (_ShardedPayloadMixin, _mesh_specs,
                                _strip_shard)
from repro.core.types import JoinConfig, JoinStats
from repro.kernels.sorted_merge import next_pow2
from repro.quant import autotune
from repro.quant.autotune import TunedConfig
from repro.quant.quantize import resident_extra_bytes

__all__ = ["QuantMegastepEngine", "ShardedQuantMegastepEngine",
           "quantize_queries_jnp"]

# resident re-rank auto-threshold: keep the fp32 rows + id pair in HBM
# only while they fit comfortably (the whole point of the int8 tier is
# the regime where they don't)
_RESIDENT_MAX_BYTES = int(os.environ.get(
    "REPRO_QUANT_RESIDENT_MAX_BYTES", 1 << 30))


def quantize_queries_jnp(q):
    """Per-row symmetric int8 query quantization, in-jit.

    Returns ``(codes int8, scales f32, eps f32)`` with eps an upper
    bound on ‖q − q̂‖₂: the float32-computed norm is inflated by a
    relative + absolute margin that dwarfs its own rounding error
    (mirrors the rounded-up storage of the S-side ε).
    """
    import jax.numpy as jnp

    amax = jnp.max(jnp.abs(q), axis=1)
    scale = jnp.where(amax > 0, amax / 127.0, 1.0).astype(jnp.float32)
    codes = jnp.clip(jnp.round(q / scale[:, None]), -127, 127)
    recon = codes * scale[:, None]
    err = jnp.sqrt(jnp.sum(jnp.square(q - recon), axis=1))
    eps = err * np.float32(1.0 + 1e-5) + np.float32(1e-7)
    return codes.astype(jnp.int8), scale, eps.astype(jnp.float32)


def _quant_coarse_body(q, n_valid, dead_total, segs, tiles, *,
                       mp: int, k: int, bm: int, bn: int, metric: str,
                       n_finite_total: int, seg_meta: tuple,
                       primary: int, impl: str):
    """plan (shared with the fp32 megastep) → int8 coarse shortlist.

    The traceable body shared — *verbatim, same graph* — by the
    host-gather jit (:func:`_quant_coarse`) and the fused resident
    megastep (:func:`_quant_megastep`), so the certified lower bounds
    both paths report are the same bits.

    Returns ``(lb (B, mp) ascending certified lower bounds,
    pos (B, mp) int32 rows into the packed layout)`` in the original
    query order; empty slots are (+inf, -1).
    """
    from repro.kernels import ops

    qs, _, _, _, inv, th_q, sched, cnt = _assign_bounds_schedule(
        q, n_valid, dead_total, segs, tiles["center"], k=k, bm=bm,
        metric=metric, n_finite_total=n_finite_total, seg_meta=seg_meta,
        primary=primary)
    qi, qscale, qeps = quantize_queries_jnp(qs)
    # one dispatch for every impl (pallas / interpret / ref_sched /
    # dense ref) — the registered op, traced into this jit. θ is
    # ulp-padded (bounds.pad_theta) like every other prune site: the
    # certified lb can equal the true distance exactly, and θ's fp
    # value may round below the real Thm-3 bound.
    from repro.core.bounds import pad_theta
    lb, pos = ops.quant_coarse_topk(
        qi, qscale, qeps, pad_theta(th_q), tiles["sq"], tiles["sscale"],
        tiles["seps"], tiles["alive"], mp, schedule=sched, counts=cnt,
        bm=bm, bn=bn, impl=impl)
    return lb[inv], pos[inv]


@functools.partial(
    jax.jit,
    static_argnames=("mp", "k", "bm", "bn", "metric", "dim",
                     "n_finite_total", "seg_meta", "primary", "impl"))
def _quant_coarse(q, n_valid, dead_total, segs, tiles, *,
                  mp: int, k: int, bm: int, bn: int, metric: str,
                  dim: int, n_finite_total: int, seg_meta: tuple,
                  primary: int, impl: str):
    """The coarse pass alone (host-gather variant + `coarse_shortlist`)."""
    return _quant_coarse_body(
        q, n_valid, dead_total, segs, tiles, mp=mp, k=k, bm=bm, bn=bn,
        metric=metric, n_finite_total=n_finite_total, seg_meta=seg_meta,
        primary=primary, impl=impl)


@functools.partial(
    jax.jit,
    static_argnames=("mp", "k", "bm", "bn", "metric", "dim",
                     "n_finite_total", "seg_meta", "primary", "impl"))
def _quant_megastep(q, n_valid, dead_total, segs, tiles, *,
                    mp: int, k: int, bm: int, bn: int, metric: str,
                    dim: int, n_finite_total: int, seg_meta: tuple,
                    primary: int, impl: str):
    """The fused device-resident quant megastep: coarse int8 shortlist →
    on-device fp32 gather → canonical exact re-rank, one trace, nothing
    fetched. Returns ``(d (B, k), id_hi, id_lo, lm (B,))`` with ``lm``
    the per-query exclusion bound (the mp-th ascending certified lower
    bound; +inf when the shortlist wasn't filled).

    The re-rank is stage 5 of the fp32 megastep verbatim: gather the
    resident fp32 rows at the shortlist positions, recompute canonical
    distances (`metrics.canonical_gathered` — the same unrolled
    elementwise graph the host path's `canonical_topk` runs), mask
    empty slots to +inf, stable-argsort, take k — so the emitted bits
    match the host-gather variant and the fp32 oracle exactly.
    """
    _bump_trace()              # trace-time only == jit cache miss

    import jax.numpy as jnp

    lb, pos = _quant_coarse_body(
        q, n_valid, dead_total, segs, tiles, mp=mp, k=k, bm=bm, bn=bn,
        metric=metric, n_finite_total=n_finite_total, seg_meta=seg_meta,
        primary=primary, impl=impl)
    s = tiles["s"]
    pos_c = jnp.clip(pos, 0, s.shape[0] - 1)
    neigh = s[pos_c]                                    # (b, mp, dim)
    d_can = canonical_gathered(q, neigh, metric)
    # pos ≥ 0 ⇔ a live row: the coarse kernels mask dead/padding rows
    # before selection, so no non-negative slot points at a gid < 0
    valid_sel = pos >= 0
    d_can = jnp.where(valid_sel, d_can, jnp.inf)
    hi = jnp.where(valid_sel, tiles["id_hi"][pos_c], -1)
    lo = jnp.where(valid_sel, tiles["id_lo"][pos_c], -1)
    order = jnp.argsort(d_can, axis=1, stable=True)
    d_can = jnp.take_along_axis(d_can, order, axis=1)[:, :k]
    hi = jnp.take_along_axis(hi, order, axis=1)[:, :k]
    lo = jnp.take_along_axis(lo, order, axis=1)[:, :k]
    return d_can, hi, lo, lb[:, -1]


class QuantMegastepEngine(MegastepEngine):
    """Memory-lean drop-in for `MegastepEngine`: same index kinds
    (``SIndex`` or ``MutableIndex`` with live tombstones), same exact
    bitwise results, int8-resident payload. Reached via
    ``knn_join(..., quantized=True)``, ``knn_join_batched(...,
    quantized=True)``, ``StreamJoinEngine(..., quantized=True)`` and
    ``serve.Datastore(quantized=True)``. L2 only, like the megastep.

    Construction resolves three measured/overridable decisions:

    * ``mode`` — ``"int8"`` (the two-tier path) or ``"fp32"`` (the
      tuning table measured int8 as a loss on this shape/backend; run
      the exact fp32 megastep instead). Explicit ``slack``/``impl``
      pins int8.
    * ``resident`` — fuse the exact re-rank into the device megastep
      (zero steady-state host syncs) vs the host-gather fallback.
      ``None`` auto-sizes against ``REPRO_QUANT_RESIDENT_MAX_BYTES``.
    * ``mp``/tile shapes — explicit slack wins, else the tuned values,
      else the classic ``max(pow2(4k), 128)`` heuristic.
    """

    def __init__(self, index, config: Optional[JoinConfig] = None, *,
                 slack: Optional[int] = None, bucket_min: int = 16,
                 impl: Optional[str] = None,
                 resident: Optional[bool] = None,
                 tune="auto", tune_bn: Optional[int] = None):
        if impl not in (None, "pallas", "pallas_interpret", "ref",
                        "ref_sched"):
            raise ValueError(f"unknown quant coarse impl {impl!r}")
        cfg = config or index.config
        if cfg.metric != "l2":
            raise ValueError(
                f"the quantized tier supports metric='l2' only, got "
                f"{cfg.metric!r}; use the fp32 host engines "
                f"(JoinConfig(quantize=...) rejects this combination at "
                f"construction)")
        super().__init__(index, config, bucket_min=bucket_min)
        self.impl = impl
        k = self.config.k
        if slack is None:
            slack = self.config.quant_slack
        explicit = (slack is not None and slack >= 0) or impl is not None

        # ---- tuning-table lookup (measured mode / mp / tile shapes)
        tuned: Optional[TunedConfig] = None
        if isinstance(tune, TunedConfig):
            tuned = tune
        elif tune == "auto" and not explicit:
            tuned = autotune.lookup(index.dim, index.n_s, k)
        self.tuned = tuned
        self.autotuned = tuned is not None
        self.mode = ("fp32" if tuned is not None and tuned.mode == "fp32"
                     and not explicit else "int8")

        if slack is not None and slack >= 0:
            self.mp = next_pow2(max(k + int(slack), k, 1))
        elif tuned is not None and tuned.mp:
            self.mp = max(next_pow2(tuned.mp), next_pow2(k))
        else:
            # auto: certification needs the shortlist boundary to clear
            # the k-th neighbor by ~2·(ε_s + ε_q); on concentrated
            # high-dim data that takes a rank gap of ~10×k (the
            # kernel_quant_coarse_vs_fp32 bench pins certified_frac=1.0
            # here, vs 0.05 at a bare 2k shortlist)
            self.mp = max(next_pow2(4 * k), 128)
        if tune_bn:
            self._bn = int(tune_bn)
        elif self.mode == "int8" and tuned is not None and tuned.bn:
            self._bn = int(tuned.bn)
        if self.mode == "int8" and tuned is not None and tuned.bm:
            self._bm_cap = int(tuned.bm)

        # ---- residency: fused device re-rank vs host-gather fallback
        if self.mode == "fp32":
            self.resident = False      # plain megastep; rows resident anyway
        elif resident is not None:
            self.resident = bool(resident)
        else:
            # per-*device* fit: the sharded engine overrides
            # _resident_fit_rows with its largest shard, so sharding the
            # index across a mesh unlocks residency the whole-index
            # byte count would refuse
            self.resident = (
                resident_extra_bytes(self._resident_fit_rows(), index.dim)
                <= _RESIDENT_MAX_BYTES)

        if self.mode == "fp32":
            # the parent engine's defaults: fp32 rows + ids on device
            self._upload_fp32 = True
            self._upload_ids = True
        elif self.resident:
            # int8 codes for the coarse pass + fp32 rows and the (hi, lo)
            # id pair for the fused on-device re-rank
            self._upload_fp32 = True
            self._upload_ids = True
        else:
            self._upload_fp32 = False
            self._upload_ids = False   # ids resolve host-side via gids

    def _resident_fit_rows(self) -> int:
        """Row count the resident-re-rank HBM fit check sizes against.
        The single-device engine must fit the whole index; the sharded
        engine overrides this with its largest shard's row count —
        what actually lands on one device."""
        return int(self.index.n_s)

    # ---- device payload: int8 codes + scales + ε (+ fp32 rows when the
    # re-rank is device-resident)

    def _build_struct(self, segs, bn: int, k: int) -> dict:
        import jax.numpy as jnp

        st = super()._build_struct(segs, bn, k)
        if self.mode == "fp32":
            return st                  # no quant twin needed at all
        q_parts, sc_parts, eps_parts = [], [], []
        for si, _off in segs:
            qr = si.ensure_quant(bn)
            q_parts.append(qr.q)
            sc_parts.append(qr.scales)
            eps_parts.append(qr.eps)
        st["tiles_dev"]["sq"] = jnp.asarray(np.concatenate(q_parts, axis=0))
        st["tiles_dev"]["sscale"] = jnp.asarray(np.concatenate(sc_parts))
        # ε stays f16-resident (2 bytes/row); upcast is in-graph
        st["tiles_dev"]["seps"] = jnp.asarray(np.concatenate(eps_parts))
        return st

    # ---- two-tier query path

    def _coarse_args(self, q_dev):
        payload = self._refresh()
        bucket = int(q_dev.shape[0])
        bm = min(bucket, self._bm_cap)
        impl = self.resolved_impl
        return payload, dict(
            mp=self.mp, k=self.config.k, bm=bm, bn=self._bn,
            metric=self.config.metric, dim=payload.dim,
            n_finite_total=payload.n_finite_total,
            seg_meta=payload.seg_meta, primary=payload.primary, impl=impl)

    def coarse_shortlist(self, queries: np.ndarray):
        """The int8 pass alone: ``(lb, pos, ids)`` for one batch —
        ascending certified lower bounds, packed-row positions and their
        global ids (−1 on empty slots). Exposed for benches/tests; the
        exact path is :meth:`join_batch`."""
        if self.mode == "fp32":
            raise RuntimeError(
                "this engine was tuned to mode='fp32' (int8 measured "
                "slower on this shape/backend) — there is no coarse "
                "pass; force int8 with an explicit slack= or impl=")
        q = np.ascontiguousarray(queries, np.float32)
        n = q.shape[0]
        qd, nv = self.enqueue(q)
        payload, kw = self._coarse_args(qd)
        with obs.span("quant.coarse", rows=n, mp=self.mp,
                      mode=self.mode):
            lb, pos = _quant_coarse(qd, nv, payload.dead_total,
                                    payload.segs, payload.tiles, **kw)
            from repro.serve import faultinject
            faultinject.fire("megastep.fetch")  # simulated lost fetch
            lb = np.asarray(lb)[:n]
            pos = np.asarray(pos)[:n]
        # chaos site: deflating the certified lower bounds is exactly
        # what inflated ε would do — downstream certification then fails
        # and the fp32-oracle fallback must keep the output bitwise
        lb = faultinject.transform_value("quant.eps_inflation", lb)
        gids = self._struct[1]["gids"]
        ids = np.where(pos >= 0,
                       gids[np.clip(pos, 0, gids.shape[0] - 1)], -1)
        return lb, pos, ids

    def join_batch_device(self, q_dev, n_valid_dev, *, state=None):
        """Resident mode: the zero-host-transfer steady-state call —
        device ``(dists, id_hi, id_lo, lm)`` out of one fused jitted
        megastep (note the extra ``lm`` certification bound vs the fp32
        parent's 3-tuple). fp32 mode delegates to the parent. The
        host-gather variant has no device-only form (its re-rank *is* a
        host round-trip)."""
        if self.mode == "fp32":
            return super().join_batch_device(q_dev, n_valid_dev,
                                             state=state)
        if not self.resident:
            raise NotImplementedError(
                "the host-gather quantized variant re-ranks via a "
                "host-side shortlist gather (its fp32 rows are "
                "deliberately not device-resident) — use join_batch, or "
                "resident=True for the fused device path")
        if state is not None:
            raise NotImplementedError(
                "carried-state merge is the fp32 megastep's API; the "
                "quant megastep emits a fresh certified run per batch")
        payload, kw = self._coarse_args(q_dev)
        return _quant_megastep(q_dev, n_valid_dev, payload.dead_total,
                               payload.segs, payload.tiles, **kw)

    # ---- dispatch / finalize (the scheduler's double-buffering API)

    def dispatch(self, queries: np.ndarray, *,
                 stats: Optional[JoinStats] = None) -> JoinHandle:
        q = self._validated_queries(queries)
        n = q.shape[0]
        if stats is not None:
            stats.quant_mode = self.mode
            stats.quant_mp = self.mp if self.mode == "int8" else 0
            stats.quant_autotuned = self.tuned is not None
        if self.mode == "fp32":
            return super().dispatch(q, stats=stats)
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
        if self.resident:
            d, hi, lo, lm = self.join_batch_device(qd, nv)
            return JoinHandle(kind="quant_resident", n=n,
                              dev=(d, hi, lo, lm), q=q)
        payload, kw = self._coarse_args(qd)
        lb, pos = _quant_coarse(qd, nv, payload.dead_total, payload.segs,
                                payload.tiles, **kw)
        return JoinHandle(kind="quant_host", n=n, dev=(lb, pos), q=q)

    def finalize(self, handle: JoinHandle, *,
                 stats: Optional[JoinStats] = None
                 ) -> tuple[np.ndarray, np.ndarray]:
        if handle.kind in ("empty", "mega"):
            return super().finalize(handle, stats=stats)
        k = self.config.k
        if handle.kind == "quant_resident":
            out_d, out_i, lm = self._fetch_resident(handle)
            if stats is not None:
                stats.n_resident_rerank += handle.n
        elif handle.kind == "quant_host":
            out_d, out_i, lm = self._finalize_host_shortlist(handle)
            if stats is not None:
                stats.n_host_rerank += handle.n
        else:
            raise ValueError(f"cannot finalize handle kind {handle.kind!r}")
        # certification: excluded coarse candidates all carry lb ≥ the
        # run's last (largest) slot; +inf there means nothing was
        # excluded at all. τ̂ is the exact reported k-th distance.
        tau = out_d[:, k - 1]
        bad = ~(lm >= tau)                   # NaN-safe: fail on weirdness
        if bad.any():
            # device fetches are zero-copy read-only views; only the
            # fallback writeback pays a copy, and only when it happens
            if not out_d.flags.writeable:
                out_d = out_d.copy()
            if not out_i.flags.writeable:
                out_i = out_i.copy()
            n_bad = int(bad.sum())
            with obs.span("quant.fallback", rows=n_bad, mode=self.mode,
                          mp=self.mp):
                fb_d, fb_i = self._oracle_join(handle.q[bad])
            out_d[bad] = fb_d
            out_i[bad] = fb_i
            obs.metrics.REGISTRY.counter("quant_fallback_total") \
                .inc(n_bad)
            if stats is not None:
                stats.n_quant_fallback += n_bad
        return out_d, out_i

    # join_batch is inherited: finalize(dispatch(q)) routes through the
    # overrides above, so fp32 / resident / host-gather all share it.

    def _fetch_resident(self, handle: JoinHandle):
        """Fetch + finish a fused resident batch: ``(out_d, out_i, lm)``
        — exact top-k plus the per-query exclusion bound, numpy-side."""
        from repro.serve import faultinject
        faultinject.fire("megastep.fetch")     # simulated lost fetch
        n = handle.n
        d, hi, lo, lm = handle.dev
        out_d = np.ascontiguousarray(np.asarray(d)[:n])
        out_i = np.ascontiguousarray(
            ((np.asarray(hi, np.int64) << 32)
             | (np.asarray(lo, np.int64) & np.int64(0xFFFFFFFF)))[:n])
        lm = np.asarray(lm)[:n]
        # chaos site (same contract as coarse_shortlist): deflated bounds
        # force certification failures downstream
        lm = faultinject.transform_value("quant.eps_inflation", lm)
        return out_d, out_i, lm

    def _finalize_host_shortlist(self, handle: JoinHandle):
        """Host half of the host-gather variant: fetch the coarse
        shortlist, gather fp32 rows host-side, canonical re-rank.
        Returns ``(out_d, out_i, lm)``."""
        from repro.serve import faultinject
        faultinject.fire("megastep.fetch")     # simulated lost fetch
        n = handle.n
        k = self.config.k
        lb = np.asarray(handle.dev[0])[:n]
        pos = np.asarray(handle.dev[1])[:n]
        lb = faultinject.transform_value("quant.eps_inflation", lb)
        gids = self._struct[1]["gids"]
        ids = np.where(pos >= 0,
                       gids[np.clip(pos, 0, gids.shape[0] - 1)], -1)
        rows_host = self._struct[1]["rows_host"]
        neigh = rows_host[np.clip(pos, 0, rows_host.shape[0] - 1)]
        d_all, ids_all = canonical_topk(handle.q, ids, neigh,
                                        self.config.metric)
        return (np.ascontiguousarray(d_all[:, :k]),
                np.ascontiguousarray(ids_all[:, :k]), lb[:, -1])

    def join_batch_approx(
        self, queries: np.ndarray, *, stats: Optional[JoinStats] = None,
    ) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
        """Coarse-only certified-*approximate* join — the serving
        scheduler's degraded rung. Same coarse shortlist + exact re-rank
        as :meth:`join_batch`, but certification failures do **not**
        re-run through the fp32 oracle; instead every query reports a
        *certified recall lower bound* derived from the ε machinery.

        Returns ``(dists, ids, recall_bound)`` with ``recall_bound[i] =
        #{j : dists[i, j] ≤ lm_i} / k``, where ``lm_i`` bounds every
        excluded row's true distance from below. A reported neighbor
        with distance ≤ lm has global rank ≤ its shortlist rank ≤ k, so
        it provably belongs to the true top-k — the bound counts only
        such neighbors and is therefore sound, never optimistic. An
        unfilled shortlist (lm = +inf) excluded nothing: the result is
        exact and the bound is 1. Reported distances are always exact
        (the re-rank is fp32-canonical); only *membership* of the true
        top-k is approximate. In fp32 mode there is no shortlist — the
        exact scan runs and the bound is exactly 1.
        """
        q = self._validated_queries(queries)
        n = q.shape[0]
        k = self.config.k
        if n == 0:
            return (np.zeros((0, k), np.float32),
                    np.full((0, k), -1, np.int64),
                    np.ones((0,), np.float32))
        if self.mode == "fp32":
            out_d, out_i = self.join_batch(q, stats=stats)
            recall = np.ones((n,), np.float32)
        else:
            handle = self.dispatch(q, stats=stats)
            if handle.kind == "quant_resident":
                out_d, out_i, lm = self._fetch_resident(handle)
                if stats is not None:
                    stats.n_resident_rerank += n
            else:
                out_d, out_i, lm = self._finalize_host_shortlist(handle)
                if stats is not None:
                    stats.n_host_rerank += n
            with np.errstate(invalid="ignore"):
                proven = out_d <= lm[:, None]      # NaN-safe: counts False
            recall = proven.sum(axis=1).astype(np.float32) / np.float32(k)
        if stats is not None:
            stats.n_degraded += n
            stats.recall_bound = min(stats.recall_bound,
                                     float(recall.min()))
        return out_d, out_i, recall

    def _oracle_join(self, q: np.ndarray):
        """The fp32 host-planned oracle for certification failures —
        reports through the same canonical distance graph, so patched
        rows are bitwise what a full oracle run would emit."""
        from repro.core.api import execute_join
        from repro.core.index import plan_queries
        from repro.core.segments import MutableIndex

        if isinstance(self.index, MutableIndex):
            return self.index.join_batch(q, config=self.config)
        return execute_join(
            q, self.index, plan_queries(q, self.index, self.config))


# ---------------------------------------------------------------------------
# the sharded quantized engine (core.sharded holds the fp32 twin)


@functools.partial(
    jax.jit,
    static_argnames=("mesh", "n_shards", "mp", "k", "bm", "bn", "metric",
                     "dim", "n_finite_total", "seg_meta", "primary",
                     "impl"))
def _sharded_quant_megastep(q, n_valid, dead_total, segs, tiles, *,
                            mesh, n_shards: int, mp: int, k: int, bm: int,
                            bn: int, metric: str, dim: int,
                            n_finite_total: int, seg_meta: tuple,
                            primary: int, impl: str):
    """`_quant_megastep` under shard_map: per-shard int8 coarse shortlist
    (against the shard's codes/ε and its compacted schedule) → local
    fused fp32 exact re-rank → all-gather + tree merge of the kp-runs,
    with the certification exclusion bound combined across shards via
    ``lax.pmin``. Soundness: a row shard j excluded from its shortlist
    carries lb ≥ lm_j ≥ min_j lm_j, so the merged k-th distance checked
    against the pmin bound certifies exactly like the single-device
    engine; a shortlisted row dropped at local rank > kp has *exact*
    distance ≥ the shard's kp-th ≥ the merged k-th. Returns
    ``(d (B, k), id_hi, id_lo, lm (B,))`` replicated across shards.
    """
    _bump_trace()

    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from repro.kernels.sorted_merge import tree_merge_runs

    kp = next_pow2(k)

    @jax.shard_map(mesh=mesh,
                   in_specs=(P(), P(), P(), *_mesh_specs(segs, tiles)),
                   out_specs=(P(), P(), P(), P()), check_vma=False)
    def body(q, n_valid, dead_total, segs, tiles):
        segs, tiles = _strip_shard(segs, tiles)
        lb, pos = _quant_coarse_body(
            q, n_valid, dead_total, segs, tiles, mp=mp, k=k, bm=bm,
            bn=bn, metric=metric, n_finite_total=n_finite_total,
            seg_meta=seg_meta, primary=primary, impl=impl)
        # local exact re-rank (stage 5 of _quant_megastep) over the
        # shard's mp shortlist, kept kp wide for the cross-shard merge
        s = tiles["s"]
        pos_c = jnp.clip(pos, 0, s.shape[0] - 1)
        d_can = canonical_gathered(q, s[pos_c], metric)
        valid_sel = pos >= 0
        d_can = jnp.where(valid_sel, d_can, jnp.inf)
        hi = jnp.where(valid_sel, tiles["id_hi"][pos_c], -1)
        lo = jnp.where(valid_sel, tiles["id_lo"][pos_c], -1)
        order = jnp.argsort(d_can, axis=1, stable=True)
        d_can = jnp.take_along_axis(d_can, order, axis=1)[:, :kp]
        hi = jnp.take_along_axis(hi, order, axis=1)[:, :kp]
        lo = jnp.take_along_axis(lo, order, axis=1)[:, :kp]
        lm = lb[:, -1]
        if n_shards > 1:
            gd = jax.lax.all_gather(d_can, "shard")
            ghi = jax.lax.all_gather(hi, "shard")
            glo = jax.lax.all_gather(lo, "shard")
            d_can, (hi, lo) = tree_merge_runs(
                [(gd[j], (ghi[j], glo[j])) for j in range(n_shards)])
            lm = jax.lax.pmin(lm, "shard")
        return d_can[:, :k], hi[:, :k], lo[:, :k], lm

    return body(q, n_valid, dead_total, segs, tiles)


class ShardedQuantMegastepEngine(_ShardedPayloadMixin, QuantMegastepEngine):
    """`QuantMegastepEngine` over a 1-D "shard" mesh: int8 codes + ε +
    fp32 rows partitioned by `SIndex.shard_packing`, coarse scan and
    exact re-rank per shard, certification combined in-mesh. The
    dispatch()/finalize() surface, the certification + fp32-oracle
    fallback, and `join_batch_approx` are inherited unchanged — only
    the device call underneath them is sharded.

    The resident fit check is per shard (`_resident_fit_rows`), so an
    index too large for one device's ``REPRO_QUANT_RESIDENT_MAX_BYTES``
    becomes resident once spread across enough shards. Sharded int8
    **requires** residency: the host-gather fallback is a host
    round-trip by construction, the exact opposite of what the mesh is
    for.
    """

    def __init__(self, index, config: Optional[JoinConfig] = None, *,
                 n_shards: Optional[int] = None, mesh=None,
                 slack: Optional[int] = None, bucket_min: int = 16,
                 impl: Optional[str] = None,
                 resident: Optional[bool] = None,
                 tune="auto", tune_bn: Optional[int] = None):
        # mesh first: _resident_fit_rows needs n_shards during the
        # parent's residency decision
        self._init_mesh(n_shards, mesh)
        super().__init__(index, config, slack=slack, bucket_min=bucket_min,
                         impl=impl, resident=resident, tune=tune,
                         tune_bn=tune_bn)
        if self.mode == "int8" and not self.resident:
            raise ValueError(
                f"the sharded int8 engine is resident-only, but the "
                f"largest shard ({self._resident_fit_rows()} rows) "
                f"exceeds REPRO_QUANT_RESIDENT_MAX_BYTES "
                f"({_RESIDENT_MAX_BYTES}); add shards, raise the cap, or "
                f"use the single-device host-gather QuantMegastepEngine")

    def _resident_fit_rows(self) -> int:
        # the largest shard's row count across live segments — also
        # warms the shard packings the payload build reuses
        segs, _, _ = self._index_parts()
        per = np.zeros((self.n_shards,), np.int64)
        for si, _ in segs:
            per += si.shard_packing(self.n_shards, self._bn).rows_per_shard
        return int(per.max()) if per.size else 0

    def coarse_shortlist(self, queries: np.ndarray):
        raise NotImplementedError(
            "coarse_shortlist is the single-device debugging surface; "
            "the sharded engine's coarse pass never leaves the mesh — "
            "use join_batch / join_batch_approx")

    def join_batch_device(self, q_dev, n_valid_dev, *, state=None):
        if self.mode == "fp32":
            # tuned fp32 fallback, still sharded: the parent's delegation
            # would reach the single-device megastep against a mesh
            # payload — route to the sharded fp32 jit instead. Its 4th
            # output (the degraded-coverage bound) is dropped: this
            # engine never marks shards failed, so it is constant +inf,
            # and our consumer (MegastepEngine.dispatch) expects triples
            d, hi, lo, _lm = self._sharded_fp32_call(q_dev, n_valid_dev,
                                                     state)
            return d, hi, lo
        if state is not None:
            raise NotImplementedError(
                "carried-state merge is the fp32 megastep's API; the "
                "quant megastep emits a fresh certified run per batch")
        payload, kw = self._coarse_args(q_dev)
        return _sharded_quant_megastep(
            q_dev, n_valid_dev, payload.dead_total, payload.segs,
            payload.tiles, mesh=self.mesh, n_shards=self.n_shards, **kw)
