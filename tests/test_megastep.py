"""Fused device-resident query megastep (core.megastep): bitwise equality
with the host-planned oracle across reducers / index kinds / ragged
splits, bucketed compile reuse (no re-plan, no recompile on repeating
batch shapes), and the zero-host-transfer steady state."""
import numpy as np
import pytest

import repro.core.megastep as M
from repro.core import (
    JoinConfig, MegastepEngine, MutableIndex, StreamJoinEngine,
    brute_force_knn, build_index, compact_visit_mask, compact_visits_jnp,
    knn_join, knn_join_batched)


def _data(n, dim, seed, scale=3.0, offset=0.0):
    rng = np.random.default_rng(seed)
    return (rng.normal(size=(n, dim)).astype(np.float32) * scale
            + np.float32(offset))


def _ids64(hi, lo):
    return ((np.asarray(hi, np.int64) << 32)
            | (np.asarray(lo, np.int64) & np.int64(0xFFFFFFFF)))


def _mutable_with_history(dim=5, seed=0, k=6):
    """base + sealed delta + unsealed buffer + tombstones (more than k
    dead in one neighborhood, exercising the widened θ)."""
    rng = np.random.default_rng(seed)
    cfg = JoinConfig(k=k, n_pivots=16, n_groups=4, seed=seed)
    mi = MutableIndex.build(_data(700, dim, seed + 1), cfg,
                            seal_threshold=300)
    mi.insert(_data(340, dim, seed + 2))          # seals a delta segment
    mi.insert(_data(90, dim, seed + 3))           # stays in the buffer
    mi.delete(rng.choice(700, 3 * k + 20, replace=False))
    return mi, cfg


@pytest.mark.parametrize("reducer", ["dense", "pruned", "gather"])
def test_megastep_matches_host_sindex(reducer):
    """Acceptance: distances and int64 ids of the megastep are bitwise
    the host-planned path's, for every host reducer engine."""
    r = _data(217, 6, 0)
    s = _data(530, 6, 1)
    cfg = JoinConfig(k=7, n_pivots=24, n_groups=5, seed=3, reducer=reducer)
    index = build_index(s, cfg)
    host = knn_join(r, config=cfg, index=index)
    bd, _ = brute_force_knn(r, s, 7)
    np.testing.assert_allclose(host.distances, bd, atol=1e-4)
    mega = knn_join(r, config=cfg, index=index, megastep=True)
    np.testing.assert_array_equal(mega.distances, host.distances)
    np.testing.assert_array_equal(mega.indices, host.indices)
    assert mega.indices.dtype == np.int64


@pytest.mark.parametrize("reducer", ["dense", "pruned", "gather"])
def test_megastep_matches_host_mutable_tombstones(reducer):
    """MutableIndex fan-out (base + delta + buffer, > k tombstones) in
    one megastep call == the host per-segment adaptive-over-fetch path."""
    import dataclasses

    mi, cfg = _mutable_with_history(seed=11)
    cfg = dataclasses.replace(cfg, reducer=reducer)
    q = _data(143, 5, 99)
    host = knn_join(q, config=cfg, index=mi)
    mega = knn_join(q, config=cfg, index=mi, megastep=True)
    np.testing.assert_array_equal(mega.distances, host.distances)
    np.testing.assert_array_equal(mega.indices, host.indices)


def test_megastep_ragged_splits_bitwise():
    """Any micro-batch split through the megastep equals the one-shot
    host join — including final ragged batches of every size."""
    r = _data(201, 5, 4)
    s = _data(460, 5, 5)
    cfg = JoinConfig(k=5, n_pivots=16, n_groups=4, seed=1)
    index = build_index(s, cfg)
    one = knn_join(r, config=cfg, index=index)
    for bs in (201, 64, 33, 7):
        res = knn_join_batched(r, index=index, config=cfg, batch_size=bs,
                               megastep=True)
        np.testing.assert_array_equal(res.distances, one.distances)
        np.testing.assert_array_equal(res.indices, one.indices)


def test_megastep_far_from_origin_selection():
    """The shared-center selection math stays exact on data far from the
    origin (the cancellation regime cmp_dist centers against)."""
    r = _data(90, 4, 6, offset=50.0)
    s = _data(300, 4, 7, offset=50.0)
    cfg = JoinConfig(k=4, n_pivots=12, n_groups=3)
    index = build_index(s, cfg)
    host = knn_join(r, config=cfg, index=index)
    mega = knn_join(r, config=cfg, index=index, megastep=True)
    np.testing.assert_array_equal(mega.distances, host.distances)
    np.testing.assert_array_equal(mega.indices, host.indices)


def test_megastep_rejects_non_l2():
    index = build_index(_data(60, 3, 8),
                        JoinConfig(k=3, metric="l1", n_pivots=8))
    with pytest.raises(ValueError, match="l2"):
        MegastepEngine(index)
    # "auto" falls back to the host path instead of raising
    eng = StreamJoinEngine(index, megastep="auto")
    assert eng.megastep_engine is None


def test_no_recompile_across_identical_ragged_batches():
    """Satellite: a repeating ragged batch size re-pads into the same
    bucket and hits the jit cache — zero traces after the first; a
    *different* ragged size in the same bucket also re-traces nothing."""
    s = _data(400, 5, 9)
    cfg = JoinConfig(k=5, n_pivots=16, n_groups=4)
    engine = StreamJoinEngine(build_index(s, cfg), cfg, megastep=True)
    engine.join_batch(_data(77, 5, 10))       # warm the (128,)-bucket step
    c0 = M.trace_count()
    for i in range(3):
        engine.join_batch(_data(77, 5, 20 + i))
    assert M.trace_count() == c0, "identical ragged batches re-traced"
    engine.join_batch(_data(70, 5, 30))       # same bucket, different size
    assert M.trace_count() == c0, "bucket-mate batch size re-traced"
    engine.join_batch(_data(130, 5, 31))      # new bucket: may trace once
    assert M.trace_count() <= c0 + 1


class _fetch_counter:
    """Counts device→host conversions (np.asarray / np.array over a
    jax.Array — the fetch path this codebase uses; ArrayImpl is a C type
    and cannot be instrumented directly)."""

    def __enter__(self):
        import jax

        self._asarray, self._array = np.asarray, np.array
        self.count = 0

        def wrap(fn):
            def inner(obj=None, *a, **kw):
                if isinstance(obj, jax.Array):
                    self.count += 1
                return fn(obj, *a, **kw)
            return inner

        np.asarray = wrap(self._asarray)
        np.array = wrap(self._array)
        return self

    def __exit__(self, *exc):
        np.asarray, np.array = self._asarray, self._array
        return False


def test_megastep_zero_host_transfers_steady_state():
    """Acceptance: between input enqueue and result fetch a steady-state
    megastep call performs zero host transfers — pinned two ways: the
    JAX transfer guard (catches any host→device re-upload) and a
    device→host fetch counter (proved non-vacuous on the host path)."""
    import jax

    s = _data(500, 6, 12)
    cfg = JoinConfig(k=5, n_pivots=16, n_groups=4)
    index = build_index(s, cfg)
    eng = MegastepEngine(index, cfg)
    qd, nv = eng.enqueue(_data(100, 6, 13))
    jax.block_until_ready(eng.join_batch_device(qd, nv))   # warm + upload

    # sanity: the counter sees the host-planned path's fetches
    with _fetch_counter() as fc:
        StreamJoinEngine(index, cfg).join_batch(_data(100, 6, 13))
    assert fc.count > 0, "fetch counter is vacuous"

    with _fetch_counter() as fc:
        with jax.transfer_guard("disallow"):
            out = eng.join_batch_device(qd, nv)
            jax.block_until_ready(out)
    assert fc.count == 0, f"steady state fetched {fc.count} arrays"
    # the result is still the exact join once fetched
    host = knn_join(_data(100, 6, 13), config=cfg, index=eng.index)
    d, hi, lo = out
    np.testing.assert_array_equal(np.asarray(d)[:100], host.distances)
    np.testing.assert_array_equal(_ids64(hi, lo)[:100], host.indices)


def test_megastep_device_state_merge_dedups():
    """The carried-state merge is the dedup sorted-run merge: revisiting
    the same queries with overlapping candidates keeps each row once."""
    import jax

    s = _data(420, 5, 14)
    cfg = JoinConfig(k=6, n_pivots=16, n_groups=4)
    eng = MegastepEngine(build_index(s, cfg), cfg)
    q = _data(80, 5, 15)
    qd, nv = eng.enqueue(q)
    first = eng.join_batch_device(qd, nv)
    merged = eng.join_batch_device(qd, nv, state=first)
    jax.block_until_ready(merged)
    host = knn_join(q, config=cfg, index=eng.index)
    d, hi, lo = merged
    np.testing.assert_array_equal(np.asarray(d)[:80], host.distances)
    np.testing.assert_array_equal(_ids64(hi, lo)[:80], host.indices)


def test_serving_engine_survives_mutations():
    """One resident engine absorbs insert/seal/delete through the index
    version — results always match a fresh host join."""
    mi, cfg = _mutable_with_history(seed=21)
    eng = StreamJoinEngine(mi, cfg, megastep=True)
    q = _data(60, 5, 77)   # seed disjoint from the index rows: coincident
    # rows create exact distance ties, whose order is documented as
    # unspecified between engines (core.segments docstring)
    for step in range(3):
        d, ids = eng.join_batch(q)
        host = knn_join(q, config=cfg, index=mi)
        np.testing.assert_array_equal(d, host.distances)
        np.testing.assert_array_equal(ids, host.indices)
        if step == 0:
            mi.insert(_data(120, 5, 123))  # fresh rows (a repeated seed
            # would duplicate existing coordinates → exact-tie ids)
        elif step == 1:
            alive = np.setdiff1d(np.arange(mi._next_id),
                                 mi.tombstones_sorted())
            mi.delete(alive[:: max(1, alive.size // 10)][:10])


@pytest.mark.parametrize("impl", ["ref_sched", "pallas_interpret"])
def test_megastep_impl_variants_match_host(impl):
    """The schedule-consuming execution variants — the lax.scan twin and
    the real Pallas kernel body (interpret mode) — walk the in-jit
    concatenated schedule and still reproduce the host path bitwise:
    the visit lists lowered on device lose no true neighbor."""
    mi, cfg = _mutable_with_history(seed=41, k=5)
    q = _data(97, 5, 55)
    host = knn_join(q, config=cfg, index=mi)
    eng = MegastepEngine(mi, cfg, impl=impl)
    d, ids = eng.join_batch(q)
    np.testing.assert_array_equal(d, host.distances)
    np.testing.assert_array_equal(ids, host.indices)


@pytest.mark.parametrize("n_q", [97, 128], ids=["ragged", "full"])
def test_gather_counts_scan_twin_matches_kernel(n_q):
    """`gather_counts`: the scan twin and the Pallas kernel body
    (interpret) count the same merged steps, padding rows of a ragged
    bucket included; visited is `tile_counts`' and "ref" counts with the
    scan twin. S is integer rows and their negatives, so the shared
    center is 0 and both walkers see the same squared distances."""
    rng = np.random.default_rng(7)
    half = rng.integers(-20, 21, (600, 4)).astype(np.float32)
    s = np.concatenate([half, -half])
    cfg = JoinConfig(k=5, n_pivots=16, n_groups=4, seed=1, tile_r=32,
                     tile_s=64)
    index = build_index(s, cfg)
    q = rng.integers(-20, 21, (n_q, 4)).astype(np.float32)
    got = {impl: MegastepEngine(index, cfg, impl=impl).gather_counts(q)
           for impl in ("ref", "ref_sched", "pallas_interpret")}
    assert got["ref_sched"] == got["pallas_interpret"] == got["ref"]
    merged, visited = got["ref_sched"]
    assert visited == MegastepEngine(index, cfg).tile_counts(q)[0]
    assert 0 < merged < visited


def test_buffer_segment_cache_survives_compact_reinsert():
    """Regression: ``compact()`` re-bases ``_next_id`` downward, so a
    post-compact write buffer can reproduce the ephemeral buffer-segment
    cache key of a pre-compact buffer while holding different rows —
    the snapshot must not serve the stale index."""
    cfg = JoinConfig(k=3, n_pivots=8, n_groups=2)
    mi = MutableIndex.build(_data(40, 4, 60), cfg, seal_threshold=1 << 30)
    first = _data(5, 4, 61)
    ids = mi.insert(first)                    # buffered, key (45, 5)
    # a megastep query builds + caches the ephemeral buffer index
    knn_join(_data(8, 4, 62), config=cfg, index=mi, megastep=True)
    assert mi._buffer_seg is not None
    mi.delete(ids)
    mi.compact()                              # next_id back to 40
    second = _data(5, 4, 63) + 100.0          # same key (45, 5), new rows
    mi.insert(second)
    q = second[:4] + 0.01
    host = knn_join(q, config=cfg, index=mi)
    assert np.all(host.distances[:, 0] < 1.0), "stale buffer index served"
    mega = knn_join(q, config=cfg, index=mi, megastep=True)
    np.testing.assert_array_equal(mega.distances, host.distances)
    np.testing.assert_array_equal(mega.indices, host.indices)


def test_compact_visits_jnp_matches_host_compaction():
    """The in-jit segment-sum-rank + flat-scatter compaction reproduces
    the host `compact_visit_mask` (schedule prefix, counts, repeat-last
    padding); all-empty rows get the fallback tile-0 visit."""
    import jax.numpy as jnp

    rng = np.random.default_rng(33)
    for trial in range(5):
        visit = rng.random((6, 11)) < 0.3
        visit[2] = False                       # an all-empty row
        sched_j, cnt_j = compact_visits_jnp(jnp.asarray(visit))
        sched_j, cnt_j = np.asarray(sched_j), np.asarray(cnt_j)
        host_visit = visit.copy()
        host_visit[~host_visit.any(axis=1), 0] = True   # documented fallback
        sched_h, cnt_h = compact_visit_mask(host_visit,
                                            max_visits=visit.shape[1])
        np.testing.assert_array_equal(cnt_j, cnt_h)
        np.testing.assert_array_equal(sched_j, sched_h)


def test_bench_regression_guard_logic():
    """The CI guard trips on >2× regressions of the guarded rows and on
    any nonzero steady-state host-sync count, and passes otherwise."""
    import importlib.util
    import pathlib

    path = (pathlib.Path(__file__).resolve().parent.parent
            / "benchmarks" / "guard.py")
    spec = importlib.util.spec_from_file_location("bench_guard", path)
    guard = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(guard)

    base = [
        {"bench": "kernel_streaming_vs_oneshot", "overhead_frac": 0.05},
        {"bench": "kernel_index_build_amortization",
         "plan_frac_of_batch": 0.10},
        {"bench": "kernel_megastep_vs_hostplanned", "speedup": 10.0,
         "device_steady_state_syncs": 0.0},
    ]
    ok = [
        {"bench": "kernel_streaming_vs_oneshot", "overhead_frac": 0.12},
        {"bench": "kernel_index_build_amortization",
         "plan_frac_of_batch": 0.15},
        {"bench": "kernel_megastep_vs_hostplanned", "speedup": 6.0,
         "device_steady_state_syncs": 0.0},
    ]
    assert guard.check(base, ok) == []
    bad_overhead = [dict(ok[0], overhead_frac=0.5)] + ok[1:]
    assert any("overhead_frac" in f for f in guard.check(base, bad_overhead))
    bad_speedup = ok[:2] + [dict(ok[2], speedup=1.0)]
    assert any("speedup" in f for f in guard.check(base, bad_speedup))
    bad_syncs = ok[:2] + [dict(ok[2], device_steady_state_syncs=3.0)]
    assert any("zero host syncs" in f for f in guard.check(base, bad_syncs))
    missing = ok[1:]   # a guarded row vanished from the sweep
    assert any("missing" in f for f in guard.check(base, missing))
    # a negative baseline (streaming beat one-shot outright) keeps a
    # sane absolute limit (the slack) instead of a nonsensical negative
    # 2x bound: small positive drift passes, a real regression fails
    neg = [dict(base[0], overhead_frac=-0.9)] + base[1:]
    drift = [dict(ok[0], overhead_frac=0.05)] + ok[1:]
    assert guard.check(neg, drift) == []
    assert any("overhead_frac" in f for f in guard.check(neg, bad_overhead))


def test_hypothesis_property_megastep_bitwise():
    pytest.importorskip(
        "hypothesis", reason="property tests need hypothesis; tier-1 must "
        "still collect on clean environments without it")
    from hypothesis import given, settings, strategies as st

    @st.composite
    def instance(draw):
        n_r = draw(st.integers(10, 90))
        n_s = draw(st.integers(40, 160))
        k = draw(st.integers(1, 8))
        bs = draw(st.integers(1, n_r))
        n_del = draw(st.integers(0, 12))
        seed = draw(st.integers(0, 2**16))
        return n_r, n_s, k, bs, n_del, seed

    @given(instance())
    @settings(max_examples=10, deadline=None)
    def prop(inst):
        n_r, n_s, k, bs, n_del, seed = inst
        rng = np.random.default_rng(seed)
        r = rng.normal(size=(n_r, 5)).astype(np.float32) * 3
        s = rng.normal(size=(n_s, 5)).astype(np.float32) * 3
        cfg = JoinConfig(k=k, n_pivots=16, n_groups=4, seed=seed)
        mi = MutableIndex.build(s, cfg, seal_threshold=1 << 30)
        if n_del and n_s - n_del >= k:
            mi.delete(rng.choice(n_s, n_del, replace=False))
        host = knn_join(r, config=cfg, index=mi)
        res = knn_join_batched(r, index=mi, config=cfg, batch_size=bs,
                               megastep=True)
        np.testing.assert_array_equal(res.distances, host.distances)
        np.testing.assert_array_equal(res.indices, host.indices)

    prop()
