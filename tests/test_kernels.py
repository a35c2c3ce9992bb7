"""Pallas kernels vs pure-jnp oracles — shape/dtype sweeps in interpret
mode (the kernel body runs in Python on CPU; on TPU the same body runs
compiled)."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.kernels import ops, ref


@pytest.mark.parametrize("nr,ns,dim,k", [
    (64, 128, 8, 4),
    (100, 257, 10, 7),     # non-tile-aligned
    (128, 512, 2, 10),     # paper's OSM dimensionality
    (33, 70, 54, 5),       # forest-width features
    (16, 2048, 16, 25),    # many tiles, k large
])
def test_distance_topk_shapes(nr, ns, dim, k):
    rng = np.random.default_rng(nr * ns)
    r = jnp.asarray(rng.normal(size=(nr, dim)).astype(np.float32))
    s = jnp.asarray(rng.normal(size=(ns, dim)).astype(np.float32))
    d, i = ops.distance_topk(r, s, k, bm=32, bn=64, impl="interpret")
    rd, ri = ref.distance_topk_ref(r, s, k)
    np.testing.assert_allclose(np.asarray(d), np.asarray(rd), atol=1e-4)
    assert (np.asarray(i) == np.asarray(ri)).mean() > 0.999


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_distance_topk_dtypes(dtype):
    rng = np.random.default_rng(0)
    r = jnp.asarray(rng.normal(size=(48, 8))).astype(dtype)
    s = jnp.asarray(rng.normal(size=(96, 8))).astype(dtype)
    d, i = ops.distance_topk(r, s, 5, bm=16, bn=32, impl="interpret")
    rd, ri = ref.distance_topk_ref(r, s, 5)
    tol = 1e-4 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(np.asarray(d), np.asarray(rd), atol=tol)


def test_distance_topk_visit_mask():
    """Masked-out tiles must not contribute (bound-pruned schedule)."""
    from repro.kernels.distance_topk import distance_topk_pallas
    rng = np.random.default_rng(1)
    r = jnp.asarray(rng.normal(size=(32, 4)).astype(np.float32))
    s_near = rng.normal(size=(32, 4)).astype(np.float32)
    s_far = s_near + 100.0
    s = jnp.asarray(np.concatenate([s_near, s_far]))
    mask = jnp.asarray([[1, 0]], jnp.int8)   # skip the far tile
    d, i, _ = distance_topk_pallas(r, s, 3, visit_mask=mask, bm=32, bn=32,
                                   interpret=True)
    rd, ri = ref.distance_topk_ref(r, jnp.asarray(s_near), 3)
    np.testing.assert_allclose(np.asarray(d), np.asarray(rd), atol=1e-4)
    assert (np.asarray(i) < 32).all()


@pytest.mark.parametrize("nr,ns,dim,k", [
    (64, 128, 8, 4),
    (100, 257, 10, 7),     # non-tile-aligned
    (33, 70, 54, 5),       # forest-width features
    (16, 1024, 16, 25),    # many tiles, k large
])
def test_distance_topk_gather_full_schedule(nr, ns, dim, k):
    """With an everything-visits schedule the gather kernel must equal the
    dense reference exactly — scalar-prefetch plumbing changes nothing."""
    rng = np.random.default_rng(nr + ns)
    r = jnp.asarray(rng.normal(size=(nr, dim)).astype(np.float32))
    s = jnp.asarray(rng.normal(size=(ns, dim)).astype(np.float32))
    bm, bn = 32, 64
    nr_t, ns_t = -(-nr // bm), -(-ns // bn)
    sched = jnp.asarray(np.tile(np.arange(ns_t, dtype=np.int32), (nr_t, 1)))
    cnt = jnp.full((nr_t,), ns_t, jnp.int32)
    d, i = ops.distance_topk(r, s, k, schedule=sched, counts=cnt,
                             bm=bm, bn=bn, impl="gather_interpret")
    rd, ri = ops.distance_topk(r, s, k, impl="ref")
    np.testing.assert_allclose(np.asarray(d), np.asarray(rd), atol=1e-4)
    assert (np.asarray(i) == np.asarray(ri)).mean() > 0.999


@pytest.mark.parametrize("nr,ns,dim,k,seed", [
    (96, 300, 6, 5, 0),
    (50, 500, 3, 9, 1),
    (128, 640, 12, 16, 2),
])
def test_distance_topk_gather_pruned_schedule(nr, ns, dim, k, seed):
    """Random pruned schedules: kernel (interpret) == jnp oracle, and the
    repeat-last padding never leaks extra candidates."""
    rng = np.random.default_rng(seed)
    r = jnp.asarray(rng.normal(size=(nr, dim)).astype(np.float32))
    s = jnp.asarray(rng.normal(size=(ns, dim)).astype(np.float32))
    bm, bn = 32, 64
    nr_t, ns_t = -(-nr // bm), -(-ns // bn)
    # random ragged visit lists, >= 1 tile each, ascending, repeat-pad
    counts = rng.integers(1, ns_t + 1, nr_t)
    width = int(counts.max())
    sched = np.zeros((nr_t, width), np.int32)
    for t in range(nr_t):
        picks = np.sort(rng.choice(ns_t, counts[t], replace=False))
        sched[t, :counts[t]] = picks
        sched[t, counts[t]:] = picks[-1]
    sched_j = jnp.asarray(sched)
    cnt_j = jnp.asarray(counts.astype(np.int32))
    d, i = ops.distance_topk(r, s, k, schedule=sched_j, counts=cnt_j,
                             bm=bm, bn=bn, impl="gather_interpret")
    rd, ri = ops.distance_topk(r, s, k, schedule=sched_j, counts=cnt_j,
                               bm=bm, bn=bn, impl="gather_ref")
    np.testing.assert_allclose(np.asarray(d), np.asarray(rd), atol=1e-4)
    fin = np.isfinite(np.asarray(rd))
    assert (np.asarray(i) == np.asarray(ri))[fin].mean() > 0.999


@pytest.mark.parametrize("seed,dead_frac", [(0, 0.2), (1, 0.6), (2, 0.95)])
def test_distance_topk_gather_alive_mask(seed, dead_frac):
    """The megastep's liveness mask: rows with alive == 0 (tombstones,
    per-segment padding in a concatenated layout) can never enter the
    top-k, and the kernel (interpret) matches the masked jnp oracle —
    including when live rows run short and (-1, +inf) slots appear."""
    rng = np.random.default_rng(seed)
    nr, ns, dim, k = 64, 320, 5, 8
    r = jnp.asarray(rng.normal(size=(nr, dim)).astype(np.float32))
    s = jnp.asarray(rng.normal(size=(ns, dim)).astype(np.float32))
    alive_np = (rng.random(ns) >= dead_frac).astype(np.float32)
    alive = jnp.asarray(alive_np)
    bm, bn = 32, 64
    nr_t, ns_t = -(-nr // bm), -(-ns // bn)
    sched = jnp.asarray(np.tile(np.arange(ns_t, dtype=np.int32), (nr_t, 1)))
    cnt = jnp.full((nr_t,), ns_t, jnp.int32)
    d, i = ops.distance_topk(r, s, k, schedule=sched, counts=cnt,
                             alive=alive, bm=bm, bn=bn,
                             impl="gather_interpret")
    rd, ri = ops.distance_topk(r, s, k, schedule=sched, counts=cnt,
                               alive=alive, bm=bm, bn=bn, impl="gather_ref")
    d, i, rd, ri = map(np.asarray, (d, i, rd, ri))
    np.testing.assert_allclose(d, rd, atol=1e-4)
    fin = np.isfinite(rd)
    assert (i == ri)[fin].mean() > 0.999
    # no dead row ever surfaces; short live sets pad with -1/+inf
    dead_ids = np.where(alive_np == 0)[0]
    assert not np.isin(i[fin], dead_ids).any()
    n_live = int(alive_np.sum())
    if n_live < k:
        assert (i[:, n_live:] == -1).all() and not np.isfinite(
            d[:, n_live:]).any()


def test_distance_topk_gather_dtypes():
    rng = np.random.default_rng(3)
    r = jnp.asarray(rng.normal(size=(48, 8))).astype(jnp.bfloat16)
    s = jnp.asarray(rng.normal(size=(96, 8))).astype(jnp.bfloat16)
    sched = jnp.asarray(np.arange(3, dtype=np.int32)[None].repeat(3, 0))
    cnt = jnp.full((3,), 3, jnp.int32)
    d, i = ops.distance_topk(r, s, 5, schedule=sched, counts=cnt,
                             bm=16, bn=32, impl="gather_interpret")
    rd, ri = ref.distance_topk_ref(r, s, 5)
    np.testing.assert_allclose(np.asarray(d), np.asarray(rd), atol=5e-2)


GATE_BM, GATE_BN, GATE_K, GATE_KP = 32, 64, 10, 16


def _gate_case(case, rng):
    """``(r, s, alive, sched, cnt, n_valid)`` of one gate case. Rows are
    small integers, so every squared distance is exact in float32 and
    equal ties are common; schedules are ascending, as the megastep's,
    and as wide as S has tiles, padded by repeating their last entry."""
    dim = 3
    n_r, n_s = 64, 320
    r = rng.integers(-3, 4, (n_r, dim))
    s = rng.integers(-3, 4, (n_s, dim))
    alive = (rng.random(n_s) > 0.1).astype(np.float32)
    n_valid = n_r
    if case == "gate_closes":
        # every row's 16 nearest lie in tile 0; tiles 1.. are 100 away
        s[GATE_BN:] += 100
        alive[:GATE_BN] = 1.0
    elif case == "dead":
        # tile 0 and tile 3 are dead throughout
        alive[:GATE_BN] = 0.0
        alive[3 * GATE_BN:4 * GATE_BN] = 0.0
    elif case == "single":
        n_s = 50
        s, alive = s[:n_s], alive[:n_s]
    elif case == "ragged":
        n_r, n_valid = 75, 70
        r = rng.integers(-3, 4, (n_r, dim))
    elif case == "thresholds":
        # valid rows at the origin; far filler beside points placed so
        # that tile 0 fills the run with squared distances 1..18 (the
        # 8th is 9, the 16th 18), tile 1 brings a 17 (under the 16th
        # only), tile 2 another 17 (a tie with the new 16th), tile 3
        # only a row beside the bucket's padding rows, tile 4 a 0
        n_r, n_valid = 60, 56
        r = np.zeros((n_r, dim), np.int64)
        r[n_valid:] = (0, 0, 50)
        s = np.zeros((n_s, dim), np.int64)
        s[:, 0] = 100
        s[:, 1] = rng.integers(0, 7, n_s)
        s[:16] = [(1, 0, 0), (1, 1, 0), (1, 1, 1), (2, 0, 0), (2, 1, 0),
                  (2, 1, 1), (2, 2, 0), (3, 0, 0), (3, 1, 0), (3, 1, 1),
                  (2, 2, 2), (3, 2, 0), (3, 2, 1), (4, 0, 0), (4, 1, 0),
                  (4, 1, 1)]
        s[GATE_BN] = s[2 * GATE_BN] = (4, 1, 0)
        s[3 * GATE_BN] = (0, 0, 50)
        s[4 * GATE_BN] = (0, 0, 0)
        alive[:16] = 1.0
        alive[GATE_BN::GATE_BN] = 1.0
    nr_t, ns_t = -(-n_r // GATE_BM), -(-n_s // GATE_BN)
    if case == "ties":
        cnt = rng.integers(1, ns_t + 1, nr_t)
        sched = np.zeros((nr_t, ns_t), np.int32)
        for t in range(nr_t):
            picks = np.sort(rng.choice(ns_t, cnt[t], replace=False))
            sched[t, :cnt[t]] = picks
            sched[t, cnt[t]:] = picks[-1]
    else:
        cnt = np.full(nr_t, ns_t)
        sched = np.tile(np.arange(ns_t, dtype=np.int32), (nr_t, 1))
    return (r.astype(np.float32), s.astype(np.float32), alive, sched,
            cnt.astype(np.int32), n_valid)


def _ungated_fold(r, s, live, sched, cnt):
    """The kernels' selection without the gate, in plain jnp: every
    visited tile is sorted and merged into its R tile's run — bitwise
    what the kernels computed before the gate, ties included."""
    from repro.kernels.distance_topk import _sq_dists
    from repro.kernels.sorted_merge import merge_sorted_runs, tile_topk

    @jax.jit
    def step(run_d, run_i, r_t, s_t, live_t, gid):
        d2 = jnp.where(live_t, _sq_dists(r_t, s_t), jnp.inf)
        td, ti = tile_topk(d2, jnp.broadcast_to(gid, d2.shape), GATE_KP)
        return merge_sorted_runs(run_d, run_i, td, ti)

    n_s = s.shape[0]
    ns_t = -(-n_s // GATE_BN)
    r_p = np.pad(r, ((0, -r.shape[0] % GATE_BM), (0, 0)))
    s_p = np.pad(s, ((0, ns_t * GATE_BN - n_s), (0, 0)))
    live_p = np.pad(live, (0, ns_t * GATE_BN - n_s))
    out_d, out_i = [], []
    for t in range(sched.shape[0]):
        run_d = jnp.full((GATE_BM, GATE_KP), jnp.inf, jnp.float32)
        run_i = jnp.full((GATE_BM, GATE_KP), -1, jnp.int32)
        for tile in sched[t, :cnt[t]]:
            cols = slice(tile * GATE_BN, (tile + 1) * GATE_BN)
            gid = tile * GATE_BN + np.arange(GATE_BN, dtype=np.int32)[None]
            run_d, run_i = step(run_d, run_i,
                                r_p[t * GATE_BM:(t + 1) * GATE_BM],
                                s_p[cols], live_p[cols][None], gid)
        out_d.append(np.sqrt(np.asarray(run_d)[:, :GATE_K]))
        out_i.append(np.asarray(run_i)[:, :GATE_K])
    return np.concatenate(out_d), np.concatenate(out_i)


def _np_merged(r, s, live, sched, cnt, n_valid):
    """Per R tile, the visited steps in which some valid row has a
    candidate strictly under its running 16th distance."""
    d2 = ((r[:n_valid, None, :].astype(np.float64)
           - s[None].astype(np.float64)) ** 2).sum(-1)
    d2 = np.where(live[None, :] > 0, d2, np.inf)
    merged = []
    for t in range(sched.shape[0]):
        rows = d2[t * GATE_BM:(t + 1) * GATE_BM]
        run = np.full((rows.shape[0], GATE_KP), np.inf)
        m = 0
        for tile in sched[t, :cnt[t]]:
            c = rows[:, tile * GATE_BN:(tile + 1) * GATE_BN]
            m += bool((c < run[:, -1:]).any())
            run = np.sort(np.concatenate([run, c], axis=1), axis=1)
            run = run[:, :GATE_KP]
        merged.append(m)
    return np.asarray(merged)


@pytest.mark.parametrize("case,variant", [
    ("ties", "gather"), ("ties", "gather_alive"), ("ties", "dense"),
    ("gate_closes", "gather"), ("gate_closes", "gather_alive"),
    ("gate_closes", "dense"),
    ("dead", "gather_alive"),
    ("single", "gather"), ("single", "gather_alive"), ("single", "dense"),
    ("ragged", "gather"), ("ragged", "gather_alive"), ("ragged", "dense"),
    ("thresholds", "gather"), ("thresholds", "gather_alive"),
    ("thresholds", "dense"),
])
def test_merge_gate_bitwise_and_counted(case, variant):
    """The merge gate skips the sort only where it would change nothing:
    each kernel (interpret) returns bitwise the ungated fold's (d, ids),
    ties included, and the distances of the dense jnp oracle; its merged
    count is the steps where some valid row has a candidate strictly
    under its running 16th distance; the megastep's scan twin
    ("ref_sched") counts the same steps."""
    from repro.core.megastep import _gather_topk_run
    from repro.kernels.distance_topk import (distance_topk_gather_pallas,
                                             distance_topk_pallas)

    rng = np.random.default_rng(sum(map(ord, case + variant)))
    r, s, alive, sched, cnt, n_valid = _gate_case(case, rng)
    n_r, n_s = r.shape[0], s.shape[0]
    if variant == "dense":
        n_valid = n_r              # the dense kernel knows no n_valid
    if variant != "gather_alive":
        alive = np.ones(n_s, np.float32)
    if variant == "dense":
        ns_t = -(-n_s // GATE_BN)
        mask = np.zeros((sched.shape[0], ns_t), np.int8)
        for t in range(sched.shape[0]):
            mask[t, sched[t, :cnt[t]]] = 1
        d, i, merged = distance_topk_pallas(
            jnp.asarray(r), jnp.asarray(s), GATE_K,
            visit_mask=jnp.asarray(mask), bm=GATE_BM, bn=GATE_BN,
            interpret=True)
    else:
        d, i, merged = distance_topk_gather_pallas(
            jnp.asarray(r), jnp.asarray(s), GATE_K, jnp.asarray(sched),
            jnp.asarray(cnt), n_valid=n_valid, bm=GATE_BM, bn=GATE_BN,
            alive=jnp.asarray(alive) if variant == "gather_alive" else None,
            interpret=True)
    d, i, merged = (np.asarray(x) for x in (d, i, merged))
    fd, fi = _ungated_fold(r, s, alive, sched, cnt)
    np.testing.assert_array_equal(d[:n_valid], fd[:n_valid])
    np.testing.assert_array_equal(i[:n_valid], fi[:n_valid])
    rd, _ = ref.distance_topk_gather_ref(
        jnp.asarray(r), jnp.asarray(s), GATE_K, jnp.asarray(sched),
        jnp.asarray(cnt), bm=GATE_BM, bn=GATE_BN, alive=jnp.asarray(alive))
    np.testing.assert_array_equal(d[:n_valid], np.asarray(rd)[:n_valid])
    want = _np_merged(r, s, alive, sched, cnt, n_valid)
    np.testing.assert_array_equal(merged, want)
    assert (want <= cnt).all()
    if case == "gate_closes":
        assert (want == 1).all()       # tile 0 fills the run; then closed
    if case == "dead":
        assert (want < cnt).all()      # a dead tile never merges
    if case == "thresholds":
        assert want[0] == 3            # tiles 0, 1 and 4

    if variant == "dense":
        return
    # the megastep's two schedule walkers over the same layout
    ns_t = sched.shape[1]
    b = -(-n_r // GATE_BM) * GATE_BM
    tiles = dict(
        s=jnp.asarray(np.pad(s, ((0, ns_t * GATE_BN - n_s), (0, 0)))),
        alive=jnp.asarray(np.pad(alive, (0, ns_t * GATE_BN - n_s))),
        center=jnp.zeros((r.shape[1],), jnp.float32))
    qs = jnp.asarray(np.pad(r, ((0, b - n_r), (0, 0))))
    valid_s = jnp.arange(b) < n_valid
    counts = {
        impl: np.asarray(_gather_topk_run(
            qs, qs, valid_s, jnp.asarray(sched), jnp.asarray(cnt), tiles,
            k=GATE_K, bm=GATE_BM, bn=GATE_BN, metric="l2",
            dim=r.shape[1], impl=impl)[-1])
        for impl in ("ref_sched", "pallas_interpret")}
    np.testing.assert_array_equal(counts["ref_sched"], want)
    np.testing.assert_array_equal(counts["pallas_interpret"], want)


@pytest.mark.parametrize("n,m,dim", [(100, 16, 6), (257, 50, 12),
                                     (64, 7, 3)])
def test_assign(n, m, dim):
    rng = np.random.default_rng(n)
    x = jnp.asarray(rng.normal(size=(n, dim)).astype(np.float32))
    p = jnp.asarray(rng.normal(size=(m, dim)).astype(np.float32))
    pid, dist = ops.assign(x, p, bm=32, bp=8, impl="interpret")
    rpid, rdist = ref.assign_ref(x, p)
    assert (np.asarray(pid) == np.asarray(rpid)).all()
    np.testing.assert_allclose(np.asarray(dist), np.asarray(rdist), atol=1e-5)


@pytest.mark.parametrize("nq,nk,h,kvh,window,causal", [
    (64, 64, 4, 4, None, True),
    (64, 64, 4, 1, None, True),      # MQA
    (32, 96, 8, 2, None, True),      # GQA + decode-style offset
    (64, 64, 4, 2, 16, True),        # local window
    (48, 48, 2, 2, None, False),     # bidirectional (encoder)
])
def test_flash_attention(nq, nk, h, kvh, window, causal):
    rng = np.random.default_rng(nq + nk)
    q = jnp.asarray(rng.normal(size=(2, nq, h, 16)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(2, nk, kvh, 16)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(2, nk, kvh, 16)).astype(np.float32))
    o = ops.flash_attention(q, k, v, causal=causal, window=window,
                            bq=16, bk=16, impl="interpret")
    ro = ref.flash_attention_ref(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(np.asarray(o), np.asarray(ro), atol=2e-4)


def test_flash_attention_bf16():
    rng = np.random.default_rng(9)
    q = jnp.asarray(rng.normal(size=(1, 32, 2, 8))).astype(jnp.bfloat16)
    k = jnp.asarray(rng.normal(size=(1, 32, 2, 8))).astype(jnp.bfloat16)
    v = jnp.asarray(rng.normal(size=(1, 32, 2, 8))).astype(jnp.bfloat16)
    o = ops.flash_attention(q, k, v, bq=16, bk=16, impl="interpret")
    ro = ref.flash_attention_ref(q, k, v)
    np.testing.assert_allclose(np.asarray(o, np.float32),
                               np.asarray(ro, np.float32), atol=5e-2)
