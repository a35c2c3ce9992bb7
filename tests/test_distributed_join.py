"""Distributed (shard_map) join — runs in a subprocess with 8 forced host
devices so the main pytest process keeps the real (1-device) topology.

``distributed_knn_join`` is a compatibility wrapper over two SPMD
executions: the default ``reducer="sharded"`` routes L2 joins through
the sharded megastep (core.sharded — payload partitioned once, bitwise
the single-device megastep), and ``reducer="shuffle"`` keeps the
explicit Theorem-6-routed all_to_all + dense scan mapping.

Meshes are built with ``Auto`` axis types, the sharding mode the SPMD
code is written for (``jax.make_mesh`` defaults to ``Explicit``).
"""
import json
import os
import subprocess
import sys
import textwrap

import numpy as np

_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json
    import numpy as np
    import jax
    from repro.core import JoinConfig, brute_force_knn, plan_join
    from repro.core.distributed import build_shuffle_spec, distributed_knn_join

    def make_mesh(shape, names):
        return jax.make_mesh(
            shape, names,
            axis_types=(jax.sharding.AxisType.Auto,) * len(names))
    from repro.core.megastep import MegastepEngine
    from repro.distributed.fault import regroup

    rng = np.random.default_rng(7)
    R = rng.normal(size=(400, 5)).astype(np.float32) * 2
    S = rng.normal(size=(700, 5)).astype(np.float32) * 2
    k = 5
    out = {}

    cfg = JoinConfig(k=k, n_pivots=32, n_groups=8, grouping="geometric")
    plan = plan_join(R, S, cfg)
    bd, bi = brute_force_knn(R, S, k)

    # default reducer="auto" resolves to the sharded megastep for L2
    mesh = make_mesh((8,), ("data",))
    res = distributed_knn_join(R, S, plan, mesh, axis="data")
    out["sharded_exact"] = bool(np.allclose(res.distances, bd, atol=1e-3))
    out["n_shards"] = int(res.stats.n_shards)
    out["replicas_sharded"] = int(res.stats.replicas_s)

    # pointer test: the wrapper's sharded route is *bitwise* the
    # single-device megastep over the same index/config — the wrapper
    # adds no numerics of its own
    import dataclasses
    cfg_t = dataclasses.replace(plan.query.config, tile_s=512, tile_r=128)
    d1, i1 = MegastepEngine(plan.index, cfg_t).join_batch(R)
    out["sharded_bitwise_single"] = bool(
        np.array_equal(res.distances, d1)
        and np.array_equal(res.indices, i1))

    # explicit shuffle reducer: the Theorem-6 all_to_all mapping, dense
    # per-device scan — must agree on distances
    res_d = distributed_knn_join(R, S, plan, mesh, axis="data",
                                 reducer="shuffle")
    out["shuffle_exact"] = bool(np.allclose(res_d.distances, bd, atol=1e-3))
    out["shuffle_n_shards"] = int(res_d.stats.n_shards)
    out["replicas"] = int(res_d.stats.replicas_s)
    out["tiles"] = [int(res_d.stats.tiles_visited),
                    int(res_d.stats.tiles_total)]

    # the sharded route flattens any device grid into a 1-D shard mesh;
    # the shuffle route runs SPMD over the joint axes
    mesh2 = make_mesh((4, 2), ("data", "model"))
    res2 = distributed_knn_join(R, S, plan, mesh2, axis=("data", "model"),
                                reducer="shuffle")
    out["two_axis_exact"] = bool(np.allclose(res2.distances, bd, atol=1e-3))
    res2s = distributed_knn_join(R, S, plan, mesh2, axis=("data", "model"))
    out["two_axis_sharded_bitwise"] = bool(
        np.array_equal(res2s.distances, d1))

    # elastic: shrink to 4 groups, run on a 4-device submesh (sharded is
    # group-count-invariant; shuffle needs groups == mesh extent)
    plan4 = regroup(plan, 4)
    mesh4 = make_mesh((4,), ("data",))
    res4 = distributed_knn_join(R, S, plan4, mesh4, axis="data")
    out["shrunk_exact"] = bool(np.allclose(res4.distances, bd, atol=1e-3))
    res4s = distributed_knn_join(R, S, plan4, mesh4, axis="data",
                                 reducer="shuffle")
    out["shrunk_shuffle_exact"] = bool(
        np.allclose(res4s.distances, bd, atol=1e-3))

    # capacity model must bound actual packing (Thm 7 load-bearing)
    spec = build_shuffle_spec(plan, 8)
    out["caps"] = [spec.cap_r_send, spec.cap_s_send]

    # SPMD phase-1 (psum/pmin/pmax-merged summaries) == host phase-1
    from repro.core import assign_and_summarize, select_pivots
    from repro.core.distributed import distributed_phase1
    pivots = select_pivots(S, 16, "random", seed=3)
    pd_, dd_, td_ = distributed_phase1(S, pivots, mesh, k=4)
    ph_, dh_, th_ = assign_and_summarize(S, pivots, k=4)
    fin = np.isfinite(th_.knn_dists)
    out["phase1_exact"] = bool(
        (pd_ == ph_).all() and np.allclose(dd_, dh_, atol=1e-5)
        and (td_.counts == th_.counts).all()
        and np.allclose(td_.knn_dists[fin], th_.knn_dists[fin], atol=1e-5))
    print(json.dumps(out))
""")


def test_distributed_join_subprocess():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src"))
    proc = subprocess.run([sys.executable, "-c", _SCRIPT], env=env,
                          capture_output=True, text=True, timeout=560)
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["sharded_exact"]
    assert out["sharded_bitwise_single"]
    assert out["n_shards"] == 8
    assert out["shuffle_exact"]
    assert out["shuffle_n_shards"] == 0  # shuffle path: single-device stats
    assert out["two_axis_exact"]
    assert out["two_axis_sharded_bitwise"]
    assert out["shrunk_exact"]
    assert out["shrunk_shuffle_exact"]
    assert out["phase1_exact"]
    assert out["caps"][0] >= 1 and out["caps"][1] >= 1
    # shuffle ships self+replication ≥ |S| once; sharded is resident —
    # every row lives on exactly one shard
    assert out["replicas"] >= 700
    assert out["replicas_sharded"] == 700
    # dense reducer accounting: every received tile is visited
    assert out["tiles"][0] == out["tiles"][1] > 0
