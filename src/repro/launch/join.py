"""kNN-join launcher: the paper's workload as a CLI.

  PYTHONPATH=src python -m repro.launch.join --dataset forest --n 20000 \
      --k 10 --pivots 256 --groups 9 [--grouping greedy] [--distributed]
"""
from __future__ import annotations

import argparse
import time

import numpy as np

from repro.core import (
    JoinConfig, brute_force_knn, hbrj_join, knn_join, pbj_join, plan_join)
from repro.data import expand_dataset, forest_like, osm_like


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--dataset", choices=["forest", "osm"], default="forest")
    ap.add_argument("--n", type=int, default=20000)
    ap.add_argument("--dim", type=int, default=10)
    ap.add_argument("--expand", type=int, default=1)
    ap.add_argument("--k", type=int, default=10)
    ap.add_argument("--pivots", type=int, default=256)
    ap.add_argument("--groups", type=int, default=9)
    ap.add_argument("--pivot-strategy", default="random",
                    choices=["random", "farthest", "kmeans"])
    ap.add_argument("--grouping", default="geometric",
                    choices=["geometric", "greedy", "none"])
    ap.add_argument("--method", default="pgbj",
                    choices=["pgbj", "pbj", "hbrj"])
    ap.add_argument("--distributed", action="store_true",
                    help="shard_map execution over the host devices")
    ap.add_argument("--verify", action="store_true")
    args = ap.parse_args(argv)
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()

    data = (forest_like(args.n, args.dim) if args.dataset == "forest"
            else osm_like(args.n))
    data = expand_dataset(data, args.expand)
    cfg = JoinConfig(k=args.k, n_pivots=args.pivots, n_groups=args.groups,
                     pivot_strategy=args.pivot_strategy,
                     grouping=args.grouping)
    t0 = time.perf_counter()
    if args.method == "pgbj":
        if args.distributed:
            import jax
            from repro.core.distributed import distributed_knn_join
            n_dev = len(jax.devices())
            cfg = JoinConfig(k=args.k, n_pivots=args.pivots, n_groups=n_dev,
                             pivot_strategy=args.pivot_strategy,
                             grouping=args.grouping)
            plan = plan_join(data, data, cfg)
            mesh = jax.make_mesh(
                (n_dev,), ("data",),
                axis_types=(jax.sharding.AxisType.Auto,))
            res = distributed_knn_join(data, data, plan, mesh)
        else:
            res = knn_join(data, data, config=cfg)
    elif args.method == "pbj":
        res = pbj_join(data, data, args.k, cfg, n_reducers=args.groups)
    else:
        res = hbrj_join(data, data, args.k, n_reducers=args.groups)
    dt = time.perf_counter() - t0

    s = res.stats
    print(f"{args.method} on {args.dataset} n={data.shape[0]} k={args.k}: "
          f"{dt:.2f}s")
    print(f"  selectivity={s.selectivity:.4f} shuffle={s.shuffle_tuples} "
          f"alpha={s.replicas_s/max(s.n_s,1):.2f}")
    if args.verify:
        sample = np.random.default_rng(0).choice(
            data.shape[0], min(500, data.shape[0]), replace=False)
        bd, _ = brute_force_knn(data[sample], data, args.k)
        ok = np.allclose(res.distances[sample], bd, atol=1e-2)
        print(f"  verified vs brute force on {len(sample)} samples: {ok}")
        if not ok:
            raise SystemExit(1)


if __name__ == "__main__":
    main()
