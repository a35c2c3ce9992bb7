"""Distributed PGBJ join over an SPMD device mesh with fault-tolerant
group execution (retries + speculative backup tasks).

Run:  PYTHONPATH=src XLA_FLAGS=--xla_force_host_platform_device_count=8 \
          python examples/distributed_join.py
"""
import os

if "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=8")

import jax
import numpy as np

from repro.core import JoinConfig, brute_force_knn, plan_join
from repro.core.distributed import distributed_knn_join
from repro.data import forest_like
from repro.distributed.fault import GroupExecutor, regroup


def make_mesh(shape):
    return jax.make_mesh(shape, ("data",),
                         axis_types=(jax.sharding.AxisType.Auto,))


def main():
    n_dev = len(jax.devices())
    print(f"devices: {n_dev}")
    R = forest_like(4000, 8, seed=0)
    S = forest_like(6000, 8, seed=1)
    cfg = JoinConfig(k=10, n_pivots=64, n_groups=n_dev)
    plan = plan_join(R, S, cfg)
    mesh = make_mesh((n_dev,))
    res = distributed_knn_join(R, S, plan, mesh)
    bd, _ = brute_force_knn(R, S, 10)
    assert np.allclose(res.distances, bd, atol=1e-2)
    print(f"distributed join exact on {n_dev}-device mesh ✓  "
          f"(replicas shipped: {res.stats.replicas_s})")

    # elastic: re-run on half the devices without re-planning phase 1
    half = n_dev // 2
    plan_h = regroup(plan, half)
    mesh_h = make_mesh((half,))
    res_h = distributed_knn_join(R, S, plan_h, mesh_h)
    assert np.allclose(res_h.distances, bd, atol=1e-2)
    print(f"elastic shrink {n_dev}→{half} devices, still exact ✓")

    # fault-tolerant group execution with injected failures
    import threading
    fails = {1: 1}
    lock = threading.Lock()

    def group_fn(g):
        with lock:
            if fails.get(g, 0) > 0:
                fails[g] -= 1
                raise RuntimeError("injected node failure")
        mask = plan.s_replica_mask(g)
        return int(mask.sum())

    ex = GroupExecutor(max_retries=2, speculate=True)
    runs = ex.run(group_fn, list(range(plan.n_groups)))
    print("group execution with injected failure:",
          {g: (r.attempts, r.result) for g, r in sorted(runs.items())})
    print("fault-tolerant execution ✓")


if __name__ == "__main__":
    main()
