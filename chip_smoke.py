#!/usr/bin/env python3
"""Smoke run of the PGBJ serving path on TPU, through the user entry points.

    python chip_smoke.py              # one chip: fp32, int8, offline join
    python chip_smoke.py --chips 4    # four chips: the sharded datastore

One chip runs three phases, each checked against the brute-force oracle
(`core.baselines.brute_force_knn`) on a sample of its queries:

* fp32 retrieval: `serve.Datastore.build` over 1,000,000 x 128 clustered
  fp32 keys (the SIFT1M shape of ann-benchmarks), L2, k = 10, then 8
  batches of 256 queries through `ServeScheduler.for_datastore`;
* int8 retrieval: the same keys with ``quantized=True``, same traffic;
* offline join: `knn_join_batched(..., megastep=True)` of the first
  65,536 rows of a 581,012 x 10 Forest-like set (CoverType's scale,
  paper section 6) against all of it, k = 10.

``--chips 4`` runs only the sharded datastore (`Datastore.build(n_shards=4,
replication=2)` over 4,000,000 x 128 keys) and what it is compared with:
the one-device `MegastepEngine` over the same index, which it must match
bit for bit.

Every phase fails the run when the device is not a TPU, when a step did
not run the compiled Pallas kernels, when the scheduler retried, failed
or failed over a batch, or when a result differs from the reference.
The run never carries on on the CPU. Times printed here are smoke
timings, not benchmark numbers. The last line of standard output is one
JSON object, ``{"ok": true, "device": {...}}``, printed only on success.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import os
import sys
import time
import traceback

REPO = os.path.dirname(os.path.abspath(__file__))
K = 10
BATCH = 256
N_BATCHES = 8
DIM = 128
N_KEYS = 1_000_000            # one chip
N_KEYS_SHARDED = 4_000_000    # four chips
N_FOREST = 581_012            # CoverType rows
N_JOIN_R = 65_536
JOIN_BATCH = 8192


class SmokeFailure(Exception):
    pass


def require(cond, why: str) -> None:
    if not cond:
        raise SmokeFailure(why)


def say(phase: str, **kv) -> None:
    print(f"[{phase}] " + " ".join(f"{k}={v}" for k, v in kv.items()),
          flush=True)


class CompileClock:
    """Seconds JAX spent tracing, lowering and compiling since the last
    read, from its own monitoring events."""

    _EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
               "/jax/core/compile/jaxpr_to_mlir_module_duration",
               "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax
        self.total = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_kw):
        if event in self._EVENTS:
            self.total += duration

    def read(self) -> float:
        out, self.total = self.total, 0.0
        return out


def check_exact(phase, q, s, d, ids, n_sample, seed):
    """Distances bitwise the oracle's on a sample; ids equal except
    within a group of exactly tied distances (any member of the group
    that straddles the k-th place is as right as another)."""
    import numpy as np

    from repro.core import brute_force_knn

    rng = np.random.default_rng(seed)
    pick = np.sort(rng.choice(q.shape[0], min(n_sample, q.shape[0]),
                              replace=False))
    bd, bi = brute_force_knn(q[pick], s, d.shape[1])
    d, ids = d[pick], ids[pick]
    diff = np.abs(d.astype(np.float64) - bd)
    require(np.array_equal(d, bd),
            f"{phase}: distances differ from brute force on "
            f"{int((d != bd).any(1).sum())} of {pick.size} sampled queries "
            f"(max abs diff {np.nanmax(diff)!r})")
    for row in range(pick.size):
        vals = bd[row]
        for v in np.unique(vals)[:-1]:          # the last group may straddle
            same = vals == v
            require(set(ids[row][same]) == set(bi[row][same]),
                    f"{phase}: query {int(pick[row])} ids differ from brute "
                    f"force at distance {v!r}")
    say(phase, checked_queries=pick.size, vs="brute_force_knn",
        distances="bitwise equal", ids="equal up to exact ties")


def check_scheduler(phase, sched, tickets):
    snap = sched.snapshot()
    say(phase, snapshot=json.dumps(dataclasses.asdict(snap), default=float))
    require(all(t.done for t in tickets),
            f"{phase}: tickets not served: "
            f"{sorted({(t.status, t.reason) for t in tickets if not t.done})}")
    for field in ("n_retries", "n_failed", "n_failovers"):
        require(getattr(snap, field) == 0,
                f"{phase}: scheduler {field}={getattr(snap, field)}")
    return snap


def check_impl(phase, engine):
    impl = engine.resolved_impl
    say(phase, impl=impl)
    require(impl == "pallas",
            f"{phase}: step resolved to impl {impl!r}, not the compiled "
            f"Pallas kernel")


def serve(phase, store, queries, clock):
    """Queries through the scheduler in BATCH-row requests; returns
    (dists, ids) in query order and checks the run's health."""
    import numpy as np

    from repro.serve import ServeScheduler

    sched = ServeScheduler.for_datastore(store)
    clock.read()
    tickets, secs = [], []
    for lo in range(0, queries.shape[0], BATCH):
        t0 = time.perf_counter()
        t = sched.join_now(queries[lo:lo + BATCH], deadline_s=600.0)
        secs.append(time.perf_counter() - t0)    # results fetched: bounded
        tickets.append(t)
    say(phase, note="smoke timing, not a benchmark",
        first_batch_s=secs[0], compile_s=clock.read(),
        steady_batch_s=secs[1:])
    check_scheduler(phase, sched, tickets)
    return (np.concatenate([t.distances for t in tickets]),
            np.concatenate([t.indices for t in tickets]), sched)


def retrieval_phase(phase, keys, values, queries, clock, args, **build):
    from repro import serve as srv

    t0 = time.perf_counter()
    store = srv.Datastore.build(keys, values, k=K, seed=args.seed, **build)
    say(phase, keys=keys.shape, build_s=time.perf_counter() - t0)
    engine = store.engine().megastep_engine
    check_impl(phase, engine)
    d, ids, sched = serve(phase, store, queries, clock)
    return store, engine, sched, d, ids


def phase_fp32(keys, values, queries, clock, args):
    store, engine, _, d, ids = retrieval_phase(
        "fp32", keys, values, queries, clock, args)
    visited, total = engine.tile_counts(queries[:BATCH])
    say("fp32", tiles_visited=visited, tiles_pruned=total - visited,
        tiles_total=total, of="one batch")
    check_exact("fp32", queries, keys, d, ids, args.sample, args.seed)


def phase_int8(keys, values, queries, clock, args):
    store, engine, sched, d, ids = retrieval_phase(
        "int8", keys, values, queries, clock, args, quantized=True)
    js = sched.snapshot().join
    say("int8", mode=engine.mode, mp=engine.mp, resident=engine.resident,
        autotuned=engine.autotuned, n_quant_fallback=js.n_quant_fallback)
    check_exact("int8", queries, keys, d, ids, args.sample, args.seed)


def phase_offline_join(clock, args):
    import numpy as np

    from repro.core import JoinConfig, build_index, knn_join_batched
    from repro.core.megastep import MegastepEngine
    from repro.data import forest_like

    data = forest_like(N_FOREST, 10, seed=args.seed)
    r = data[:N_JOIN_R]
    cfg = JoinConfig(k=K, n_pivots=256, n_groups=8, seed=args.seed)
    t0 = time.perf_counter()
    index = build_index(data, cfg)
    say("join", s=data.shape, r=r.shape, build_s=time.perf_counter() - t0)
    probe = MegastepEngine(index, cfg)
    check_impl("join", probe)
    visited, total = probe.tile_counts(r[:JOIN_BATCH])
    say("join", tiles_visited=visited, tiles_pruned=total - visited,
        tiles_total=total, of=f"first {JOIN_BATCH} rows")
    del probe
    clock.read()
    t0 = time.perf_counter()
    res = knn_join_batched(r, index=index, megastep=True,
                           batch_size=JOIN_BATCH)
    say("join", note="smoke timing, not a benchmark",
        join_s=time.perf_counter() - t0, compile_s=clock.read(),
        n_batches=res.stats.n_batches)
    require(res.distances.shape == (r.shape[0], K)
            and np.isfinite(res.distances).all(),
            f"join: result shape {res.distances.shape} or non-finite values")
    check_exact("join", r, data, res.distances, res.indices, 256, args.seed)


def phase_sharded(keys, values, queries, clock, args, n_chips):
    import numpy as np

    from repro import serve as srv
    from repro.core.megastep import MegastepEngine

    t0 = time.perf_counter()
    store = srv.Datastore.build(keys, values, k=K, seed=args.seed,
                                n_shards=n_chips, replication=2)
    say("sharded", keys=keys.shape, n_shards=n_chips, replication=2,
        build_s=time.perf_counter() - t0)
    engine = store.engine().megastep_engine
    check_impl("sharded", engine)
    d, ids, _ = serve("sharded", store, queries, clock)
    rows = engine._refresh().tiles["s"]
    say("sharded", device_set=sorted(str(x) for x in rows.sharding.device_set))
    for sh in rows.addressable_shards:
        say("sharded", shard_device=str(sh.device), shape=sh.data.shape)
    require(len(rows.sharding.device_set) == n_chips,
            f"sharded: payload on {len(rows.sharding.device_set)} devices, "
            f"expected {n_chips}")

    one = MegastepEngine(store.index, store.config)
    check_impl("one-device", one)
    d1, i1 = one.join_batch(queries)
    say("one-device", device_set=sorted(
        str(x) for x in one._refresh().tiles["s"].sharding.device_set))
    require(np.array_equal(d, d1) and np.array_equal(ids, i1),
            f"sharded: results differ from the one-device engine on "
            f"{int(((d != d1) | (ids != i1)).any(1).sum())} queries")
    say("sharded", vs="one-device MegastepEngine", result="bitwise equal",
        queries=queries.shape[0])
    check_exact("sharded", queries, keys, d, ids, args.sample, args.seed)


def run(args) -> dict:
    require(os.path.isdir(os.path.join(REPO, "src", "repro")),
            f"the repro package is not next to this script "
            f"({os.path.join(REPO, 'src', 'repro')} is missing)")
    sys.path.insert(0, os.path.join(REPO, "src"))
    import jax
    import numpy as np

    devices = jax.devices()
    dev = devices[0]
    say("device", platform=dev.platform, kind=dev.device_kind,
        count=len(devices))
    require(dev.platform == "tpu",
            f"JAX found platform {dev.platform!r}, not 'tpu'; this smoke "
            f"never runs on the CPU")
    require(len(devices) >= args.chips,
            f"{args.chips} chips asked for, JAX sees {len(devices)}")

    from repro.compile_cache import enable_compile_cache
    from repro.data import clustered_like

    say("device", compile_cache=enable_compile_cache())
    clock = CompileClock()
    rng = np.random.default_rng(args.seed)
    queries = clustered_like(N_BATCHES * BATCH, DIM, seed=args.seed + 1)
    n_keys = N_KEYS if args.chips == 1 else N_KEYS_SHARDED
    keys = clustered_like(n_keys, DIM, seed=args.seed)
    values = rng.integers(0, 50_000, n_keys).astype(np.int32)
    if args.chips == 1:
        phases = [("fp32", lambda: phase_fp32(keys, values, queries, clock,
                                              args)),
                  ("int8", lambda: phase_int8(keys, values, queries, clock,
                                              args)),
                  ("join", lambda: phase_offline_join(clock, args))]
    else:
        phases = [("sharded", lambda: phase_sharded(
            keys, values, queries, clock, args, args.chips))]
    failed = []
    for name, phase in phases:       # a failed phase does not hide the rest
        try:
            phase()
        except Exception as e:  # noqa: BLE001 — reported, fails the run
            if not isinstance(e, SmokeFailure):
                traceback.print_exc()
            print(f"FAIL: {name}: {type(e).__name__}: {e}", flush=True)
            failed.append(name)
        gc.collect()
    require(not failed, f"phases failed: {failed}")
    return {"ok": True, "device": {"platform": dev.platform,
                                   "kind": dev.device_kind,
                                   "count": args.chips}}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: fp32, int8 and join phases; 4: the sharded "
                         "datastore only")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--sample", type=int, default=64,
                    help="queries per phase checked against brute force")
    args = ap.parse_args()
    try:
        result = run(args)
    except SmokeFailure as e:
        print(f"FAIL: {e}", flush=True)
        return 1
    except Exception as e:  # noqa: BLE001 — any other error fails the run
        traceback.print_exc()
        print(f"FAIL: {type(e).__name__}: {e}", flush=True)
        return 1
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
