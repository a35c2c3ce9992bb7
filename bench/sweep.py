#!/usr/bin/env python3
"""Find the knee of an open-loop cell once: one process, one build, the
cell's traffic offered at rising fixed rates.

    python3 bench/sweep.py --workload sift1m.online --seed 5 \
        --seconds 10 --rates 60,90,120,150,180,210,240

Prints one JSON line per rate (offered rate, requests, failed, p50 and
p99 in ms, rows per second completed) and, last, the knee: the highest
rate at which no request failed and p99 stayed under the scheduler's
default deadline. The cells run at a fixed rate written into their
traffic file; this script is how that rate was found, not part of a run.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args()
    sys.path.insert(0, os.path.join(ROOT, "src"))
    sys.path.insert(0, ROOT)
    from bench import harness
    from repro import compile_cache
    from repro.serve import SchedulerConfig

    spec = harness.load_spec()
    c = {w["name"]: w for w in spec["workloads"]}[args.workload]
    harness.check_device(int(c["chips"]))
    compile_cache.enable_compile_cache()
    cfg = harness.load_json("configs", c["config"])
    mix = harness.load_json("traffic", c["traffic"])
    ctx = harness.make_context(args.workload, args.seed, args.seconds, cfg,
                               mix)
    system = harness.build_system(ctx)
    loop = harness.load_module("loops", mix["loop"])
    span = harness.span_factory(False)
    deadline_ms = 1e3 * SchedulerConfig().default_deadline_s
    knee = None
    for i, rate in enumerate(float(r) for r in args.rates.split(",")):
        ctx.mix = dict(mix, rate_per_s=rate)
        ctx.seed = args.seed + i
        p = loop.plan(ctx)
        if i == 0:
            loop.warm(system, p)
        rec = loop.measure(system, p, args.seconds, span)
        rows = sum(t["rows"] for t in rec.tickets if t["status"] == "done")
        row = {"rate_per_s": rate, "requests": rec.attempted,
               "failed": rec.failed, **rec.values,
               "rows_per_s_done": rows / args.seconds,
               "batch_rows": rec.sched["rows_completed"]
               / max(1, rec.sched["n_dispatches"]),
               "generator_late_ms": rec.side["generator_late_ms"]}
        print(json.dumps(row), flush=True)
        if rec.failed == 0 and rec.values["p99_ms"] < deadline_ms:
            knee = rate
    print(json.dumps({"knee_rate_per_s": knee,
                      "rule": "highest rate with no failed request and "
                              "p99 under the default deadline"}),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
