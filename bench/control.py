#!/usr/bin/env python3
"""Readings of the control: the plain reference put in the program's
place at the nearest lower precision (``high``, three bf16 passes),
judged by the same comparison as a run.

    python3 bench/control.py --workload <cell> --seeds 11,12,13

For each seed it makes the cell's data and traffic exactly as a run
does, takes as many query rows as a run checks, and prints the numbers
compared beside the cell's limits. The control has to come out not
correct on every seed; the smallest reading of each number is the upper
reading its limit is set below. Needs a TPU, like a run.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def readings(cell: str, seed: int, seconds: float, *,
             require_tpu: bool = True, cfg=None, mix=None) -> dict:
    import numpy as np

    from bench import correct, harness

    spec = harness.load_spec()
    c = {w["name"]: w for w in spec["workloads"]}[cell]
    harness.check_device(int(c["chips"]), require_tpu)
    cfg = cfg or harness.load_json("configs", c["config"])
    mix = mix or harness.load_json("traffic", c["traffic"])
    ctx = harness.make_context(cell, seed, seconds, cfg, mix)
    p = harness.load_module("loops", mix["loop"]).plan(ctx)
    n = int(cfg["check"]["sample_rows"])
    rng = np.random.default_rng(ctx.subseed("sample"))
    pick = correct.sample_rows(p.rows.shape[0], n, rng)
    q = p.rows[pick]
    ref = harness.load_module("references", cfg["metric"])
    searcher = ref.Searcher(ctx.data)
    k = int(cfg["k"])
    dref = ref.truth(searcher, q, k)
    d, ids = ref.control(searcher, q, k)
    numbers = correct.gaps(q, ctx.data, d, ids, dref, ref.exact_dists)
    numbers["unanswered"] = 0
    ok, table = correct.verdict(numbers, cfg["check"]["limits"])
    return {"seed": seed, "correct": ok, "check": table}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    args = ap.parse_args()
    sys.path.insert(0, os.path.join(ROOT, "src"))
    sys.path.insert(0, ROOT)
    for s in args.seeds.split(","):
        print(json.dumps(readings(args.workload, int(s), args.seconds)),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
