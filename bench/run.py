#!/usr/bin/env python3
"""Run one benchmark cell once, on the chip, and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

Run from the root of a checkout: the system under test is the checkout's
``src/repro``. The last lines on standard error are the numbers compared
for ``correct``, each beside its limit; the last line on standard output
is one JSON object (``correct``, ``attempted``, ``failed``, ``metrics``,
``device``, with ``--trace 1`` also ``breakdown``, and ``check`` last).
Exits non-zero, printing no result, when JAX finds no TPU or fewer chips
than the cell asks for, and when the checkout holds no ``src/repro``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import traceback

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()
    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        print(f"FAIL: no system under test: {ROOT}/src/repro is missing",
              file=sys.stderr)
        return 2
    # libtpu logs under /tmp unless told otherwise
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path.insert(0, os.path.join(ROOT, "src"))
    sys.path.insert(0, ROOT)
    from bench import harness
    try:
        result = harness.run(args.workload, args.seed, args.seconds,
                             bool(args.trace))
    except harness.NoChip as e:
        print(f"FAIL: {e}", file=sys.stderr)
        return 3
    except Exception as e:  # noqa: BLE001 — any error fails the run
        traceback.print_exc()
        print(f"FAIL: {type(e).__name__}: {e}", file=sys.stderr)
        return 1
    for name, row in result["check"].items():
        print(f"check {name} = {row['value']!r} (limit {row['limit']!r})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
