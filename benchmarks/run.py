# One function per paper table. Print ``name,us_per_call,derived`` CSV;
# ``--json PATH`` additionally records the rows as a JSON list.
import argparse
import inspect
import json
import sys
import traceback


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="substring filter on benchmark function names")
    ap.add_argument("--fast", action="store_true",
                    help="smaller sizes (CI mode)")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="also write rows as JSON records to PATH")
    args = ap.parse_args()
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()

    from . import kernel_bench, paper_tables, roofline
    from .common import HEADER

    fns = list(paper_tables.ALL) + list(kernel_bench.ALL) + list(roofline.ALL)
    if args.only:
        fns = [f for f in fns if args.only in f.__name__]

    print(HEADER)
    failures = 0
    records = []
    for fn in fns:
        try:
            kwargs = {}
            if args.fast:
                sig = inspect.signature(fn)
                if "n" in sig.parameters:
                    kwargs["n"] = 3000
                if "base_n" in sig.parameters:
                    kwargs["base_n"] = 1500
                # index-build / streaming benches: fewer micro-batches
                if "batches" in sig.parameters:
                    kwargs["batches"] = 3
            for row in fn(**kwargs):
                print(row.csv(), flush=True)
                # numpy scalars (int64/float32) are not JSON serializable
                records.append({"bench": row.bench, "params": row.params,
                                "seconds": float(row.seconds),
                                **{k: float(v)
                                   for k, v in row.derived.items()}})
        except Exception:  # noqa: BLE001 — keep the suite going
            failures += 1
            print(f"# FAILED {fn.__name__}", flush=True)
            traceback.print_exc(file=sys.stderr)
    if args.json:
        with open(args.json, "w") as fh:
            json.dump(records, fh, indent=2)
            fh.write("\n")
    if failures:
        sys.exit(1)


if __name__ == '__main__':
    main()
