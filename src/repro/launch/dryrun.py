import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=512").strip()

"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

Proves the distribution config is coherent without hardware: sharding
mismatches, compile-time OOM, or unsupported collectives fail here.
Outputs per cell: memory_analysis (fits/doesn't), cost_analysis flops &
bytes, and collective-operand bytes parsed from the post-SPMD HLO — the
three §Roofline terms derive from exactly this record.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch granite-34b \
      --shape train_4k [--multi-pod] [--out results.json]
  PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod]
"""
import argparse
import json
import re
import sys
import time
import traceback

import jax
import numpy as np

from repro.configs import ARCH_IDS, SHAPES, get_arch, runnable_cells
from repro.launch import hlo_analysis
from repro.launch.mesh import make_production_mesh
from repro.launch.specs import build_cell

# v5e-class hardware constants (per chip) for the roofline terms
PEAK_FLOPS = 197e12      # bf16
HBM_BW = 819e9           # B/s
LINK_BW = 50e9           # B/s per ICI link


_COLLECTIVE_RE = re.compile(
    r"\b(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?(?:\.\d+)?\s*\(")
_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "s64": 8, "u64": 8,
    "s32": 4, "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1, "pred": 1,
    "f8e4m3fn": 1, "f8e5m2": 1,
}


def collective_bytes(hlo_text: str) -> dict:
    """Sum result-shape bytes of every collective op in post-SPMD HLO.

    Works on the per-device (partitioned) module: shapes are shard-local,
    so the totals are per-device collective traffic per step.
    """
    out = {k: 0 for k in ("all-gather", "all-reduce", "reduce-scatter",
                          "all-to-all", "collective-permute")}
    for line in hlo_text.splitlines():
        line = line.strip()
        m = re.search(r"=\s*((?:\([^)]*\)|[a-z0-9]+\[[0-9,]*\][^ ]*))\s*"
                      r"(all-gather|all-reduce|reduce-scatter|all-to-all|"
                      r"collective-permute)", line)
        if not m:
            continue
        kind = m.group(2)
        nbytes = 0
        for dt, dims in _SHAPE_RE.findall(m.group(1)):
            if dt not in _DTYPE_BYTES:
                continue
            n = 1
            for d in dims.split(","):
                if d:
                    n *= int(d)
            nbytes += n * _DTYPE_BYTES[dt]
        out[kind] += nbytes
    out["total"] = sum(out.values())
    return out


def run_cell(arch_name: str, shape_name: str, *, multi_pod: bool,
             verbose: bool = True) -> dict:
    mesh = make_production_mesh(multi_pod=multi_pod)
    arch = get_arch(arch_name)
    cell = build_cell(arch, shape_name, mesh)
    rec = {
        "arch": arch_name, "shape": shape_name,
        "mesh": "x".join(str(mesh.shape[a]) for a in mesh.axis_names),
        "chips": int(np.prod(list(mesh.shape.values()))),
        "kind": cell.kind, "notes": cell.notes,
    }
    t0 = time.time()
    donate = (0, 1) if cell.kind == "train" else ()
    with mesh:
        jitted = jax.jit(cell.step_fn, in_shardings=cell.in_shardings,
                         donate_argnums=donate)
        lowered = jitted.lower(*cell.abstract_args)
        rec["lower_s"] = round(time.time() - t0, 1)
        t1 = time.time()
        compiled = lowered.compile()
        rec["compile_s"] = round(time.time() - t1, 1)

    ma = compiled.memory_analysis()
    rec["memory"] = {
        "argument_bytes": int(ma.argument_size_in_bytes),
        "output_bytes": int(ma.output_size_in_bytes),
        "temp_bytes": int(ma.temp_size_in_bytes),
        "alias_bytes": int(ma.alias_size_in_bytes),
        "peak_bytes": int(ma.argument_size_in_bytes
                          + ma.output_size_in_bytes
                          + ma.temp_size_in_bytes
                          - ma.alias_size_in_bytes),
    }
    # XLA-CPU stages bf16 scan stacks as bulk f32 buffers before its f32
    # dot kernels; TPU MXUs take bf16 directly, so subtract the artifact
    # for the fits-HBM verdict (both numbers are recorded).
    staging = hlo_analysis.cpu_bf16_convert_staging_bytes(compiled.as_text())
    rec["memory"]["cpu_convert_staging_bytes"] = int(staging)
    # floor at live arguments+outputs: the staging estimate can exceed the
    # true overlap when distinct-shape staging buffers are not co-live
    rec["memory"]["peak_bytes_tpu_adj"] = int(max(
        rec["memory"]["peak_bytes"] - staging,
        rec["memory"]["argument_bytes"] + rec["memory"]["output_bytes"]
        - rec["memory"]["alias_bytes"]))
    rec["memory"]["fits_hbm_16g"] = \
        rec["memory"]["peak_bytes_tpu_adj"] <= 16 * 2**30
    ca = hlo_analysis.cost_analysis_dict(compiled)
    rec["cost_analysis_raw"] = {"flops": float(ca.get("flops", 0.0)),
                                "bytes": float(ca.get("bytes accessed", 0.0))}
    # trip-count-scaled accounting (cost_analysis counts loop bodies once)
    totals = hlo_analysis.analyze(compiled.as_text())
    rec["cost"] = {"flops": totals.flops, "bytes": totals.bytes}
    rec["collectives"] = dict(totals.coll)
    rec["collectives"]["total"] = sum(totals.coll.values())

    chips = rec["chips"]
    flops, hbm_b = rec["cost"]["flops"], rec["cost"]["bytes"]
    coll_b = rec["collectives"]["total"]
    # cost_analysis is per-device on the partitioned module
    rec["roofline"] = {
        "compute_s": flops / PEAK_FLOPS,
        "memory_s": hbm_b / HBM_BW,
        "collective_s": coll_b / LINK_BW,
    }
    rec["roofline"]["bottleneck"] = max(
        rec["roofline"], key=lambda k: rec["roofline"][k])

    # model flops (per device): 6·N_active·tokens / chips
    shape = SHAPES[shape_name]
    tokens = shape.global_batch * shape.seq_len
    n_active = arch.active_param_count()
    mf = 6.0 * n_active * tokens
    if cell.kind != "train":
        mf /= 3.0                      # forward only
    if cell.kind == "decode":
        # decode flops ≈ 2·N_active per token + attention over the cache
        mf = 2.0 * n_active * shape.global_batch
    rec["model_flops_per_chip"] = mf / chips
    rec["useful_flop_ratio"] = (mf / chips) / max(flops, 1.0)

    if verbose:
        r = rec["roofline"]
        print(f"[{arch_name} × {shape_name} @ {rec['mesh']}] "
              f"peak={rec['memory']['peak_bytes']/2**30:.2f}GiB "
              f"compute={r['compute_s']*1e3:.2f}ms "
              f"memory={r['memory_s']*1e3:.2f}ms "
              f"collective={r['collective_s']*1e3:.2f}ms "
              f"bottleneck={r['bottleneck']} "
              f"useful={rec['useful_flop_ratio']:.2f} "
              f"lower={rec['lower_s']}s compile={rec['compile_s']}s "
              f"({rec['notes']})", flush=True)
    return rec


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS)
    ap.add_argument("--shape", choices=list(SHAPES))
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()

    cells = []
    if args.all:
        for a in ARCH_IDS:
            for s in runnable_cells(get_arch(a)):
                cells.append((a, s))
    else:
        if not (args.arch and args.shape):
            ap.error("--arch/--shape or --all required")
        cells = [(args.arch, args.shape)]

    results, failures = [], []
    for a, s in cells:
        try:
            results.append(run_cell(a, s, multi_pod=args.multi_pod))
        except Exception as e:  # noqa: BLE001 — report, keep sweeping
            traceback.print_exc()
            failures.append({"arch": a, "shape": s, "error": repr(e)})
    if args.out:
        with open(args.out, "w") as f:
            json.dump({"results": results, "failures": failures}, f, indent=1)
    print(f"\n{len(results)} cells OK, {len(failures)} failed")
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
