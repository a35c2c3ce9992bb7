"""Cells of the benchmark cut to a size a CPU test run can hold. Only
the scale changes; the loop, the checks and the limits are the cell's
own."""
from __future__ import annotations

import copy

from bench import harness


# A cell the benchmark does not declare, kept so that its loop (closed,
# fresh queries) is driven through the whole harness: sift1m.bulk, left
# out of BENCHMARK.json while device-to-host fetches stall.
UNDECLARED = {
    "sift1m.bulk": ("sift1m-k10", {"loop": "closed", "queries": "fresh",
                                   "batch_rows": 4096, "cycle_batches": 16,
                                   "master_seed": 20121207}),
}


def small(cell: str):
    spec = copy.deepcopy(harness.load_spec())
    if cell in UNDECLARED:
        config, mix = UNDECLARED[cell]
        spec["workloads"].append({"name": cell, "config": config,
                                  "traffic": None, "chips": 1})
        for m in spec["end_to_end"]:
            if m["name"] == "rows_per_s":
                m["workloads"].append(cell)
    else:
        c = {w["name"]: w for w in spec["workloads"]}[cell]
        config, mix = c["config"], harness.load_json("traffic", c["traffic"])
    cfg = copy.deepcopy(harness.load_json("configs", config))
    mix = copy.deepcopy(mix)
    data = cfg["data"]
    if data["generator"] == "clustered":
        data["n"] = 6000
    else:
        data["n_base"] = 1500
    cfg["build"]["n_pivots"] = 24
    cfg["check"]["sample_rows"] = 256
    if mix["loop"] == "closed":
        mix["batch_rows"] = 256
        mix["cycle_batches"] = 4
        if "n_cells" in mix:
            mix["n_cells"] = 24
    else:
        mix["rate_per_s"] = 40.0
    return spec, cfg, mix


def run_small(cell, seed, tmp_path, *, trace=False, seconds=2.0,
              monkeypatch=None, mix=None):
    """One run of the small cell; ``mix`` overrides keys of its
    traffic."""
    spec, cfg, mix_ = small(cell)
    mix = dict(mix_, **(mix or {}))
    if monkeypatch is not None:
        from repro import compile_cache
        monkeypatch.setattr(compile_cache, "enable_compile_cache",
                            lambda: "off")
    return harness.run(cell, seed, seconds, trace, require_tpu=False,
                       spec=spec, cfg=cfg, mix=mix, out_dir=tmp_path)
