"""BENCHMARK.json against the benchmark's contract, and every piece it
names found by name."""
import json
import os
import re

import pytest

from bench import harness

ROOT = harness.ROOT
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_./-]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def _line(s, n=200):
    return isinstance(s, str) and 1 <= len(s) <= n and "\n" not in s \
        and "\t" not in s


@pytest.fixture(scope="module")
def spec():
    return harness.load_spec()


def test_top_level_keys_and_sizes(spec):
    assert set(spec) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(ROOT / "BENCHMARK.json") <= 64 * 1024
    assert 1 <= len(spec["paths"]) <= 16
    for p in spec["paths"]:
        assert PATH.match(p) and not p.startswith("/") and ".." not in p
        assert (ROOT / p).is_dir()
    assert 1 <= len(spec["command"]) <= 32
    assert all(_line(w) for w in spec["command"])
    assert isinstance(spec["run_seconds"], int)
    assert 1 <= spec["run_seconds"] <= 51
    n = len(spec["workloads"])
    budget = (2 + 14 * 24) * (spec["run_seconds"] + 60) + 24 * 180 + 1200
    assert budget <= 43200 and 1 <= n <= 24


def test_entries(spec):
    names = set()
    for c in spec["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and _line(c["source"]) \
            and _line(c["why"])
        assert c["file"].startswith("bench/")
        with open(ROOT / c["file"]) as f:
            assert json.load(f)["name"] == c["name"]
        assert len(c["reduced"]) <= 16
        names.add(c["name"])
    used = set()
    pairs = set()
    for w in spec["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["config"] in names and w["chips"] in (1, 4)
        assert _line(w["why"])
        assert (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
        used.add(w["config"])
    assert used == names
    metric_names = set()
    for m in spec["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better",
                                          "bound", "source"}
        assert m["source"] in {"host_clock", "device_trace"}
        assert 0.01 <= m["bound"] <= 0.25
        metric_names.add(m["name"])
    assert "setup_s" in metric_names
    for m in spec["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better",
                                          "source", "layer", "moves"}
        assert m["moves"] in metric_names and _line(m["layer"])
        assert m["source"] in SOURCES
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    all_names = [m["name"] for m in spec["end_to_end"] + spec["per_layer"]]
    assert len(all_names) == len(set(all_names))
    for m in spec["per_layer"]:
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"


def test_every_cell_reports_setup_another_and_a_layer(spec):
    for w in spec["workloads"]:
        e2e = {m["name"] for m in harness.cell_metrics(spec, w["name"],
                                                       "end_to_end")}
        assert "setup_s" in e2e and len(e2e) >= 2
        layer = harness.cell_metrics(spec, w["name"], "per_layer")
        assert layer
        assert all(m["moves"] in e2e for m in layer)


def test_pieces_found_by_name(spec):
    for w in spec["workloads"]:
        cfg = harness.load_json("configs", w["config"])
        mix = harness.load_json("traffic", w["traffic"])
        assert hasattr(harness.load_module(
            "datagen", cfg["data"]["generator"]), "generate")
        assert hasattr(harness.load_module(
            "builders", cfg["build"]["builder"]), "build")
        loop = harness.load_module("loops", mix["loop"])
        assert all(hasattr(loop, f) for f in ("plan", "warm", "measure"))
        assert hasattr(harness.load_module("references", cfg["metric"]),
                       "truth")
        assert set(cfg["check"]["limits"]) == {
            "unanswered", "bad_ids", "rank_gap"}
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert callable(harness.load_module("metrics", m["name"]).read)
    with pytest.raises(FileNotFoundError):
        harness.load_module("metrics", "no.such.metric")


def test_peaks_table():
    with open(harness.BENCH / "peaks.json") as f:
        peaks = json.load(f)
    v5e = peaks["devices"]["TPU v5 lite"]
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9
    assert "TPU v5e" in peaks["source"]
