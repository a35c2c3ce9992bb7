"""The harness never measures off the chip."""
import os
import shutil
import subprocess
import sys

import pytest

from bench import harness


def test_check_device_refuses_cpu():
    with pytest.raises(harness.NoChip, match="not 'tpu'"):
        harness.check_device(1)


def test_check_device_counts_chips():
    with pytest.raises(harness.NoChip, match="asks for 4"):
        harness.check_device(4, require_tpu=False)


def test_run_refuses_cpu_before_any_work(monkeypatch):
    monkeypatch.setattr(harness, "load_json", lambda *a: pytest.fail(
        "read a configuration before checking the device"))
    with pytest.raises(harness.NoChip):
        harness.run("sift1m.online", 1, 1.0, False)


def _run_py(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "sift1m.online",
         "--seed", "3000000000", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_cli_exits_nonzero_without_a_result_on_cpu():
    p = _run_py(str(harness.ROOT))
    assert p.returncode != 0
    assert "not 'tpu'" in p.stderr
    assert p.stdout.strip() == ""


def test_cli_exits_nonzero_with_only_the_benchmark(tmp_path):
    shutil.copytree(harness.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(harness.ROOT / "BENCHMARK.json", tmp_path)
    p = _run_py(str(tmp_path), {"PYTHONPATH": ""})
    assert p.returncode != 0
    assert p.stdout.strip() == ""
