"""The command refuses to measure anywhere but on a TPU of the peaks table,
and prints no result when it refuses or cannot run."""
from __future__ import annotations

import os
import shutil
import subprocess
import sys
import types

import pytest

from bench_testkit import ROOT
from bench import harness


def _run(cwd, *extra_env):
    env = {**os.environ, "JAX_PLATFORMS": "cpu", **dict(extra_env)}
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "tall16k.open",
         "--seed", str(2**31 + 1), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=120)


def test_cli_refuses_a_cpu_backend():
    p = _run(ROOT)
    assert p.returncode == 2
    assert p.stdout.strip() == ""
    assert "refused" in p.stderr


def test_cli_fails_with_only_the_benchmark_files(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    for d in harness.load_spec()["paths"]:
        shutil.copytree(ROOT / d, tmp_path / d,
                        ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path)
    assert p.returncode != 0
    assert p.stdout.strip() == ""


def _fake(platform, kind, count):
    dev = types.SimpleNamespace(platform=platform, device_kind=kind)
    return lambda: [dev] * count


@pytest.mark.parametrize("platform,kind,count,chips", [
    ("cpu", "cpu", 1, 1),                      # no accelerator
    ("tpu", "TPU v9 imagined", 1, 1),          # not in the peaks table
    ("gpu", "TPU v5 lite", 1, 1),              # right name, wrong platform
    ("tpu", "TPU v5 lite", 1, 4),              # fewer chips than asked
])
def test_device_check_refuses(monkeypatch, platform, kind, count, chips):
    import jax
    monkeypatch.setattr(jax, "devices", _fake(platform, kind, count))
    with pytest.raises(harness.NoChip):
        harness.device_info(chips, require_tpu=True)


def test_device_check_accepts_a_v5e(monkeypatch):
    import jax
    monkeypatch.setattr(jax, "devices", _fake("tpu", "TPU v5 lite", 4))
    info, peaks = harness.device_info(4, require_tpu=True)
    assert info == {"platform": "tpu", "kind": "TPU v5 lite", "count": 4}
    assert peaks["hbm_bytes_per_s"] == 819e9
