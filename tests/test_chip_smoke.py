"""The chip smoke's phases, run in-process on the CPU at a tiny size.

``chip_smoke.py`` is what proves the main path on the TPU; here its phase
functions run in float32 with the Pallas kernels in interpret mode, and
every answer is checked in float64 numpy — so the script cannot rot
between chip runs.  The script itself must refuse to report success off
the chip, and without the rest of the repository.
"""
import importlib.util
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from repro import solvers

ROOT = Path(__file__).resolve().parents[1]
TOL = 1e-4
ITERS = 60


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def system(smoke):
    sys_ = smoke.make_system(256, 128, 4, seed=0)
    prm, _ = solvers.get("apc").analyze(sys_)
    return sys_, prm, smoke.host_A(sys_)


def test_phase_solve(smoke, system):
    sys_, prm, A64 = system
    assert sys_.A_blocks.dtype == np.float32
    a = smoke.phase_solve(sys_, prm, ITERS)
    b = np.asarray(sys_.b_blocks).reshape(-1)
    assert smoke.host_residuals(A64, a["x"], b)[0] <= TOL
    assert a["device_residual"] <= TOL
    assert 0 < a["iters_to_tol"] <= ITERS


def test_phase_serve(smoke, system):
    sys_, prm, A64 = system
    b = smoke.phase_serve(sys_, prm, ITERS, 0, A64)
    assert (b["served"], b["pending"]) == (smoke.REQUESTS, 0)
    assert b["retraces"] == 0
    assert np.all(b["residuals"] <= TOL)


def test_phase_kernel(smoke, system):
    sys_, prm, _ = system
    factors = solvers.FactorStore().factors(solvers.get("apc"), sys_,
                                            use_kernel=True, **prm)
    c = smoke.phase_kernel(sys_, factors, prm["gamma"], 0)
    assert c["rel_err"] <= TOL
    assert c["tpu_custom_call"] is False        # interpret mode on the CPU


def test_phase_mesh(smoke, system):
    sys_, prm, A64 = system
    out = smoke.phase_mesh(sys_, prm, ITERS, 0, A64, k=4)
    data = out["mesh"]["data"]
    for name in ("solve", "solve_many"):
        assert out[name]["agree"] <= TOL
        assert np.all(out[name]["residuals_mesh"] <= TOL)
        assert np.all(out[name]["residuals_local"] <= TOL)
    assert len({d for d, _ in out["shards"]}) == data
    assert all(s == (sys_.m // data, sys_.p, sys_.n)
               for _, s in out["shards"])


def _run(script, cwd):
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    return subprocess.run([sys.executable, str(script)], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


def test_refuses_off_the_chip():
    r = _run(ROOT / "chip_smoke.py", ROOT)
    assert r.returncode != 0
    last = json.loads(r.stdout.strip().splitlines()[-1])
    assert last["ok"] is False and last["device"]["platform"] == "cpu"


def test_fails_without_the_repository(tmp_path):
    shutil.copy(ROOT / "chip_smoke.py", tmp_path)
    r = _run(tmp_path / "chip_smoke.py", tmp_path)
    assert r.returncode != 0
    assert '"ok": true' not in r.stdout
