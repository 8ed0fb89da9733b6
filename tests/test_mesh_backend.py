"""Mesh execution backend: every registered solver through shard_map.

In-process tests run on a (1, 1) mesh — the full backend path (specs,
on-mesh prepare/init, shard_mapped scan, collectives) executes, the axes
just have size 1.  The true multi-device parity check (2 x 2 data x model
mesh, forced host devices) runs as a slow subprocess test, mirrored by the
tier-1 smoke in scripts/ci.sh.
"""
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from repro import solvers
from repro.data import linsys
from repro.launch import mesh as mesh_lib
from repro.solvers import mesh as mesh_backend

ALL = ["apc", "cimmino", "consensus", "dgd", "dhbm", "dnag", "madmm",
       "pdhbm"]
ITERS = 150

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ENV = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))


@pytest.fixture(scope="module")
def sys_():
    return linsys.conditioned_gaussian(n=64, m=4, cond=10.0, seed=3)


@pytest.fixture(scope="module")
def mesh():
    return mesh_lib.solver_mesh(1, 1)


def _assert_history_match(r_mesh, r_loc):
    np.testing.assert_allclose(np.asarray(r_mesh.x), np.asarray(r_loc.x),
                               rtol=1e-8, atol=1e-10)
    # rtol 1e-6 is the contract; atol covers the converged noise floor
    # where both histories sit at machine epsilon.
    np.testing.assert_allclose(np.asarray(r_mesh.residuals),
                               np.asarray(r_loc.residuals),
                               rtol=1e-6, atol=1e-12)


@pytest.mark.parametrize("name", ALL)
def test_mesh_matches_local(sys_, mesh, name):
    """backend='mesh' returns the same SolveResult as the local driver."""
    s = solvers.get(name)
    prm = s.resolve_params(sys_)
    r_loc = s.solve(sys_, iters=ITERS, **prm)
    r_mesh = s.solve(sys_, iters=ITERS, backend="mesh", mesh=mesh, **prm)
    assert r_mesh.name == name
    assert r_mesh.residuals.shape == (ITERS,)
    assert r_mesh.errors is not None          # x_true given -> error history
    assert r_mesh.params == prm
    _assert_history_match(r_mesh, r_loc)
    np.testing.assert_allclose(np.asarray(r_mesh.errors),
                               np.asarray(r_loc.errors),
                               rtol=1e-6, atol=1e-12)
    assert r_mesh.iters_to_tol == r_loc.iters_to_tol


@pytest.mark.parametrize("name", ALL)
def test_mesh_state_roundtrips_with_local(sys_, mesh, name):
    """Warm starts cross backends both ways: mesh -> local and local ->
    mesh resume exactly like an uninterrupted run."""
    s = solvers.get(name)
    prm = s.resolve_params(sys_)
    full = s.solve(sys_, iters=100, **prm)

    half_m = s.solve(sys_, iters=50, backend="mesh", mesh=mesh, **prm)
    res_l = s.solve(sys_, iters=50, warm_state=jax.device_get(half_m.state),
                    **prm)
    np.testing.assert_allclose(np.asarray(res_l.x), np.asarray(full.x),
                               rtol=1e-8, atol=1e-10)
    assert int(res_l.state.t) == 100

    half_l = s.solve(sys_, iters=50, **prm)
    res_m = s.solve(sys_, iters=50, backend="mesh", mesh=mesh,
                    warm_state=half_l.state, **prm)
    np.testing.assert_allclose(np.asarray(res_m.x), np.asarray(full.x),
                               rtol=1e-8, atol=1e-10)
    assert int(res_m.state.t) == 100


def test_mesh_state_roundtrips_through_checkpoint(sys_, mesh, tmp_path):
    from repro.checkpoint import ckpt
    s = solvers.get("apc")
    prm = s.resolve_params(sys_)
    r1 = s.solve(sys_, iters=40, backend="mesh", mesh=mesh, **prm)
    ckpt.save(str(tmp_path), 40, r1.state)
    restored = ckpt.restore(str(tmp_path), r1.state)
    r2 = s.solve(sys_, iters=40, backend="mesh", mesh=mesh,
                 warm_state=restored, **prm)
    full = s.solve(sys_, iters=80, **prm)
    np.testing.assert_allclose(np.asarray(r2.x), np.asarray(full.x),
                               rtol=1e-8, atol=1e-10)


@pytest.mark.parametrize("name", ["apc", "dhbm", "madmm"])
def test_mesh_solve_many_matches_local(sys_, mesh, name):
    s = solvers.get(name)
    B = np.random.default_rng(4).standard_normal((3, sys_.N))
    rm = s.solve_many(sys_, B, iters=100, backend="mesh", mesh=mesh)
    rl = s.solve_many(sys_, B, iters=100)
    assert rm.x.shape == (3, sys_.n)
    assert rm.residuals.shape == (3, 100)
    assert rm.errors is None
    _assert_history_match(rm, rl)
    np.testing.assert_array_equal(np.asarray(rm.iters_to_tol),
                                  np.asarray(rl.iters_to_tol))


def test_mesh_rejects_kernel_and_unknown_backend(sys_, mesh):
    # use_kernel now COMPOSES with backend="mesh" for the projection
    # family (see test_kernel_engine.py); it must still be rejected for
    # solvers without a kernel path, same as on the local backend.
    s = solvers.get("dgd")
    with pytest.raises(ValueError, match="use_kernel"):
        s.solve(sys_, iters=5, backend="mesh", mesh=mesh, use_kernel=True)
    s = solvers.get("apc")
    with pytest.raises(ValueError, match="backend"):
        s.solve(sys_, iters=5, backend="bogus")
    with pytest.raises(ValueError, match="backend='mesh'"):
        s.solve(sys_, iters=5, mesh=mesh)      # mesh given, backend local
    with pytest.raises(ValueError, match="backend='mesh'"):
        s.solve_many(sys_, np.ones((2, sys_.N)), iters=5, mesh=mesh)


def test_mesh_context_validates_axes(sys_):
    mesh1 = mesh_lib.make_mesh((1,), ("data",))
    ctx = mesh_backend.make_context(mesh1, sys_)   # model axis: absent -> None
    assert ctx.model_axis is None and ctx.worker_axes == ("data",)
    with pytest.raises(ValueError, match="worker axes"):
        mesh_backend.make_context(mesh1, sys_, worker_axes=("pod",))


def test_unimplemented_solver_raises(sys_, mesh):
    class Bare(solvers.Solver):
        name = "bare"

    with pytest.raises(NotImplementedError, match="mesh backend"):
        mesh_backend.solve_mesh(Bare(), sys_, mesh=mesh, iters=2)


@pytest.mark.slow
def test_all_solvers_mesh_parity_2x2_subprocess():
    """Acceptance check: every registered solver on a 4-device 2 x 2
    (data x model) host mesh matches its single-host residual history."""
    code = """
import jax
jax.config.update('jax_enable_x64', True)
import numpy as np
from repro import solvers
from repro.data import linsys
from repro.launch.mesh import make_mesh

assert len(jax.devices()) == 4
sys_ = linsys.conditioned_gaussian(n=64, m=4, cond=10.0, seed=3)
mesh = make_mesh((2, 2), ('data', 'model'))
for name in solvers.available():
    s = solvers.get(name)
    prm = s.resolve_params(sys_)
    rl = s.solve(sys_, iters=150, **prm)
    rm = s.solve(sys_, iters=150, backend='mesh', mesh=mesh, **prm)
    assert np.allclose(np.asarray(rm.residuals), np.asarray(rl.residuals),
                       rtol=1e-6, atol=1e-12), name
    assert np.allclose(np.asarray(rm.x), np.asarray(rl.x),
                       rtol=1e-8, atol=1e-10), name
print('OK')
"""
    r = subprocess.run(
        [sys.executable, "-c", code],
        env=dict(ENV, XLA_FLAGS="--xla_force_host_platform_device_count=4"),
        capture_output=True, text=True, timeout=900)
    assert "OK" in r.stdout, r.stdout[-1000:] + r.stderr[-2000:]
