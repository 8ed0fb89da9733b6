"""The fused Pallas iteration engine across the whole projection family.

What PR 5 claims, tested:

  * ``use_kernel=True`` on apc / consensus / cimmino matches the unfused
    path to <= 1e-6 relative on BOTH backends (the in-process mesh is
    (1, 1) — the full shard_map + Pallas path executes; the true 2x2
    multi-device parity runs as a slow subprocess test, mirrored by the
    CI kernel smoke).
  * ``solve_many`` routes batches through the true multi-RHS kernels and
    matches the unfused batched path.
  * ``LinsysServer(use_kernel=True)`` serves at zero steady-state
    retraces on both backends.
  * The ``FactorStore`` augments an entry with the pinv factors exactly
    ONCE — including through the mesh-side ``lookup``/``insert`` split
    (the PR-5 bugfix) — with the augmentation visible in ``store.stats``
    as hits, never as extra misses.
"""
import os
import subprocess
import sys

import numpy as np
import pytest

from repro import solvers
from repro.data import linsys
from repro.launch import mesh as mesh_lib
from repro.solvers import FactorStore, LinsysServer

PROJ = ["apc", "consensus", "cimmino"]
ITERS = 120

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ENV = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))


@pytest.fixture(scope="module")
def sys_():
    return linsys.conditioned_gaussian(n=96, m=4, cond=10.0, seed=3)


@pytest.fixture(scope="module")
def mesh():
    return mesh_lib.solver_mesh(1, 1)


def _close(a, b, rtol=1e-6, atol=1e-12):
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=rtol, atol=atol)


# ---------------------------------------------------------------------------
# Kernel path == unfused path, local and mesh, single and batched RHS
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("name", PROJ)
def test_kernel_matches_unfused_local(sys_, name):
    s = solvers.get(name)
    prm = s.resolve_params(sys_)
    r0 = s.solve(sys_, iters=ITERS, **prm)
    rk = s.solve(sys_, iters=ITERS, use_kernel=True, **prm)
    _close(rk.residuals, r0.residuals)
    _close(rk.x, r0.x, rtol=1e-8, atol=1e-10)
    assert rk.iters_to_tol == r0.iters_to_tol


@pytest.mark.parametrize("name", PROJ)
def test_kernel_matches_unfused_mesh(sys_, mesh, name):
    """use_kernel=True composes with backend='mesh': each worker shard
    runs the kernel on its local block, psum contract unchanged."""
    s = solvers.get(name)
    prm = s.resolve_params(sys_)
    r0 = s.solve(sys_, iters=ITERS, **prm)
    rk = s.solve(sys_, iters=ITERS, use_kernel=True, backend="mesh",
                 mesh=mesh, **prm)
    _close(rk.residuals, r0.residuals)
    _close(rk.x, r0.x, rtol=1e-8, atol=1e-10)


@pytest.mark.parametrize("name", PROJ)
@pytest.mark.parametrize("backend", ["local", "mesh"])
def test_solve_many_kernel_matches_unfused(sys_, mesh, name, backend):
    """The multi-RHS kernel path (one A/B read serves the whole batch)
    returns the same batched histories as the unfused driver."""
    s = solvers.get(name)
    prm = s.resolve_params(sys_)
    B = np.random.default_rng(4).standard_normal((6, sys_.N))
    kw = dict(backend=backend, mesh=mesh) if backend == "mesh" else {}
    r0 = s.solve_many(sys_, B, iters=ITERS, **prm)
    rk = s.solve_many(sys_, B, iters=ITERS, use_kernel=True, **kw, **prm)
    assert rk.x.shape == (6, sys_.n)
    _close(rk.residuals, r0.residuals)
    np.testing.assert_array_equal(np.asarray(rk.iters_to_tol),
                                  np.asarray(r0.iters_to_tol))


def test_kernel_state_warm_starts_unfused(sys_):
    """Kernel and unfused runs share the state layout: a kernel half-run
    resumes through the unfused driver exactly (and vice versa)."""
    s = solvers.get("apc")
    prm = s.resolve_params(sys_)
    full = s.solve(sys_, iters=100, **prm)
    half = s.solve(sys_, iters=50, use_kernel=True, **prm)
    rest = s.solve(sys_, iters=50, warm_state=half.state, **prm)
    _close(rest.x, full.x, rtol=1e-8, atol=1e-10)
    half_u = s.solve(sys_, iters=50, **prm)
    rest_k = s.solve(sys_, iters=50, use_kernel=True,
                     warm_state=half_u.state, **prm)
    _close(rest_k.x, full.x, rtol=1e-8, atol=1e-10)


def test_redundancy_still_rejects_kernel(sys_):
    with pytest.raises(ValueError, match="use_kernel"):
        solvers.get("apc").solve(sys_, iters=5, redundancy=2,
                                 use_kernel=True)


# ---------------------------------------------------------------------------
# FactorStore: augment-once through every acquisition path (PR-5 bugfix)
# ---------------------------------------------------------------------------


def test_store_augments_once_local(sys_):
    s = solvers.get("apc")
    prm = s.resolve_params(sys_)
    store = FactorStore()
    f1 = store.factors(s, sys_, use_kernel=True, **prm)
    assert f1.B is not None
    assert store.stats.misses == 1 and store.stats.hits == 0
    f2 = store.factors(s, sys_, use_kernel=True, **prm)
    # the SAME augmented object comes back — kernel_factors detected the
    # augmentation instead of recomputing the pinv
    assert f2 is f1
    assert store.stats.misses == 1 and store.stats.hits == 1


def test_store_augments_once_mesh_lookup_insert(sys_, mesh):
    """The mesh backend's lookup/insert split must augment-once too: a
    kernel mesh solve that MISSES inserts an already-augmented entry, a
    kernel mesh solve that HITS gets the augmentation written back —
    never extra misses, never a second pinv computation."""
    s = solvers.get("apc")
    prm = s.resolve_params(sys_)

    # mesh-side miss: on-mesh kernel prepare inserts augmented factors
    store = FactorStore()
    s.solve(sys_, iters=10, use_kernel=True, backend="mesh", mesh=mesh,
            store=store, **prm)
    assert store.stats.misses == 1 and store.stats.hits == 0, store.stats
    key = store.key(s, sys_, **prm)
    assert store._mem[key].B is not None
    # local kernel hit reuses it unchanged (no extra miss, same object)
    cached = store._mem[key]
    s.solve(sys_, iters=10, use_kernel=True, store=store, **prm)
    assert store.stats.misses == 1 and store.stats.hits == 1, store.stats
    assert store._mem[key] is cached

    # unfused entry first, then a kernel MESH hit: augmented in place
    store2 = FactorStore()
    s.solve(sys_, iters=10, store=store2, **prm)            # plain miss
    assert store2._mem[store2.key(s, sys_, **prm)].B is None
    s.solve(sys_, iters=10, use_kernel=True, backend="mesh", mesh=mesh,
            store=store2, **prm)                            # kernel hit
    assert store2.stats.misses == 1 and store2.stats.hits == 1, store2.stats
    aug = store2._mem[store2.key(s, sys_, **prm)]
    assert aug.B is not None
    # and a second kernel mesh solve reuses the augmented entry as-is
    s.solve(sys_, iters=10, use_kernel=True, backend="mesh", mesh=mesh,
            store=store2, **prm)
    assert store2.stats.misses == 1 and store2.stats.hits == 2, store2.stats
    assert store2._mem[store2.key(s, sys_, **prm)] is aug


# ---------------------------------------------------------------------------
# Serving: the batched kernel path at zero steady-state retraces
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("backend", ["local", "mesh"])
def test_server_kernel_zero_retrace(sys_, mesh, backend):
    kw = {"mesh": mesh} if backend == "mesh" else {}
    store = FactorStore()
    srv = LinsysServer(store, solver="apc", iters=300, batch=3,
                       backend=backend, use_kernel=True, **kw)
    fp = srv.register(sys_)
    rng = np.random.default_rng(0)
    sizes = []
    for _ in range(4):
        for _ in range(3):
            srv.submit(fp, rng.standard_normal(sys_.N))
        out = srv.step()
        assert all(r.residual < 1e-6 for r in out)
        sizes.append(srv.jit_cache_size())
    tail = sizes[1:]
    assert (-1 in tail) or len(set(tail)) == 1, sizes
    assert store.stats.misses == 1 and store.stats.hits >= 3


def test_server_kernel_matches_unfused(sys_):
    rng = np.random.default_rng(1)
    rhs = [rng.standard_normal(sys_.N) for _ in range(4)]
    xs = {}
    for use_kernel in (False, True):
        srv = LinsysServer(FactorStore(), solver="cimmino", iters=400,
                           batch=4, use_kernel=use_kernel)
        fp = srv.register(sys_)
        for r in rhs:
            srv.submit(fp, r)
        xs[use_kernel] = np.stack([r.x for r in srv.drain()])
    _close(xs[True], xs[False], rtol=1e-8, atol=1e-10)


def test_server_rejects_kernel_for_gradient_family():
    with pytest.raises(ValueError, match="use_kernel"):
        LinsysServer(FactorStore(), solver="dgd", use_kernel=True)


# ---------------------------------------------------------------------------
# True multi-device parity (slow subprocess, mirrored by the CI smoke)
# ---------------------------------------------------------------------------


_SUBPROCESS_KERNEL_PARITY = """
import jax
jax.config.update("jax_enable_x64", True)
import numpy as np
from repro import solvers
from repro.data import linsys
from repro.launch.mesh import make_mesh

assert len(jax.devices()) == 4, jax.devices()
sys_ = linsys.conditioned_gaussian(n=96, m=4, cond=10.0, seed=3)
mesh = make_mesh((2, 2), ("data", "model"))
B = np.random.default_rng(4).standard_normal((5, sys_.N))
for name in ("apc", "consensus", "cimmino"):
    s = solvers.get(name)
    prm = s.resolve_params(sys_)
    r0 = s.solve(sys_, iters=120, **prm)
    rk = s.solve(sys_, iters=120, use_kernel=True, backend="mesh",
                 mesh=mesh, **prm)
    np.testing.assert_allclose(np.asarray(rk.residuals),
                               np.asarray(r0.residuals),
                               rtol=1e-6, atol=1e-12)
    m0 = s.solve_many(sys_, B, iters=120, **prm)
    mk = s.solve_many(sys_, B, iters=120, use_kernel=True,
                      backend="mesh", mesh=mesh, **prm)
    np.testing.assert_allclose(np.asarray(mk.residuals),
                               np.asarray(m0.residuals),
                               rtol=1e-6, atol=1e-12)
print("OK")
"""


@pytest.mark.slow
def test_kernel_mesh_parity_2x2_subprocess():
    """use_kernel=True on a REAL 2x2 (data x model) mesh: the n axis is
    column-sharded, each shard's kernel sees (p, n/2) blocks, and the
    psum between gather and scatter restores exact parity."""
    env = dict(ENV, XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run([sys.executable, "-c", _SUBPROCESS_KERNEL_PARITY],
                         env=env, capture_output=True, text=True,
                         timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "OK" in out.stdout
