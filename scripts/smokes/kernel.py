"""Kernel smoke: EVERY Pallas path of the fused projection engine.

Covers, against the unfused/pure-jnp references:

  * raw ops — fused block_projection (single + multi-RHS), the split
    proj_gather/proj_scatter pair, and the Cimmino gather/scatter pair,
    including a non-multiple-of-128 n and a p=1 edge block;
  * sparse ops — the compressed-support ``sparse_proj_update`` /
    ``sparse_cimmino_update`` pair vs the einsum oracles with the engine
    pinned fused, then end-to-end silent sparse dispatch (local + mesh,
    fused-residual history parity) and a ``precision="mixed"`` solve;
  * solver paths — apc / consensus / cimmino with ``use_kernel=True`` on
    the local AND mesh backends (forced 4-host-device 2x2 data x model
    mesh, so the column-sharded gather/psum/scatter composition runs),
    plus the fused multi-RHS ``solve_many``;
  * serving — a ``LinsysServer(use_kernel=True)`` batch at zero
    steady-state retraces;
  * autotune — the BN cache fills, and ``REPRO_KERNEL_BN`` pins.

Interpret vs compiled: the smoke honors the ambient
``REPRO_PALLAS_INTERPRET`` (ci.sh runs it with ``=1`` every push; lanes
where Pallas lowering is available re-run it with ``=0`` so lowering
regressions surface — exactly the use ``default_interpret`` promises).
"""
import os

os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=4")
os.environ.setdefault("REPRO_PALLAS_INTERPRET", "1")

import time  # noqa: E402

import _path  # noqa: F401

import jax  # noqa: E402

jax.config.update("jax_enable_x64", True)

import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro import solvers  # noqa: E402
from repro.data import linsys  # noqa: E402
from repro.kernels import block_projection as bp  # noqa: E402
from repro.kernels import ops, ref  # noqa: E402
from repro.launch.mesh import make_mesh  # noqa: E402
from repro.solvers import FactorStore, LinsysServer  # noqa: E402

PROJ = ("apc", "consensus", "cimmino")


def _mk(p, n, k, dtype, seed=0):
    rng = np.random.default_rng(seed)
    A = jnp.asarray(rng.standard_normal((p, n)), dtype)
    G = np.asarray(A, np.float64) @ np.asarray(A, np.float64).T
    B = jnp.asarray(np.linalg.solve(G, np.asarray(A, np.float64)), dtype).T
    shp = (n,) if k == 1 else (k, n)
    x = jnp.asarray(rng.standard_normal(shp), dtype)
    xb = jnp.asarray(rng.standard_normal(shp), dtype)
    b = jnp.asarray(rng.standard_normal((p,) if k == 1 else (k, p)), dtype)
    return A, B, x, xb, b


def smoke_raw_ops():
    for p, n, k, dtype, tol in ((8, 256, 1, jnp.float32, 1e-4),
                                (7, 130, 5, jnp.float64, 1e-10),
                                (1, 128, 16, jnp.float64, 1e-10)):
        A, B, x, xb, b = _mk(p, n, k, dtype)
        y = ops.block_projection(A, B, x, xb, 1.2)
        np.testing.assert_allclose(
            np.asarray(y), np.asarray(ref.block_projection_ref(A, B, x, xb,
                                                               1.2)),
            rtol=tol, atol=tol)
        u = ops.proj_gather(A, x, xb)
        np.testing.assert_allclose(
            np.asarray(u), np.asarray(ref.apc_gather_ref(A, x, xb)),
            rtol=tol, atol=tol)
        y2 = ops.proj_scatter(B, x, xb, u, 0.8)
        np.testing.assert_allclose(
            np.asarray(y2),
            np.asarray(ref.apc_scatter_ref(B, x, xb, u, 0.8)),
            rtol=tol, atol=tol)
        r = ops.cimmino_update(A, B, b, xb)
        np.testing.assert_allclose(
            np.asarray(r), np.asarray(ref.cimmino_update_ref(A, B, b, xb)),
            rtol=tol, atol=tol * 10)
    assert len(ops.bn_cache()) > 0, "BN autotune cache never filled"


def smoke_solver_paths():
    assert len(jax.devices()) == 4, jax.devices()
    sys_ = linsys.conditioned_gaussian(n=96, m=4, cond=10.0, seed=3)
    mesh = make_mesh((2, 2), ("data", "model"))
    Bk = np.random.default_rng(4).standard_normal((5, sys_.N))
    for name in PROJ:
        s = solvers.get(name)
        prm = s.resolve_params(sys_)
        r0 = s.solve(sys_, iters=100, **prm)
        for tag, plan in (
                ("local", solvers.ExecutionPlan(kernel=True)),
                ("mesh", solvers.ExecutionPlan(kernel=True, backend="mesh",
                                               mesh=mesh))):
            rk = s.solve(sys_, iters=100, plan=plan, **prm)
            assert np.allclose(np.asarray(rk.residuals),
                               np.asarray(r0.residuals),
                               rtol=1e-6, atol=1e-12), (name, tag)
        m0 = s.solve_many(sys_, Bk, iters=100, **prm)
        mk = s.solve_many(sys_, Bk, iters=100,
                          plan=solvers.ExecutionPlan(kernel=True), **prm)
        assert np.allclose(np.asarray(mk.residuals),
                           np.asarray(m0.residuals),
                           rtol=1e-6, atol=1e-12), name


def smoke_sparse_paths():
    """Sparse fused pair + mixed precision (PR 9): raw ops against the
    einsum oracles with the engine PINNED fused (so the autotune cannot
    route around the kernels), then end-to-end dispatch parity."""
    rng = np.random.default_rng(6)
    for p, w, n, k, dtype, tol in ((8, 128, 256, 1, jnp.float32, 1e-4),
                                   (7, 61, 130, 5, jnp.float64, 1e-10)):
        vals = jnp.asarray(rng.standard_normal((p, w)), dtype)
        cols = jnp.asarray(rng.choice(n, size=w, replace=False), jnp.int32)
        bvals = jnp.asarray(rng.standard_normal((w, p)), dtype)
        shp = (n,) if k == 1 else (k, n)
        x = jnp.asarray(rng.standard_normal(shp), dtype)
        xb = jnp.asarray(rng.standard_normal(shp), dtype)
        b = jnp.asarray(rng.standard_normal((p,) if k == 1 else (k, p)),
                        dtype)
        prev = os.environ.get(ops.ENGINE_ENV)
        os.environ[ops.ENGINE_ENV] = "fused"
        try:
            y, u = ops.sparse_proj_update(vals, cols, bvals, x, xb, 0.9)
            yr, ur = ref.sparse_proj_update_ref(vals, cols, bvals, x, xb,
                                                0.9)
            np.testing.assert_allclose(np.asarray(y), np.asarray(yr),
                                       rtol=tol, atol=tol)
            np.testing.assert_allclose(np.asarray(u), np.asarray(ur),
                                       rtol=tol, atol=tol)
            r, uc = ops.sparse_cimmino_update(vals, cols, bvals, b, xb)
            rr, ucr = ref.sparse_cimmino_update_ref(vals, cols, bvals, b,
                                                    xb)
            np.testing.assert_allclose(np.asarray(r), np.asarray(rr),
                                       rtol=tol, atol=tol)
            np.testing.assert_allclose(np.asarray(uc), np.asarray(ucr),
                                       rtol=tol, atol=tol)
        finally:
            if prev is None:
                os.environ.pop(ops.ENGINE_ENV, None)
            else:
                os.environ[ops.ENGINE_ENV] = prev

    # end-to-end: silent sparse dispatch + fused-residual history parity
    import warnings
    sys_ = linsys.banded_system(n=192, m=4, bandwidth=6, seed=0)
    mesh = make_mesh((2, 2), ("data", "model"))
    for name in ("apc", "cimmino"):
        s = solvers.get(name)
        prm = s.resolve_params(sys_)
        r0 = s.solve(sys_, iters=80, **prm)
        with warnings.catch_warnings():
            warnings.simplefilter("error", RuntimeWarning)
            rk = s.solve(sys_, iters=80,
                         plan=solvers.ExecutionPlan(kernel=True), **prm)
            rm = s.solve(sys_, iters=80,
                         plan=solvers.ExecutionPlan(kernel=True,
                                                    backend="mesh",
                                                    mesh=mesh), **prm)
        for tag, r in (("local", rk), ("mesh", rm)):
            assert np.allclose(np.asarray(r.residuals),
                               np.asarray(r0.residuals),
                               rtol=1e-4, atol=2e-6), (name, tag)
        # mixed precision: bf16 tile streams must stay finite and track
        # the f32 history within the bf16 envelope
        rx = s.solve(sys_, iters=80,
                     plan=solvers.ExecutionPlan(kernel=True,
                                                precision="mixed"), **prm)
        res = np.asarray(rx.residuals)
        assert np.all(np.isfinite(res)), name
        assert np.allclose(res, np.asarray(r0.residuals),
                           rtol=0.5, atol=5e-2), (name, float(res[-1]))


def smoke_serving():
    sys_ = linsys.conditioned_gaussian(n=96, m=4, cond=10.0, seed=3)
    store = FactorStore()
    srv = LinsysServer(store, solver="apc", iters=300, batch=4,
                       use_kernel=True)
    fp = srv.register(sys_)
    rng = np.random.default_rng(0)
    sizes = []
    for _ in range(3):
        for _ in range(4):
            srv.submit(fp, rng.standard_normal(sys_.N))
        out = srv.step()
        assert all(r.residual < 1e-6 for r in out), [r.residual for r in out]
        sizes.append(srv.jit_cache_size())
    tail = sizes[1:]
    assert (-1 in tail) or len(set(tail)) == 1, sizes
    assert store.stats.misses == 1 and store.stats.hits >= 2, store.stats


def main():
    t0 = time.time()
    mode = ("interpret" if bp.default_interpret() else "COMPILED")
    smoke_raw_ops()
    smoke_solver_paths()
    smoke_sparse_paths()
    smoke_serving()
    print(f"kernel smoke OK ({mode}, "
          f"REPRO_PALLAS_INTERPRET={os.environ['REPRO_PALLAS_INTERPRET']}): "
          f"raw ops + sparse/mixed + 3 solvers x local/mesh/solve_many + "
          f"serving, bn cache {ops.bn_cache()} in {time.time()-t0:.1f}s")


if __name__ == "__main__":
    main()
