"""HPCG's 27-point stencil on the sparse path, against a float64 reference
built here with ``scipy.sparse`` from the stencil's definition.

The operator (HPCG ``GenerateProblem``): row ``ix + nx·(iy + ny·iz)`` has
26 on the diagonal and −1 for each grid neighbour inside the grid; the
exact solution is all ones.  Solves run in float32, as on the chip.  The
sparse step projects against the support pinv that ``_sparse_factors``
makes on the host in float64, with no triangular solve in any step (its
compiled form for the TPU is checked in ``tests/test_tpu_compile.py``).
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
import scipy.sparse as sps
import scipy.sparse.linalg as spla

from repro import solvers
from repro.core import blockops
from repro.data import linsys
from repro.kernels import ops as kops
from repro.runtime import spans
from repro.solvers import projection

GRID = (8, 8, 16)
M = 4


def reference_operator(nx, ny, nz):
    """The stencil's float64 matrix, point by point from its definition."""
    n = nx * ny * nz
    rows, cols, vals = [], [], []
    for iz in range(nz):
        for iy in range(ny):
            for ix in range(nx):
                i = ix + nx * (iy + ny * iz)
                for jz in range(max(iz - 1, 0), min(iz + 2, nz)):
                    for jy in range(max(iy - 1, 0), min(iy + 2, ny)):
                        for jx in range(max(ix - 1, 0), min(ix + 2, nx)):
                            j = jx + nx * (jy + ny * jz)
                            rows.append(i)
                            cols.append(j)
                            vals.append(26.0 if i == j else -1.0)
    return sps.csr_matrix((vals, (rows, cols)), shape=(n, n))


@pytest.fixture
def f32():
    prev = jax.config.jax_enable_x64
    jax.config.update("jax_enable_x64", False)
    try:
        yield
    finally:
        jax.config.update("jax_enable_x64", prev)


@pytest.fixture(scope="module")
def ref():
    return reference_operator(*GRID)


def _stencil():
    return linsys.hpcg_stencil(*GRID, M, dtype=jnp.float32)


def _rel_res(A, x, b):
    x = np.asarray(x, np.float64)
    b = np.asarray(b, np.float64)
    return np.linalg.norm(A @ x - b) / np.linalg.norm(b)


@pytest.mark.parametrize("grid,m", [((4, 4, 8), 4), ((8, 8, 16), 4),
                                    ((3, 5, 6), 2)])
def test_the_operator_is_the_reference_entry_for_entry(grid, m):
    s = linsys.hpcg_stencil(*grid, m)
    R = reference_operator(*grid)
    A = np.asarray(s.A_blocks, np.float64).reshape(s.N, s.n)
    np.testing.assert_array_equal(A, R.toarray())
    assert s.is_sparse and s.mode == "square" and s.m == m
    np.testing.assert_array_equal(np.asarray(s.x_true), np.ones(s.n))
    np.testing.assert_array_equal(np.asarray(s.b_blocks).reshape(-1),
                                  R @ np.ones(s.n))                # b = A·1
    # the compressed operand holds every nonzero of its slab
    dense = np.asarray(blockops.densify(s.A_op))
    np.testing.assert_array_equal(dense, np.asarray(s.A_blocks))


def test_slabs_must_be_whole_planes():
    with pytest.raises(ValueError, match="z-slabs"):
        linsys.hpcg_stencil(4, 4, 6, 4)


def test_support_fill_is_the_hand_count():
    """4 × 4 × 8 in 4 z-slabs: (3·4−2)(3·4−2)(3·8−2) = 2200 nonzeros, p = 32
    rows a slab, the support of an inner slab 4 planes of 16 = 64 wide."""
    s = linsys.hpcg_stencil(4, 4, 8, 4)
    assert s.cols.shape == (4, 64)
    assert s.support_fill == 2200 / (4 * 32 * 64)
    assert s.densified().support_fill == 2200 / (128 * 128)


@pytest.mark.parametrize("kernel", [True, False], ids=["kernel", "plain"])
def test_apc_solves_the_stencil_in_float32(kernel, f32, ref):
    s = _stencil()
    apc = solvers.get("apc")
    prm = apc.resolve_params(s)
    v = np.random.default_rng(3).standard_normal(s.n)
    b = (ref @ v).astype(np.float32)
    r = apc.solve_many(s, b[None], iters=150,
                       plan=solvers.ExecutionPlan(kernel=kernel), **prm)
    x = np.asarray(r.x[0])
    assert x.dtype == np.float32
    assert _rel_res(ref, x, b) <= 1e-5
    np.testing.assert_allclose(x, spla.spsolve(ref.tocsc(), b), rtol=0,
                               atol=1e-5 * np.abs(v).max())
    # and the system's own b = A·1 gives HPCG's exact solution
    r1 = apc.solve(s, iters=150, plan=solvers.ExecutionPlan(kernel=kernel),
                   **prm)
    np.testing.assert_allclose(np.asarray(r1.x), np.ones(s.n), atol=1e-5)


def test_served_closed_loop_gives_the_solves_answers(f32, ref):
    """A ``LinsysServer(batch=1)`` time stepper, each next b built from the
    last answer, answers each step as ``solve`` answers that b."""
    s = _stencil()
    apc = solvers.get("apc")
    prm = apc.resolve_params(s)
    plan = solvers.ExecutionPlan(kernel=True)
    srv = solvers.LinsysServer(solver="apc", iters=150, tol=1e-4, batch=1,
                               plan=plan, **prm)
    fp = srv.register(s)
    b = (ref @ np.random.default_rng(4).standard_normal(s.n)).astype(
        np.float32)
    for _ in range(3):
        srv.submit(fp, b)
        (out,) = srv.step()
        want = apc.solve_many(s, b[None], iters=150, plan=plan, **prm).x[0]
        np.testing.assert_allclose(out.x, np.asarray(want), rtol=1e-5,
                                   atol=1e-6)
        assert _rel_res(ref, out.x, b) <= 1e-5
        assert 0 < out.iters_to_tol <= 150
        u = ref @ out.x.astype(np.float64)
        b = (u / np.linalg.norm(u) * np.linalg.norm(b)).astype(np.float32)


def test_sparse_factors_are_the_float64_factors_rounded_once(f32, ref):
    s = _stencil()
    f = projection._sparse_factors(s.A_op, 0.0)
    V = np.asarray(s.A_op.vals, np.float64)
    B = np.asarray(f.B, np.float64)
    assert f.B.dtype == jnp.float32 and f.B.shape == (M, s.cols.shape[1],
                                                      s.p)
    for i in range(M):
        want = np.linalg.solve(V[i] @ V[i].T, V[i]).T
        np.testing.assert_allclose(B[i], want.astype(np.float32), rtol=0,
                                   atol=2 * np.finfo(np.float32).eps
                                   * np.abs(want).max())
        # A_i B_i = I to float32 rounding: what the answers' accuracy rests on
        assert np.abs(V[i] @ B[i] - np.eye(s.p)).max() < 1e-6
        L = np.asarray(f.chol, np.float64)[i]
        G = V[i] @ V[i].T
        np.testing.assert_allclose(L @ L.T, G, rtol=0,
                                   atol=1e-6 * np.abs(G).max())


def _primitives(jaxpr):
    names = set()
    for eqn in jaxpr.eqns:
        names.add(eqn.primitive.name)
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else (v,)):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    names |= _primitives(sub)
    return names


@pytest.mark.parametrize("engine", ["unfused", "fused"])
@pytest.mark.parametrize("name", ["apc", "consensus"])
def test_no_sparse_step_traces_a_triangular_solve(name, engine, f32,
                                                  monkeypatch):
    """Neither engine of any sparse entry point the solve and serve paths
    call, with the kernel flag on or off, holds a triangular solve or a
    Cholesky: the factors come from the host, the step contracts against
    them."""
    monkeypatch.setenv(kops.ENGINE_ENV, engine)
    s = _stencil()
    sol = solvers.get(name)
    prm = sol.resolve_params(s)
    f = sol.prepare(s.A_op, prm)
    assert sol.kernel_factors(f) is f            # B came with prepare
    Bb = jnp.asarray(np.asarray(s.b_blocks))[None].repeat(2, axis=0)
    st = jax.vmap(lambda b: sol.init(f, b, prm))(Bb)
    one = jax.tree.map(lambda a: a[0], st)
    calls = [lambda: sol.init(f, Bb[0], prm),
             lambda: sol.step_residual(f, Bb[0], one, prm),
             lambda: sol.step_many_residual(f, Bb, st, prm)]
    for kernel in (True, False):
        calls += [lambda k=kernel: sol.step(f, Bb[0], one, prm,
                                            use_kernel=k),
                  lambda k=kernel: sol.step_many(f, Bb, st, prm,
                                                 use_kernel=k)]
    for fn in calls:
        prims = _primitives(jax.make_jaxpr(fn)().jaxpr)
        assert "triangular_solve" not in prims
        assert "cholesky" not in prims


def test_sparse_factor_span_times_the_prepare_once(f32):
    """``repro.proj.sparse_factor`` closes once, inside the store's prepare,
    when a registered sparse system is first served, and not again when
    the store hits."""
    spans.reset()
    s = _stencil()
    srv = solvers.LinsysServer(solver="apc", iters=20, batch=1,
                               plan=solvers.ExecutionPlan(kernel=True),
                               **solvers.get("apc").resolve_params(s))
    fp = srv.register(s)
    assert "repro.proj.sparse_factor" not in spans.totals()
    for _ in range(2):
        srv.submit(fp, np.asarray(s.b_blocks).reshape(-1))
        srv.step()
    t = spans.totals()
    fac, prep = t["repro.proj.sparse_factor"], t["repro.store.prepare"]
    assert fac.count == prep.count == 1
    assert 0 < fac.total_s <= prep.total_s - prep.self_s + 1e-9
    spans.reset()


@pytest.mark.parametrize("engine", ["unfused", "fused"])
def test_sparse_step_carries_its_device_scopes(engine, f32, monkeypatch):
    """The lowered sparse step names its three phases, so a device trace's
    ``tf_op`` splits it: gather, project, scatter under ``apc.step``."""
    monkeypatch.setenv(kops.ENGINE_ENV, engine)
    s = _stencil()
    apc = solvers.get("apc")
    prm = apc.resolve_params(s)
    f = apc.prepare(s.A_op, prm)
    Bb = jnp.asarray(np.asarray(s.b_blocks))[None]
    st = jax.vmap(lambda b: apc.init(f, b, prm))(Bb)
    text = jax.jit(lambda f_, st_: apc.step_many_residual(
        f_, Bb, st_, prm)).lower(f, st).compile().as_text()
    names = re.findall(r'op_name="([^"]*)"', text)
    for scope in ("apc.gather", "apc.project", "apc.scatter"):
        assert any(re.search(rf"apc\.step/(.*/)?{scope}/", name)
                   for name in names), scope
