"""The pinv step: dense APC and consensus under ``use_kernel=True``, where
the engine autotune says the Pallas pair loses, project against the stored
pinv factor B = Aᵀ G⁻¹ with plain XLA contractions and no triangular solve.

``REPRO_KERNEL_ENGINE=unfused`` pins the fallback.  Each test runs for
both solvers, at one right-hand side and at the served batch of 16, on a
small float32 tall Gaussian (the benchmark's ensemble).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import solvers
from repro.core import apc as apc_core
from repro.core import blockops
from repro.data import linsys
from repro.kernels import ops as kops
from repro.solvers import api

NAMES = ["apc", "consensus"]
KS = [1, 16]
ITERS = 40
# float32 solves of one system by two engines that round differently: a
# few hundred ulps on unit-scale iterates, and on the relative residual
# histories, whose floor sits near 1e-6 late in a run
X_TOL = dict(rtol=1e-4, atol=1e-5)
HIST_TOL = dict(rtol=1e-3, atol=1e-5)


@pytest.fixture(autouse=True)
def _unfused(monkeypatch):
    monkeypatch.setenv(kops.ENGINE_ENV, "unfused")


@pytest.fixture(scope="module")
def sys_():
    return linsys.tall_gaussian(N=256, n=128, m=4, seed=2,
                                dtype=jnp.float32)


def _rhs(sys_, k):
    """k consistent right-hand sides b = A x*, as (k, N) and (k, m, p)."""
    xs = np.random.default_rng(k).standard_normal((k, sys_.n))
    A = np.asarray(sys_.A_blocks, np.float64).reshape(sys_.N, sys_.n)
    B = (xs @ A.T).astype(np.float32)
    return B, jnp.asarray(B.reshape(k, sys_.m, sys_.p))


def _setup(name, sys_, k, kernel=True):
    s = solvers.get(name)
    prm = s.resolve_params(sys_)
    f = s.prepare(sys_.A_op, prm)
    if kernel:
        f = s.kernel_factors(f)
    B, Bb = _rhs(sys_, k)
    states = jax.vmap(lambda b: s.init(f, b, prm))(Bb)
    return s, prm, f, B, Bb, states


def _primitives(jaxpr):
    """Every primitive name in ``jaxpr`` and in the jaxprs its equations
    carry (jit, scan, pallas_call bodies)."""
    names = set()
    for eqn in jaxpr.eqns:
        names.add(eqn.primitive.name)
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else (v,)):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    names |= _primitives(sub)
    return names


def _traced(s, prm, f, Bb, states):
    """The primitives of each entry point the drivers call."""
    b, st = Bb[0], jax.tree.map(lambda a: a[0], states)
    calls = {
        "step_many_residual": lambda: s.step_many_residual(f, Bb, states,
                                                           prm),
        "step_many": lambda: s.step_many(f, Bb, states, prm,
                                         use_kernel=True),
        "step": lambda: s.step(f, b, st, prm, use_kernel=True),
        "step_residual": lambda: s.step_residual(f, b, st, prm),
        "init": lambda: jax.vmap(lambda bk: s.init(f, bk, prm))(Bb),
    }
    return {k: _primitives(jax.make_jaxpr(fn)().jaxpr)
            for k, fn in calls.items()}


@pytest.mark.parametrize("k", KS)
@pytest.mark.parametrize("name", NAMES)
def test_pinv_step_traces_no_triangular_solve(sys_, name, k):
    s, prm, f, _, Bb, states = _setup(name, sys_, k)
    assert f.B is not None
    for entry, prims in _traced(s, prm, f, Bb, states).items():
        assert "triangular_solve" not in prims, entry
        assert "dot_general" in prims, entry


@pytest.mark.parametrize("k", KS)
@pytest.mark.parametrize("name", NAMES)
def test_cholesky_path_unchanged_without_pinv_factor(sys_, name, k):
    """``use_kernel=False``: the factors carry no B, every step solves
    against the Cholesky factor, and ``solve_many`` is bit-equal to the
    Cholesky formulation written out here (``core/apc.py`` step, min-norm
    init through ``_gram_solve``)."""
    s, prm, f, B, Bb, _ = _setup(name, sys_, k, kernel=False)
    assert f.B is None
    st = jax.vmap(lambda b: s.init(f, b, prm))(Bb)
    one = jax.tree.map(lambda a: a[0], st)
    for fn in (lambda: s.step_many(f, Bb, st, prm),
               lambda: s.step(f, Bb[0], one, prm),
               lambda: jax.vmap(lambda b: s.init(f, b, prm))(Bb)):
        assert "triangular_solve" in _primitives(jax.make_jaxpr(fn)().jaxpr)

    got = s.solve_many(sys_, B, iters=ITERS, **prm)

    def init(b):
        x0 = jax.vmap(lambda Ai, Li, bi: Ai.T @ apc_core._gram_solve(Li, bi))(
            f.A, f.chol, b)
        return apc_core.APCState(x=x0, xbar=jnp.mean(x0, axis=0),
                                 t=jnp.zeros((), jnp.int32))

    legacy = apc_core.APCFactors(A=f.A, chol=f.chol, x0=None, b=None)

    def step_many(_, bb, sts):
        return jax.vmap(lambda b, st_: apc_core.apc_step(
            legacy, st_, prm["gamma"], prm["eta"]))(bb, sts)

    states, res = api._history_scan_many(
        step_many, s.extract, f, Bb, jax.vmap(init)(Bb), sys_.A_op, ITERS)
    assert np.array_equal(np.asarray(got.x),
                          np.asarray(jax.vmap(s.extract)(states)))
    assert np.array_equal(np.asarray(got.residuals), np.asarray(res))


@pytest.mark.parametrize("k", KS)
@pytest.mark.parametrize("name", NAMES)
def test_pinv_solve_many_matches_cholesky(sys_, name, k):
    s = solvers.get(name)
    prm = s.resolve_params(sys_)
    B, _ = _rhs(sys_, k)
    pinv = s.solve_many(sys_, B, iters=ITERS,
                        plan=solvers.ExecutionPlan(kernel=True), **prm)
    chol = s.solve_many(sys_, B, iters=ITERS, **prm)
    assert not np.array_equal(np.asarray(pinv.x), np.asarray(chol.x))
    np.testing.assert_allclose(np.asarray(pinv.x), np.asarray(chol.x),
                               **X_TOL)
    np.testing.assert_allclose(np.asarray(pinv.residuals),
                               np.asarray(chol.residuals), **HIST_TOL)
    res = np.asarray(pinv.residuals)
    assert np.all(res[:, -1] < res[:, 0] / 10)          # it converges


@pytest.mark.parametrize("k", KS)
@pytest.mark.parametrize("name", NAMES)
def test_pinv_fused_residual_is_the_consumed_states(sys_, name, k):
    """The gather pass u = A_i(x̄ − x_i) still yields ‖A x̄ − b‖² of the
    state each step CONSUMED (A_i x_i = b_i survives the pinv init and
    step), which ``api._history_scan_many`` shifts by one."""
    s, prm, f, _, Bb, states = _setup(name, sys_, k)
    state = jax.tree.map(lambda a: a[0], states)
    for _ in range(4):
        r = blockops.bmatvec_many(sys_.A_op, states.xbar) - Bb
        true = np.sqrt(np.asarray(jnp.sum(r * r, axis=(1, 2))))
        r1 = blockops.bmatvec(sys_.A_op, state.xbar) - Bb[0]
        true1 = float(jnp.sqrt(jnp.sum(r1 * r1)))
        states, rsq = s.step_many_residual(f, Bb, states, prm)
        state, rsq1 = s.step_residual(f, Bb[0], state, prm)
        scale = float(np.sqrt(np.sum(np.asarray(Bb) ** 2, axis=(1, 2))).max())
        np.testing.assert_allclose(np.sqrt(np.asarray(rsq)), true,
                                   rtol=1e-4, atol=1e-5 * scale)
        np.testing.assert_allclose(np.sqrt(float(rsq1)), true1,
                                   rtol=1e-4, atol=1e-5 * scale)
