"""Compile the main-path Pallas kernels for a described TPU v5e chip.

No chip is attached: the TPU compiler is installed and compiles for a
topology it is only told about, so these tests catch what interpret mode
cannot — a block shape the lowering refuses, tiles that overflow VMEM —
at the widths users run: one worker block of p=2048 rows by n=8192
columns (k=1 and a k=16 batch), the p=4096, n=32768 block of a system
that fills a chip, and the bf16 tile stream of ``precision="mixed"``.
Autotune is off, so the tiles under test are the shape-derived defaults.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, and every test worker imports this
file.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.compilation_cache import compilation_cache
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops as kops

SHAPES = {                       # (p, n, k, A/B tile dtype)
    "p2048-n8192-k1": (2048, 8192, 1, jnp.float32),
    "p2048-n8192-k16": (2048, 8192, 16, jnp.float32),
    "p4096-n32768-k16": (4096, 32768, 16, jnp.float32),
    "p2048-n8192-k16-mixed": (2048, 8192, 16, jnp.bfloat16),
}
WORKERS = 2                      # the vmapped step's worker axis


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    with pytest.MonkeyPatch.context() as mp:
        if "TPU_LOG_DIR" not in os.environ:
            mp.setenv("TPU_LOG_DIR", "disabled")   # else it logs under /tmp
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def compile_env(monkeypatch):
    """Shape-derived tiles, and no persistent compilation cache: an entry
    compiled for a described chip cannot be read back without one."""
    monkeypatch.setenv(kops.AUTOTUNE_ENV, "0")
    for env in (kops.BN_ENV, kops.BP_ENV, kops.BK_ENV):
        monkeypatch.delenv(env, raising=False)
    kops.bn_cache_clear()
    kops.tile_cache_clear()
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    kops.bn_cache_clear()
    kops.tile_cache_clear()


def _gather_scatter(p, n, k, dt, S):
    def step(A, B, x, xbar):
        u = kops.proj_gather(A, x, xbar, interpret=False)
        return kops.proj_scatter(B, x, xbar, u, 1.0, interpret=False)
    return step, (S((p, n), dt), S((n, p), dt), S((k, n)), S((k, n)))


def _cimmino(p, n, k, dt, S):
    def step(A, B, b, xbar):
        return kops.cimmino_update(A, B, b, xbar, interpret=False)
    return step, (S((p, n), dt), S((n, p), dt), S((k, p)), S((k, n)))


def _worker_step(p, n, k, dt, S):
    def step(A, B, X, xbar):
        return jax.vmap(lambda Ai, Bi, Xi: kops.block_projection(
            Ai, Bi, Xi, xbar, 1.0, interpret=False))(A, B, X)
    return step, (S((WORKERS, p, n), dt), S((WORKERS, n, p), dt),
                  S((WORKERS, k, n)), S((k, n)))


OPS = {"proj_gather+proj_scatter": _gather_scatter,
       "cimmino_update": _cimmino,
       "vmapped_block_projection": _worker_step}


@pytest.mark.parametrize("shape", list(SHAPES))
@pytest.mark.parametrize("op", list(OPS))
def test_kernel_compiles_for_v5e(one_chip, compile_env, op, shape):
    p, n, k, dt = SHAPES[shape]

    def S(dims, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    step, args = OPS[op](p, n, k, dt, S)
    compiled = jax.jit(step).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    # the tiles it compiled with are the budgeted default for this shape
    bn, bp_, bk = kops.pick_tiles(n, p, k, np.dtype(dt), interpret=False)
    assert kops.tile_fits(bn, bp_, bk, np.dtype(dt))
    if (p, dt) == (4096, jnp.float32):
        assert (bn, bp_, bk) == (512, 2048, k)   # whole p does not fit


# ---------------------------------------------------------------------------
# The sparse step at the HPCG stencil's widths (16 x 16 x 32 in 4 z-slabs:
# p = 2048 rows, support padded to w = 2560, n = 8192)
# ---------------------------------------------------------------------------

STENCIL = dict(m=4, p=2048, w=2560, n=8192)
# the TPU's triangular solve: blocked against the diagonal-block inverses
# this custom call forms
TRSM_INVERSE = "InvertDiagBlocksLowerTriangular"


def _sparse_factors_spec(S, m, p, w, n):
    from repro.core import blockops
    from repro.solvers.projection import ProjFactors
    A = blockops.SparseBlocks(vals=S((m, p, w)), cols=S((m, w), jnp.int32),
                              span=S((n,)))
    return ProjFactors(A=A, chol=S((m, p, p)), B=S((m, w, p)))


def test_the_tpu_triangular_solve_runs_on_diagonal_block_inverses(
        one_chip, compile_env):
    """What the sparse step ran each iteration before it moved to the
    support pinv: ``cho_solve`` compiles for the TPU to a blocked solve on
    the inverses of the factor's diagonal blocks, formed in a custom
    call, so the next test can tell that solve from its absence."""
    p = STENCIL["p"]

    def S(dims, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    def solve(L, u):
        return jax.scipy.linalg.cho_solve((L, True), u)

    text = jax.jit(solve).lower(S((p, p)), S((p,))).compile().as_text()
    assert TRSM_INVERSE in text


@pytest.mark.parametrize("engine", ["unfused", "fused"])
def test_sparse_served_scan_compiles_for_v5e_on_the_support_pinv(
        one_chip, compile_env, monkeypatch, engine):
    """The served sparse program (the executor's init and scan of
    ``step_many_residual`` at k = 1) compiles at the stencil's widths with
    either engine, and no triangular solve is left in it: the step projects
    against the support pinv that ``_sparse_factors`` makes on the host."""
    from repro import solvers
    from repro.solvers import api
    monkeypatch.setenv(kops.ENGINE_ENV, engine)
    monkeypatch.setattr(kops.bp, "default_interpret", lambda: False)
    m, p, n = STENCIL["m"], STENCIL["p"], STENCIL["n"]
    apc = solvers.get("apc")
    prm = {"gamma": 1.1, "eta": 3.0}

    def S(dims, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    def served(factors, Bb):
        states = jax.vmap(lambda b: apc.init(factors, b, prm))(Bb)
        return api._history_scan_many(
            None, apc.extract, factors, Bb, states, factors.A, 150,
            step_many_residual=lambda f, bb, st: apc.step_many_residual(
                f, bb, st, prm))

    factors = _sparse_factors_spec(S, **STENCIL)
    text = jax.jit(served).lower(factors, S((1, m, p))).compile().as_text()
    assert TRSM_INVERSE not in text
    assert "triangular_solve" not in text and "triangular-solve" not in text
    assert ("tpu_custom_call" in text) == (engine == "fused")
