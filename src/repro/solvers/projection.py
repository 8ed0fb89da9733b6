"""Projection-family solvers: APC, plain projection consensus, block Cimmino.

All three share the per-worker null-space projection P_i v = v - B_i A_i v
with B_i = A_i^T G_i^{-1}, G_i = A_i A_i^T the block's Gram, support the
Pallas kernel path uniformly (``use_kernel=True``), and auto-tune their
parameters from the Theorem-1 spectral analysis of X when none are given.

Three engines apply the projection:

- the Cholesky step (``core/apc.py``): two triangular solves against the
  Gram's Cholesky factor per worker per iteration.  Runs for a dense
  operand under ``use_kernel=False``, where the factors carry no pinv
  factor B, and for Cimmino where the Pallas pair loses;
- the pinv step: APC and consensus contract against B in plain XLA, one
  pass over A and one over B with no triangular solve.  A dense operand
  carries B under ``use_kernel=True`` (``kernel_factors``) and takes this
  step where the engine autotune (``kops.use_fused``) says the Pallas pair
  loses; a sparse operand always carries its support pinv
  (``_sparse_factors``) and always takes it unless the pair wins;
- the Pallas pair (``kernels/``), where the autotune says it wins.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
import scipy.linalg
from jax.sharding import PartitionSpec as P

from repro.core import blockops
from repro.core import spectral
from repro.core import apc as apc_core
from repro.core.apc import APCState, _gram_chol, _gram_solve
from repro.core.partition import BlockSystem
from repro.runtime.spans import span

from .api import Solver
from .registry import register


def _scoped(name: str):
    """Trace the decorated function under ``jax.named_scope(name)``: the
    device trace's op metadata then names the phase (the Cholesky solves
    and the kernel pair already carry ``jit(_cho_solve)``,
    ``jit(proj_gather)``, ``jit(block_projection)``... beneath it)."""
    def deco(fn):
        @functools.wraps(fn)
        def scoped(*args, **kwargs):
            with jax.named_scope(name):
                return fn(*args, **kwargs)
        return scoped
    return deco


class ProjFactors(NamedTuple):
    """b-independent per-worker factors (leading axis = worker)."""
    A: jnp.ndarray      # (m, p, n) row blocks, or a blockops.SparseBlocks
    chol: jnp.ndarray   # (m, p, p) Cholesky of Gram A_i A_i^T
    B: Optional[jnp.ndarray] = None  # pinv factors A^T G^{-1}: (m, n, p)
                                     # dense, present under use_kernel=True
                                     # only (see kernel_factors); (m, w, p)
                                     # support-compressed for SparseBlocks
                                     # operands, always (_sparse_factors)


def _proj_prepare(A, jitter: float) -> ProjFactors:
    if blockops.is_sparse(A):
        return _sparse_factors(A, jitter)
    chol = jax.vmap(lambda Ai: _gram_chol(Ai, jitter))(A)
    return ProjFactors(A=A, chol=chol)


def _sparse_factors(A: blockops.SparseBlocks, jitter: float) -> ProjFactors:
    """The factors of a SparseBlocks operand, made on the host in float64
    and rounded once to the operand's dtype: the support Gram
    G_i = V_i V_iᵀ (exact: padded columns carry zeros), its Cholesky
    factor, and the support-compressed pinv Bvals_i = (G_i⁻¹ V_i)ᵀ (w, p).

    The sparse step projects against Bvals with plain contractions, so no
    triangular solve runs per iteration, and every answer's accuracy rests
    on B alone: A_i x_i = b_i holds as well as A_i B_i = I, here to one
    rounding of the float64 factor, whatever device the step runs on.
    """
    with span("repro.proj.sparse_factor"):
        V = np.asarray(jax.device_get(A.vals), np.float64)      # (m, p, w)
        G = V @ V.transpose(0, 2, 1)
        p = G.shape[-1]
        if jitter:
            tr = np.trace(G, axis1=-2, axis2=-1)[:, None, None]
            G = G + jitter * tr / p * np.eye(p)
        L = np.linalg.cholesky(G)
        Bt = np.stack([scipy.linalg.cho_solve((Li, True), Vi)
                       for Li, Vi in zip(L, V)])                 # (m, p, w)
        dt = A.vals.dtype
        return ProjFactors(A=A, chol=jnp.asarray(L, dt),
                           B=jnp.asarray(Bt.transpose(0, 2, 1), dt))


def _with_pinv(factors: ProjFactors) -> ProjFactors:
    """Precompute B_i = A_i^T G_i^{-1} once (iteration-invariant).

    Sparse operands get the SUPPORT-COMPRESSED pinv: B_i has rows only on
    the block's column support, so Bvals_i = (G_i^{-1} vals_i)^T is the
    full factor stored as (w, p) on the same ``cols`` — padded support
    slots carry exact-zero vals columns and therefore exact-zero Bvals
    rows, keeping every kernel contraction exact.  Local sparse factors
    come with it (``_sparse_factors``); the mesh backend's on-mesh
    prepare forms it here.
    """
    if factors.B is not None:
        return factors
    if blockops.is_sparse(factors.A):
        B = jax.vmap(
            lambda Vi, Li: jax.scipy.linalg.cho_solve((Li, True), Vi).T)(
                factors.A.vals, factors.chol)          # (m, w, p)
        return factors._replace(B=B)
    B = jax.vmap(lambda Ai, Li: jax.scipy.linalg.cho_solve((Li, True), Ai).T)(
        factors.A, factors.chol)
    return factors._replace(B=B)


def _min_norm_solutions(factors: ProjFactors, b: jnp.ndarray) -> jnp.ndarray:
    """x0_i = A_i^T (A_i A_i^T)^{-1} b_i — the min-norm local solutions;
    B_i b_i where the pinv factor is present (always, for a sparse
    operand: scattered from its support)."""
    if blockops.is_sparse(factors.A):
        with jax.named_scope("apc.pinv"):
            return blockops.scatter_support(
                factors.A, jnp.einsum("mwp,mp->mw", factors.B, b))
    if factors.B is not None:
        with jax.named_scope("apc.pinv"):
            return jnp.einsum("mnp,mp->mn", factors.B, b)
    return jax.vmap(lambda Ai, Li, bi: Ai.T @ _gram_solve(Li, bi))(
        factors.A, factors.chol, b)


def _pinv_update(factors: ProjFactors, x, xbar, gamma):
    """Eq. 2a against the stored dense pinv factor: (x_new, u) with
    u_i = A_i d_i, d_i = x̄ − x_i (the gather pass, and the residual source)
    and x_new_i = x_i + γ (d_i − B_i u_i).  B_i u_i = A_i^T G_i^{-1} u_i is
    the Cholesky step's two triangular solves and second pass over A in one
    contraction over B's minor axis.  Batch-polymorphic: x (m, n) with
    x̄ (n,), or x (k, m, n) with x̄ (k, n)."""
    d = xbar[..., None, :] - x
    u = jnp.einsum("mpn,...mn->...mp", factors.A, d)
    with jax.named_scope("apc.pinv"):
        x_new = x + gamma * (d - jnp.einsum("mnp,...mp->...mn", factors.B, u))
    return x_new, u


def _sparse_pinv_update(factors: ProjFactors, x, xbar, gamma):
    """``_pinv_update`` for a SparseBlocks operand, on each block's column
    support: (x_new, u) with u_i = V_i d_i[cols_i] (``apc.gather``),
    c_i = Bvals_i u_i (``apc.project``) and x_new_i = x_i + γ d_i with
    −γ c_i scatter-added on cols_i (``apc.scatter``).  Padded support slots
    carry zero vals and zero Bvals rows, so they add exact zeros.
    Batch-polymorphic as ``_pinv_update``."""
    Asp = factors.A
    d = xbar[..., None, :] - x
    with jax.named_scope("apc.gather"):
        u = jnp.einsum("mpw,...mw->...mp", Asp.vals,
                       blockops.gather_support(Asp, d))
    with jax.named_scope("apc.project"):
        c = jnp.einsum("mwp,...mp->...mw", factors.B, u)
    with jax.named_scope("apc.scatter"):
        x_new = x + gamma * d + blockops.scatter_support(Asp, -gamma * c)
    return x_new, u


def _sparse_step_u(factors: ProjFactors, x, xbar, gamma, use_kernel: bool):
    """One sparse APC worker update, (x_new, u): x (m, n) with x̄ (n,), or
    x (k, m, n) with x̄ (k, n), and u (m, p) or (k, m, p).  The fused
    compressed-support Pallas pair where ``use_kernel`` and the engine
    autotune say it wins, else the support pinv step."""
    Asp = factors.A
    batched = x.ndim == 3
    if use_kernel and _sparse_use_fused("apc_sparse", Asp,
                                        x.shape[0] if batched else 1):
        from repro.kernels import ops as kops

        # one VMEM residency of the (p, w) vals / (w, p) Bvals tiles per
        # worker, the k rows of a batch streamed through it
        def worker(Vi, ci, Bvi, xi):
            return kops.sparse_proj_update(Vi, ci, Bvi, xi, xbar, gamma)

        X = jnp.swapaxes(x, 0, 1) if batched else x       # (m, [k,] n)
        x_new, u = jax.vmap(worker)(Asp.vals, Asp.cols, factors.B, X)
        if batched:
            x_new, u = jnp.swapaxes(x_new, 0, 1), jnp.swapaxes(u, 0, 1)
        return x_new, u
    return _sparse_pinv_update(factors, x, xbar, gamma)


def _cho_solve_workers(chol, u):
    """Per-worker G_i^{-1} u_i with the stored Cholesky factors."""
    return jax.vmap(
        lambda Li, ui: jax.scipy.linalg.cho_solve((Li, True), ui))(chol, u)


def _cho_solve_replicas(chol, u):
    """Replicated form: leading (m, r) worker x slot axes."""
    return jax.vmap(_cho_solve_workers)(chol, u)


def _sparse_use_fused(family: str, Asp, k: int) -> bool:
    """Trace-time engine choice for the compressed-support kernel pair."""
    from repro.kernels import ops as kops
    return kops.use_fused(family, Asp.vals.shape[1], blockops.ncols(Asp),
                          k, Asp.vals.dtype, w=Asp.vals.shape[2])


def _cast_proj_factors(factors: ProjFactors, precision: str) -> ProjFactors:
    """``precision="mixed"``: bf16 storage for the streamed A/B tiles.

    Only the memory-bound tile streams are cast — the Cholesky factors
    (and every cho_solve against them) stay in the working precision, and
    the kernels accumulate every contraction in f32 (see
    ``kernels/block_projection``).  Residual histories then hold to the
    bf16 storage tolerance (~1e-2 relative) while halving the HBM bytes
    of the dominant per-iteration reads.
    """
    if precision == "default":
        return factors
    if blockops.is_sparse(factors.A):
        A = factors.A._replace(vals=factors.A.vals.astype(jnp.bfloat16))
    else:
        A = factors.A.astype(jnp.bfloat16)
    B = None if factors.B is None else factors.B.astype(jnp.bfloat16)
    return ProjFactors(A=A, chol=factors.chol, B=B)


def _mesh_gram_chol(A, jitter: float, ctx):
    """Cholesky of the full Gram A_i A_i^T from column-sharded blocks."""
    G = ctx.psum_model(blockops.bgram(A))
    if jitter:
        p = G.shape[-1]
        tr = jnp.trace(G, axis1=-2, axis2=-1)[:, None, None]
        G = G + jitter * tr / p * jnp.eye(p, dtype=G.dtype)
    return jnp.linalg.cholesky(G)


@register("apc")
class APCSolver(Solver):
    """Accelerated Projection-based Consensus (paper Algorithm 1)."""

    paper_name = "APC"
    supports_kernel = True
    param_names = ("gamma", "eta")
    # the paper's convergence theory (Theorem 1) assumes an exact solution
    # exists, so APC keeps its square-only contract; sparse blocks are fine
    supports = frozenset({"square", "sparse"})

    def default_params(self, sys: BlockSystem):
        return self.analyze(sys)[0]

    def theoretical_rate(self, sys: BlockSystem):
        return self.analyze(sys)[1]

    def analyze(self, sys: BlockSystem):
        X = spectral.x_matrix(sys)
        prm = spectral.apc_optimal(*spectral.mu_extremes(X))
        return {"gamma": prm.gamma, "eta": prm.eta}, prm.rho

    def prepare(self, A, params):
        return _proj_prepare(A, params.get("jitter", 0.0))

    def kernel_factors(self, factors):
        """Add the pinv factors B (``_with_pinv``).  Dense B serves both
        engines of ``use_kernel=True``: the Pallas pair, and the XLA pinv
        step (``_pinv_update``, and the init's B_i b_i) where the engine
        autotune says the pair loses."""
        return _with_pinv(factors)

    @_scoped("apc.init")
    def init(self, factors, b, params):
        x0 = _min_norm_solutions(factors, b)
        return APCState(x=x0, xbar=jnp.mean(x0, axis=0),
                        t=jnp.zeros((), jnp.int32))

    @_scoped("apc.step")
    def step(self, factors, b, state, params, *, use_kernel=False):
        gamma, eta = params["gamma"], params["eta"]
        if blockops.is_sparse(factors.A):
            x_new, _ = _sparse_step_u(factors, state.x, state.xbar, gamma,
                                      use_kernel)
            xbar_new = (eta * jnp.mean(x_new, axis=0)
                        + (1.0 - eta) * state.xbar)
            return APCState(x=x_new, xbar=xbar_new, t=state.t + 1)
        if use_kernel and factors.B is not None:
            from repro.kernels import ops as kops
            # the engine autotune includes "unfused" as a candidate: when
            # the fused pair loses at this (p, n, k=1, dtype) the step
            # runs the XLA pinv step instead (trace-time choice — baked
            # into the compiled executor, never retraced)
            if kops.use_fused("apc", factors.A.shape[1], factors.A.shape[2],
                              1, factors.A.dtype):
                def worker(Ai, Bi, xi):
                    return kops.block_projection(Ai, Bi, xi, state.xbar,
                                                 gamma)

                x_new = jax.vmap(worker)(factors.A, factors.B, state.x)
            else:
                x_new, _ = _pinv_update(factors, state.x, state.xbar, gamma)
            xbar_new = (eta * jnp.mean(x_new, axis=0)
                        + (1.0 - eta) * state.xbar)
            return APCState(x=x_new, xbar=xbar_new, t=state.t + 1)
        legacy = apc_core.APCFactors(A=factors.A, chol=factors.chol,
                                     x0=None, b=None)
        return apc_core.apc_step(legacy, state, gamma, eta,
                                 use_kernel=use_kernel)

    @_scoped("apc.step")
    def step_many(self, factors, Bb, states, params, *, use_kernel=False):
        """Fused multi-RHS iteration: the k batch rows stream through ONE
        VMEM residency of every A/B tile (states.x (k, m, n))."""
        gamma, eta = params["gamma"], params["eta"]
        if blockops.is_sparse(factors.A):
            x_new, _ = _sparse_step_u(factors, states.x, states.xbar, gamma,
                                      use_kernel)
            xbar_new = (eta * jnp.mean(x_new, axis=1)
                        + (1.0 - eta) * states.xbar)
            return APCState(x=x_new, xbar=xbar_new, t=states.t + 1)
        if not (use_kernel and factors.B is not None):
            return super().step_many(factors, Bb, states, params,
                                     use_kernel=use_kernel)
        from repro.kernels import ops as kops
        if kops.use_fused("apc", factors.A.shape[1], factors.A.shape[2],
                          Bb.shape[0], factors.A.dtype):
            X = jnp.swapaxes(states.x, 0, 1)              # (m, k, n)

            def worker(Ai, Bi, Xi):
                return kops.block_projection(Ai, Bi, Xi, states.xbar, gamma)

            x_new = jnp.swapaxes(
                jax.vmap(worker)(factors.A, factors.B, X), 0, 1)  # (k, m, n)
        else:                                     # measured fallback
            x_new, _ = _pinv_update(factors, states.x, states.xbar, gamma)
        xbar_new = (eta * jnp.mean(x_new, axis=1)
                    + (1.0 - eta) * states.xbar)
        return APCState(x=x_new, xbar=xbar_new, t=states.t + 1)

    # ----- fused residual --------------------------------------------------
    # The iterates satisfy A_i x_i = b_i exactly (min-norm init, preserved
    # by the projection since A_i B_i = I), so the gather pass's result
    # u_i = A_i(x̄ − x_i) IS the residual block A_i x̄ − b_i of the CONSUMED
    # state — the history costs no second read of A per iteration.  The
    # drivers in ``api._history_scan`` shift the lagged records by one and
    # close with a single true-A residual of the final state.
    supports_fused_residual = True

    def cast_factors(self, factors, precision):
        return _cast_proj_factors(factors, precision)

    def _step_u(self, factors, state, gamma):
        """One worker update plus the gather result u (the residual
        source); engine dispatch identical to ``step``."""
        if blockops.is_sparse(factors.A):
            return _sparse_step_u(factors, state.x, state.xbar, gamma, True)
        from repro.kernels import ops as kops
        if factors.B is not None and kops.use_fused(
                "apc", factors.A.shape[1], factors.A.shape[2], 1,
                factors.A.dtype):
            u = jax.vmap(
                lambda Ai, xi: kops.proj_gather(Ai, xi, state.xbar))(
                    factors.A, state.x)                   # (m, p)
            x_new = jax.vmap(
                lambda Bi, xi, ui: kops.proj_scatter(Bi, xi, state.xbar,
                                                     ui, gamma))(
                    factors.B, state.x, u)
        elif factors.B is not None:
            x_new, u = _pinv_update(factors, state.x, state.xbar, gamma)
        else:
            d = state.xbar[None, :] - state.x
            u = blockops.bmatvec_each(factors.A, d)
            w = _cho_solve_workers(factors.chol, u)
            proj = d - blockops.brmatvec(factors.A, w)
            x_new = state.x + gamma * proj
        return x_new, u

    @_scoped("apc.step")
    def step_residual(self, factors, b, state, params):
        gamma, eta = params["gamma"], params["eta"]
        x_new, u = self._step_u(factors, state, gamma)
        xbar_new = (eta * jnp.mean(x_new, axis=0)
                    + (1.0 - eta) * state.xbar)
        return (APCState(x=x_new, xbar=xbar_new, t=state.t + 1),
                jnp.sum(u * u))

    @_scoped("apc.step")
    def step_many_residual(self, factors, Bb, states, params):
        gamma, eta = params["gamma"], params["eta"]
        from repro.kernels import ops as kops
        sparse = blockops.is_sparse(factors.A)
        kern = (not sparse and factors.B is not None
                and kops.use_fused("apc", factors.A.shape[1],
                                   factors.A.shape[2], Bb.shape[0],
                                   factors.A.dtype))
        if sparse:
            x_new, u = _sparse_step_u(factors, states.x, states.xbar, gamma,
                                      True)
            rsq = jnp.sum(u * u, axis=(1, 2))             # (k,)
        elif kern:
            X = jnp.swapaxes(states.x, 0, 1)              # (m, k, n)
            u = jax.vmap(
                lambda Ai, Xi: kops.proj_gather(Ai, Xi, states.xbar))(
                    factors.A, X)                         # (m, k, p)
            x_new = jax.vmap(
                lambda Bi, Xi, ui: kops.proj_scatter(
                    Bi, Xi, states.xbar, ui, gamma))(
                        factors.B, X, u)                  # (m, k, n)
            x_new = jnp.swapaxes(x_new, 0, 1)             # (k, m, n)
            rsq = jnp.sum(u * u, axis=(0, 2))             # (k,)
        elif factors.B is not None:
            x_new, u = _pinv_update(factors, states.x, states.xbar, gamma)
            rsq = jnp.sum(u * u, axis=(1, 2))             # (k,)
        else:
            def one(xk, xbark):
                d = xbark[None, :] - xk
                uk = blockops.bmatvec_each(factors.A, d)
                w = _cho_solve_workers(factors.chol, uk)
                proj = d - blockops.brmatvec(factors.A, w)
                return xk + gamma * proj, uk

            x_new, u = jax.vmap(one)(states.x, states.xbar)
            rsq = jnp.sum(u * u, axis=(1, 2))             # (k,)
        xbar_new = (eta * jnp.mean(x_new, axis=1)
                    + (1.0 - eta) * states.xbar)
        return (APCState(x=x_new, xbar=xbar_new, t=states.t + 1), rsq)

    def extract(self, state):
        return state.xbar

    # ----- mesh backend ---------------------------------------------------
    def mesh_factor_specs(self, ctx, use_kernel=False):
        return ProjFactors(A=P(ctx.w, None, ctx.n),
                           chol=P(ctx.w, None, None),
                           B=P(ctx.w, ctx.n, None) if use_kernel else None)

    def mesh_state_specs(self, ctx):
        return APCState(x=P(ctx.w, ctx.n), xbar=P(ctx.n), t=P())

    def mesh_factors(self, factors, use_kernel=False):
        if use_kernel:
            return _with_pinv(factors)      # idempotent host augmentation
        return factors._replace(B=None)     # pinv factors are kernel-only

    def mesh_prepare(self, A, params, ctx, use_kernel=False):
        chol = _mesh_gram_chol(A, params.get("jitter", 0.0), ctx)
        factors = ProjFactors(A=A, chol=chol)
        if use_kernel:
            # B_loc = A_locᵀ G⁻¹ is shard-local given the FULL Gram's
            # Cholesky (cho_solve acts on the p axis only), so the pinv
            # augmentation runs on-mesh without materializing A anywhere
            factors = _with_pinv(factors)
        return factors

    def mesh_init(self, factors, b, params, ctx):
        w = _cho_solve_workers(factors.chol, b)
        x0 = blockops.brmatvec(factors.A, w)          # min-norm local sols
        m = ctx.workers_total(x0.shape[0])
        xbar0 = ctx.psum_workers(jnp.sum(x0, axis=0)) / m
        return APCState(x=x0, xbar=xbar0, t=jnp.zeros((), jnp.int32))

    def _mesh_step_u(self, factors, state, gamma, ctx, use_kernel):
        """Shared Eq. 2a body on local shards: (x_new, full u)."""
        if use_kernel and factors.B is not None:
            from repro.kernels import ops as kops
            if blockops.is_sparse(factors.A):
                # sparse systems shard over worker axes only (model_axis
                # is None — cols index the global n), so the per-worker
                # fused pair composes directly and u is already full
                Asp = factors.A

                def worker(Vi, ci, Bvi, xi):
                    return kops.sparse_proj_update(Vi, ci, Bvi, xi,
                                                   state.xbar, gamma)

                x_new, u = jax.vmap(worker)(Asp.vals, Asp.cols, factors.B,
                                            state.x)
                return x_new, ctx.psum_model(u)
            u_loc = jax.vmap(
                lambda Ai, xi: kops.proj_gather(Ai, xi, state.xbar))(
                    factors.A, state.x)               # (m_loc, p)
            u = ctx.psum_model(u_loc)                 # full u = A_i d
            x_new = jax.vmap(
                lambda Bi, xi, ui: kops.proj_scatter(Bi, xi, state.xbar,
                                                     ui, gamma))(
                    factors.B, state.x, u)            # Eq. 2a, fused
            return x_new, u
        d = state.xbar[None, :] - state.x             # (m_loc, n_loc)
        u = ctx.psum_model(blockops.bmatvec_each(factors.A, d))
        w = _cho_solve_workers(factors.chol, u)       # G^{-1} A_i d
        proj = d - blockops.brmatvec(factors.A, w)
        return state.x + gamma * proj, u              # Eq. 2a

    def mesh_step(self, factors, b, state, params, ctx, *, use_kernel=False):
        gamma, eta = params["gamma"], params["eta"]
        x_new, _ = self._mesh_step_u(factors, state, gamma, ctx, use_kernel)
        m = ctx.workers_total(x_new.shape[0])
        s = ctx.psum_workers(jnp.sum(x_new, axis=0))      # Eq. 2b psum
        xbar_new = (eta / m) * s + (1.0 - eta) * state.xbar
        return APCState(x=x_new, xbar=xbar_new, t=state.t + 1)

    def mesh_step_residual(self, factors, b, state, params, ctx):
        """Mesh step plus the consumed state's GLOBAL squared residual,
        psum'd from the gather results (see the local hook)."""
        gamma, eta = params["gamma"], params["eta"]
        x_new, u = self._mesh_step_u(factors, state, gamma, ctx, True)
        m = ctx.workers_total(x_new.shape[0])
        s = ctx.psum_workers(jnp.sum(x_new, axis=0))
        xbar_new = (eta / m) * s + (1.0 - eta) * state.xbar
        rsq = ctx.psum_workers(jnp.sum(u * u))
        return APCState(x=x_new, xbar=xbar_new, t=state.t + 1), rsq

    def _mesh_step_many_u(self, factors, states, gamma, ctx):
        """Batched Eq. 2a body: (x_new (k, m_loc, n_loc), full u)."""
        from repro.kernels import ops as kops
        X = jnp.swapaxes(states.x, 0, 1)                  # (m_loc, k, n_loc)
        if blockops.is_sparse(factors.A):
            Asp = factors.A

            def worker(Vi, ci, Bvi, Xi):
                return kops.sparse_proj_update(Vi, ci, Bvi, Xi,
                                               states.xbar, gamma)

            x_new, u = jax.vmap(worker)(Asp.vals, Asp.cols, factors.B, X)
            return jnp.swapaxes(x_new, 0, 1), ctx.psum_model(u)
        u_loc = jax.vmap(
            lambda Ai, Xi: kops.proj_gather(Ai, Xi, states.xbar))(
                factors.A, X)                             # (m_loc, k, p)
        u = ctx.psum_model(u_loc)
        x_new = jnp.swapaxes(jax.vmap(
            lambda Bi, Xi, ui: kops.proj_scatter(Bi, Xi, states.xbar,
                                                 ui, gamma))(
                factors.B, X, u), 0, 1)                   # (k, m_loc, n_loc)
        return x_new, u

    def mesh_step_many(self, factors, Bb, states, params, ctx, *,
                       use_kernel=False):
        if not (use_kernel and factors.B is not None):
            return super().mesh_step_many(factors, Bb, states, params, ctx)
        gamma, eta = params["gamma"], params["eta"]
        x_new, _ = self._mesh_step_many_u(factors, states, gamma, ctx)
        m = ctx.workers_total(x_new.shape[1])
        s = ctx.psum_workers(jnp.sum(x_new, axis=1))      # (k, n_loc)
        xbar_new = (eta / m) * s + (1.0 - eta) * states.xbar
        return APCState(x=x_new, xbar=xbar_new, t=states.t + 1)

    def mesh_step_many_residual(self, factors, Bb, states, params, ctx):
        gamma, eta = params["gamma"], params["eta"]
        if factors.B is not None:
            x_new, u = self._mesh_step_many_u(factors, states, gamma, ctx)
            rsq = ctx.psum_workers(jnp.sum(u * u, axis=(0, 2)))   # (k,)
        else:
            def one(xk, xbark):
                d = xbark[None, :] - xk
                uk = ctx.psum_model(blockops.bmatvec_each(factors.A, d))
                w = _cho_solve_workers(factors.chol, uk)
                proj = d - blockops.brmatvec(factors.A, w)
                return xk + gamma * proj, uk

            x_new, u = jax.vmap(one)(states.x, states.xbar)
            rsq = ctx.psum_workers(jnp.sum(u * u, axis=(1, 2)))
        m = ctx.workers_total(x_new.shape[1])
        s = ctx.psum_workers(jnp.sum(x_new, axis=1))
        xbar_new = (eta / m) * s + (1.0 - eta) * states.xbar
        return APCState(x=x_new, xbar=xbar_new, t=states.t + 1), rsq

    # ----- redundant execution (solvers/redundant.py) ---------------------
    # Internal state keeps the APCState structure with x grown to the
    # replicated (m, r, n) layout; xbar stays global.  Eq. 2b becomes the
    # W-masked block-unique mean — the same worker-axis psum as above.
    supports_redundancy = True

    def red_init(self, factors, b, params, W0, ctx):
        w = _cho_solve_replicas(factors.chol, b)
        x0 = jnp.einsum("mrpn,mrp->mrn", factors.A, w)    # min-norm per slot
        m = ctx.workers_total(x0.shape[0])
        xbar0 = ctx.psum_workers(jnp.einsum("mr,mrn->n", W0, x0)) / m
        return APCState(x=x0, xbar=xbar0, t=jnp.zeros((), jnp.int32))

    def red_step(self, factors, b, state, params, W, ctx):
        gamma, eta = params["gamma"], params["eta"]
        d = state.xbar[None, None, :] - state.x           # (m, r, n)
        u = ctx.psum_model(jnp.einsum("mrpn,mrn->mrp", factors.A, d))
        w = _cho_solve_replicas(factors.chol, u)
        proj = d - jnp.einsum("mrpn,mrp->mrn", factors.A, w)
        x_new = state.x + gamma * proj                    # every replica
        m = ctx.workers_total(x_new.shape[0])
        s = ctx.psum_workers(jnp.einsum("mr,mrn->n", W, x_new))
        xbar_new = (eta / m) * s + (1.0 - eta) * state.xbar
        return APCState(x=x_new, xbar=xbar_new, t=state.t + 1)

    def red_expand(self, state, assign):
        x = jnp.asarray(state.x)
        return APCState(x=x[assign.holder], xbar=jnp.asarray(state.xbar),
                        t=state.t)

    def red_collapse(self, state, assign):
        # slot 0 of worker j holds block j, and replicas are identical
        return APCState(x=state.x[:, 0], xbar=state.xbar, t=state.t)

    def red_state_specs(self, ctx):
        return APCState(x=P(ctx.w, None, ctx.n), xbar=P(ctx.n), t=P())

    # ----- cross-partition warm start (solvers/elastic.py) ------------------
    # APC states are partition-specific: each x_i must satisfy A_i x_i =
    # b_i for THIS partition's blocks.  The lift projects the global
    # estimate onto every new block's feasible set — x_i = x + A_iᵀ
    # G_i⁻¹(b_i − A_i x) — so the invariant the step relies on holds from
    # the first post-repartition iteration, with x̄ carrying x verbatim.
    supports_lift = True
    supports_block_store = True    # per-block Gram Cholesky, leading m axis

    def lift_state(self, factors, b, params, x):
        x = jnp.asarray(x)
        v = b - blockops.bmatvec(factors.A, x)            # (m, p)
        w = _cho_solve_workers(factors.chol, v)
        xi = x[None, :] + blockops.brmatvec(factors.A, w)
        return APCState(x=xi, xbar=x, t=jnp.zeros((), jnp.int32))


@register("consensus")
class ConsensusSolver(APCSolver):
    """Plain projection consensus [11,14] == APC with gamma = eta = 1."""

    paper_name = "Consensus"

    def default_params(self, sys: BlockSystem):
        return {"gamma": 1.0, "eta": 1.0}

    def theoretical_rate(self, sys: BlockSystem):
        X = spectral.x_matrix(sys)
        mu_min, _ = spectral.mu_extremes(X)
        return spectral.consensus_rate(mu_min)

    def analyze(self, sys: BlockSystem):
        return self.default_params(sys), self.theoretical_rate(sys)


class CimminoState(NamedTuple):
    xbar: jnp.ndarray   # (n,) master estimate
    t: jnp.ndarray      # ()   iteration counter


@register("cimmino")
class CimminoSolver(Solver):
    """Block Cimmino row projections (Sec 4.5; Proposition 2: APC gamma=1)."""

    paper_name = "B-Cimmino"
    supports_kernel = True
    param_names = ("nu",)
    # state is the master estimate alone and b enters every step, so a
    # prior state warm-starts perturbed right-hand sides too
    warm_rhs_ok = True
    # the fixed point Σ A_iᵀG_i⁻¹(b_i − A_i x̄) = 0 is the G⁻¹-weighted
    # least-squares optimum, well-defined for inconsistent systems too
    # (each block must stay row-independent: p ≤ n per block)
    supports = frozenset({"square", "least_squares", "sparse"})

    def default_params(self, sys: BlockSystem):
        return self.analyze(sys)[0]

    def theoretical_rate(self, sys: BlockSystem):
        return self.analyze(sys)[1]

    def analyze(self, sys: BlockSystem):
        X = spectral.x_matrix(sys)
        nu_m, rho = spectral.cimmino_optimal(*spectral.mu_extremes(X))
        return {"nu": nu_m / sys.m}, rho

    def prepare(self, A, params):
        return _proj_prepare(A, params.get("jitter", 0.0))

    def kernel_factors(self, factors):
        return _with_pinv(factors)

    def init(self, factors, b, params):
        n = blockops.ncols(factors.A)
        # state dtype follows b, not the stored blocks: under
        # precision="mixed" the A/B tiles are bf16 storage but the
        # iterate (and every accumulation) stays in the working precision
        return CimminoState(xbar=jnp.zeros(n, b.dtype),
                            t=jnp.zeros((), jnp.int32))

    def step(self, factors, b, state, params, *, use_kernel=False):
        nu = params["nu"]
        if blockops.is_sparse(factors.A):
            Asp = factors.A
            if (use_kernel and factors.B is not None
                    and _sparse_use_fused("cimmino_sparse", Asp, 1)):
                from repro.kernels import ops as kops

                def worker(Vi, ci, Bvi, bi):
                    return kops.sparse_cimmino_update(Vi, ci, Bvi, bi,
                                                      state.xbar)[0]

                r = jax.vmap(worker)(Asp.vals, Asp.cols, factors.B, b)
            else:
                u = blockops.bmatvec(factors.A, state.xbar)
                w = _cho_solve_workers(factors.chol, b - u)
                r = blockops.brmatvec(factors.A, w)   # row projections
            return CimminoState(xbar=state.xbar + nu * jnp.sum(r, axis=0),
                                t=state.t + 1)
        kern = use_kernel and factors.B is not None
        if kern:
            # single-RHS cimmino is the measured corner where the fused
            # pair LOSES (no batch to amortize the A/B tile reads) — the
            # engine autotune includes "unfused" as a candidate and this
            # dispatch honors it at trace time
            from repro.kernels import ops as kops
            kern = kops.use_fused("cimmino", factors.A.shape[1],
                                  factors.A.shape[2], 1, factors.A.dtype)
        if kern:
            from repro.kernels import ops as kops

            # the dedicated Cimmino kernel pair: r_i = B_i (b_i − A_i x̄)
            # (B = A^T G^{-1} bakes the Gram inverse in, so no per-step
            # cho_solve and no rewrite onto the APC update shape)
            def worker(Ai, Bi, bi):
                return kops.cimmino_update(Ai, Bi, bi, state.xbar)

            r = jax.vmap(worker)(factors.A, factors.B, b)
        else:
            def worker(Ai, Li, bi):
                u = jax.scipy.linalg.cho_solve((Li, True), bi - Ai @ state.xbar)
                return Ai.T @ u

            r = jax.vmap(worker)(factors.A, factors.chol, b)
        return CimminoState(xbar=state.xbar + nu * jnp.sum(r, axis=0),
                            t=state.t + 1)

    def step_many(self, factors, Bb, states, params, *, use_kernel=False):
        """Fused multi-RHS row projections (Bb (k, m, p), x̄ (k, n))."""
        if not (use_kernel and factors.B is not None):
            return super().step_many(factors, Bb, states, params,
                                     use_kernel=use_kernel)
        from repro.kernels import ops as kops
        if blockops.is_sparse(factors.A):
            Asp = factors.A
            if not _sparse_use_fused("cimmino_sparse", Asp, Bb.shape[0]):
                return super().step_many(factors, Bb, states, params,
                                         use_kernel=False)  # measured fb
            bw = jnp.swapaxes(Bb, 0, 1)                   # (m, k, p)

            def worker(Vi, ci, Bvi, bi):
                return kops.sparse_cimmino_update(Vi, ci, Bvi, bi,
                                                  states.xbar)[0]

            r = jax.vmap(worker)(Asp.vals, Asp.cols, factors.B, bw)
            return CimminoState(
                xbar=states.xbar + params["nu"] * jnp.sum(r, 0),
                t=states.t + 1)
        if not kops.use_fused("cimmino", factors.A.shape[1],
                              factors.A.shape[2], Bb.shape[0],
                              factors.A.dtype):
            return super().step_many(factors, Bb, states, params,
                                     use_kernel=False)   # measured fallback
        bw = jnp.swapaxes(Bb, 0, 1)                       # (m, k, p)

        def worker(Ai, Bi, bi):
            return kops.cimmino_update(Ai, Bi, bi, states.xbar)   # (k, n)

        r = jax.vmap(worker)(factors.A, factors.B, bw)    # (m, k, n)
        return CimminoState(xbar=states.xbar + params["nu"] * jnp.sum(r, 0),
                            t=states.t + 1)

    # ----- fused residual --------------------------------------------------
    # The gather result u_i = A_i x̄ gives the consumed state's residual
    # blocks directly: A x̄ − b = u − b = −v where v = b − u is exactly the
    # operand the scatter consumes, so the history rides along for free.
    supports_fused_residual = True

    def cast_factors(self, factors, precision):
        return _cast_proj_factors(factors, precision)

    def _r_v(self, factors, b, xbar):
        """Row projections r plus v = b − A x̄ (the residual source);
        engine dispatch identical to ``step``.  Batch-polymorphic: b may
        be (m, p) or (m, k, p) with xbar (n,) / (k, n)."""
        k = b.shape[1] if b.ndim == 3 else 1
        sparse = blockops.is_sparse(factors.A)
        kern = factors.B is not None
        if kern:
            if sparse:
                kern = _sparse_use_fused("cimmino_sparse", factors.A, k)
            else:
                from repro.kernels import ops as kops
                kern = kops.use_fused("cimmino", factors.A.shape[1],
                                      factors.A.shape[2], k,
                                      factors.A.dtype)
        if kern and sparse:
            from repro.kernels import ops as kops
            Asp = factors.A

            def worker(Vi, ci, Bvi, bi):
                return kops.sparse_cimmino_update(Vi, ci, Bvi, bi, xbar)

            r, u = jax.vmap(worker)(Asp.vals, Asp.cols, factors.B, b)
        elif kern:
            from repro.kernels import ops as kops
            u = jax.vmap(lambda Ai: kops.cimmino_gather(Ai, xbar))(
                factors.A)                                # (m[, k], p)
            r = jax.vmap(kops.cimmino_scatter)(factors.B, b - u)
        else:
            def one(bk, xk):
                uk = blockops.bmatvec(factors.A, xk)      # (m, p)
                wk = _cho_solve_workers(factors.chol, bk - uk)
                return blockops.brmatvec(factors.A, wk), bk - uk

            if b.ndim == 2:
                return one(b, xbar)
            # batched: map the k axis (b (m, k, p) ax 1, xbar (k, n) ax 0)
            return jax.vmap(one, in_axes=(1, 0), out_axes=(1, 1))(b, xbar)
        return r, b - u

    def step_residual(self, factors, b, state, params):
        r, v = self._r_v(factors, b, state.xbar)
        return (CimminoState(xbar=state.xbar + params["nu"] * jnp.sum(r, 0),
                             t=state.t + 1),
                jnp.sum(v * v))

    def step_many_residual(self, factors, Bb, states, params):
        bw = jnp.swapaxes(Bb, 0, 1)                       # (m, k, p)
        r, v = self._r_v(factors, bw, states.xbar)        # (m, k, n/p)
        rsq = jnp.sum(v * v, axis=(0, 2))                 # (k,)
        return (CimminoState(
            xbar=states.xbar + params["nu"] * jnp.sum(r, 0),
            t=states.t + 1), rsq)

    def extract(self, state):
        return state.xbar

    # ----- mesh backend ---------------------------------------------------
    def mesh_factor_specs(self, ctx, use_kernel=False):
        return ProjFactors(A=P(ctx.w, None, ctx.n),
                           chol=P(ctx.w, None, None),
                           B=P(ctx.w, ctx.n, None) if use_kernel else None)

    def mesh_state_specs(self, ctx):
        return CimminoState(xbar=P(ctx.n), t=P())

    def mesh_factors(self, factors, use_kernel=False):
        if use_kernel:
            return _with_pinv(factors)
        return factors._replace(B=None)

    def mesh_prepare(self, A, params, ctx, use_kernel=False):
        factors = ProjFactors(
            A=A, chol=_mesh_gram_chol(A, params.get("jitter", 0.0), ctx))
        if use_kernel:
            factors = _with_pinv(factors)     # shard-local, see APCSolver
        return factors

    def _mesh_r_v(self, factors, b, xbar, ctx, use_kernel):
        """Local row projections r plus full v = b − A x̄ (the residual
        source) from local shards."""
        if use_kernel and factors.B is not None:
            from repro.kernels import ops as kops
            if blockops.is_sparse(factors.A):
                # sparse systems shard over worker axes only (cols index
                # the global n), so the fused pair composes per worker
                Asp = factors.A

                def worker(Vi, ci, Bvi, bi):
                    return kops.sparse_cimmino_update(Vi, ci, Bvi, bi, xbar)

                r, u = jax.vmap(worker)(Asp.vals, Asp.cols, factors.B, b)
                return r, b - ctx.psum_model(u)
            u = ctx.psum_model(jax.vmap(
                lambda Ai: kops.cimmino_gather(Ai, xbar))(factors.A))
            return jax.vmap(kops.cimmino_scatter)(factors.B, b - u), b - u
        u = ctx.psum_model(blockops.bmatvec(factors.A, xbar))
        w = _cho_solve_workers(factors.chol, b - u)   # G^{-1}(b - A xbar)
        return blockops.brmatvec(factors.A, w), b - u  # row projections

    def mesh_step(self, factors, b, state, params, ctx, *, use_kernel=False):
        r, _ = self._mesh_r_v(factors, b, state.xbar, ctx, use_kernel)
        s = ctx.psum_workers(jnp.sum(r, axis=0))
        return CimminoState(xbar=state.xbar + params["nu"] * s,
                            t=state.t + 1)

    def mesh_step_residual(self, factors, b, state, params, ctx):
        """Mesh step plus ‖A x̄ − b‖² of the CONSUMED state, harvested
        from the gather pass (v = b − A x̄)."""
        r, v = self._mesh_r_v(factors, b, state.xbar, ctx, True)
        s = ctx.psum_workers(jnp.sum(r, axis=0))
        rsq = ctx.psum_workers(jnp.sum(v * v))
        return CimminoState(xbar=state.xbar + params["nu"] * s,
                            t=state.t + 1), rsq

    # ----- least-squares mode ---------------------------------------------
    # The Cimmino fixed point minimizes Σᵢ ‖L_i^{-1}(A_i x − b_i)‖² — the
    # Gram-whitened least-squares problem.  ``ls_moment`` is exactly the
    # update direction (zero at the optimum); ``ls_reference`` solves the
    # whitened system directly for error tracking.
    def ls_moment(self, factors, A, b, x, params, ctx):
        u = ctx.psum_model(blockops.bmatvec(A, x))
        w = _cho_solve_workers(factors.chol, b - u)
        r = blockops.brmatvec(A, w)
        return ctx.psum_workers(jnp.sum(r, axis=0))

    def ls_reference(self, sys: BlockSystem) -> jnp.ndarray:
        A = np.asarray(sys.A_blocks, dtype=np.float64)
        b = np.asarray(sys.b_blocks, dtype=np.float64)
        rows = []
        rhs = []
        for Ai, bi in zip(A, b):
            L = np.linalg.cholesky(Ai @ Ai.T)
            rows.append(np.linalg.solve(L, Ai))       # L_i^{-1} A_i
            rhs.append(np.linalg.solve(L, bi))        # L_i^{-1} b_i
        x, *_ = np.linalg.lstsq(np.concatenate(rows), np.concatenate(rhs),
                                rcond=None)
        return jnp.asarray(x, dtype=sys.b_blocks.dtype)

    def _mesh_r_v_many(self, factors, Bb, xbar, ctx):
        """Batched kernel-path row projections: r (m_loc, k, n_loc) and
        v = b − A x̄ (m_loc, k, p).  Bb (k, m_loc, p); x̄ (k, n_loc)."""
        from repro.kernels import ops as kops
        bw = jnp.swapaxes(Bb, 0, 1)                       # (m_loc, k, p)
        if blockops.is_sparse(factors.A):
            Asp = factors.A

            def worker(Vi, ci, Bvi, bi):
                return kops.sparse_cimmino_update(Vi, ci, Bvi, bi, xbar)

            r, u = jax.vmap(worker)(Asp.vals, Asp.cols, factors.B, bw)
            return r, bw - ctx.psum_model(u)
        # gather is RHS-batched per worker
        u = ctx.psum_model(jax.vmap(
            lambda Ai: kops.cimmino_gather(Ai, xbar))(factors.A))
        v = bw - u                                        # (m_loc, k, p)
        return jax.vmap(kops.cimmino_scatter)(factors.B, v), v

    def mesh_step_many(self, factors, Bb, states, params, ctx, *,
                       use_kernel=False):
        if not (use_kernel and factors.B is not None):
            return super().mesh_step_many(factors, Bb, states, params, ctx)
        r, _ = self._mesh_r_v_many(factors, Bb, states.xbar, ctx)
        s = ctx.psum_workers(jnp.sum(r, axis=0))          # (k, n_loc)
        return CimminoState(xbar=states.xbar + params["nu"] * s,
                            t=states.t + 1)

    def mesh_step_many_residual(self, factors, Bb, states, params, ctx):
        if factors.B is not None:
            r, v = self._mesh_r_v_many(factors, Bb, states.xbar, ctx)
            s = ctx.psum_workers(jnp.sum(r, axis=0))
            rsq = ctx.psum_workers(jnp.sum(v * v, axis=(0, 2)))   # (k,)
        else:
            def one(bk, xk):
                uk = ctx.psum_model(blockops.bmatvec(factors.A, xk))
                vk = bk - uk
                wk = _cho_solve_workers(factors.chol, vk)
                return blockops.brmatvec(factors.A, wk), vk

            r, v = jax.vmap(one)(Bb, states.xbar)         # (k, m_loc, ·)
            s = ctx.psum_workers(jnp.sum(r, axis=1))
            rsq = ctx.psum_workers(jnp.sum(v * v, axis=(1, 2)))
        return CimminoState(xbar=states.xbar + params["nu"] * s,
                            t=states.t + 1), rsq

    # ----- redundant execution (solvers/redundant.py) ---------------------
    # State is the master estimate alone (already global-shaped): the
    # masked sum of row projections replaces the plain worker-axis sum.
    supports_redundancy = True

    def red_init(self, factors, b, params, W0, ctx):
        return CimminoState(xbar=jnp.zeros(factors.A.shape[3],
                                           factors.A.dtype),
                            t=jnp.zeros((), jnp.int32))

    def red_step(self, factors, b, state, params, W, ctx):
        u = ctx.psum_model(jnp.einsum("mrpn,n->mrp", factors.A, state.xbar))
        w = _cho_solve_replicas(factors.chol, b - u)
        r = jnp.einsum("mrpn,mrp->mrn", factors.A, w)     # row projections
        s = ctx.psum_workers(jnp.einsum("mr,mrn->n", W, r))
        return CimminoState(xbar=state.xbar + params["nu"] * s,
                            t=state.t + 1)

    # ----- cross-partition warm start (solvers/elastic.py) ------------------
    # The state is the master estimate alone and carries no per-block
    # invariant, so it lifts across any repartition verbatim.
    supports_lift = True
    supports_block_store = True    # per-block Gram Cholesky, leading m axis

    def lift_state(self, factors, b, params, x):
        return CimminoState(xbar=jnp.asarray(x), t=jnp.zeros((), jnp.int32))
