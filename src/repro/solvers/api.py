"""The canonical solver API: one lifecycle, one result type.

Every distributed solver in this repo — APC and all of the paper's
comparison methods — implements the same three-phase lifecycle:

    factors = solver.prepare(A_blocks, params)   # one-time, b-INDEPENDENT
    state   = solver.init(factors, b_blocks, params)
    state   = solver.step(factors, b_blocks, state, params)

on top of which this module provides the shared drivers:

    solver.solve(sys, iters=..., **params)       -> SolveResult
    solver.solve_many(sys, B, iters=...)         -> SolveResult (batched)

``prepare`` must not look at the right-hand side: everything expensive
(Gram Cholesky factors, preconditioners) depends only on A, which is what
lets ``solve_many`` amortize one factorization across a batch of RHS — the
serving hot path — and lets a cached ``factors`` be reused across requests.

Warm starts: any prior ``SolveResult.state`` (or a state restored via
``repro.checkpoint.ckpt``) can be passed back as ``solve(...,
warm_state=state)`` to resume iterating instead of starting from scratch.

Projection-family solvers (``apc``, ``consensus``, ``cimmino``) additionally
accept ``use_kernel=True`` to route the per-worker projection through the
Pallas TPU kernels — on BOTH backends (each mesh shard runs the kernel on
its local block; the psum contract is unchanged), and with ``solve_many``
batches fused through the multi-RHS kernels (one A/B read serves the whole
batch) — and auto-tune their parameters from the Theorem-1 spectral
analysis when none are given.

Backends: ``solve(..., backend="mesh", mesh=...)`` runs the same lifecycle
sharded across a device mesh via shard_map (see ``solvers/mesh.py``) — the
row blocks shard over the mesh's worker axes, the master update becomes a
psum, and setup runs on-mesh so no host materializes the full A.  States
keep global shapes, so warm starts and checkpoints round-trip between the
two backends.
"""
from __future__ import annotations

import dataclasses
import logging
import warnings
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import blockops
from repro.core.partition import BlockSystem
from repro.solvers.capability import (CapabilityError, ExecutionPlan,
                                      resolve_plan)

log = logging.getLogger("repro.solvers")

__all__ = ["Solver", "SolveResult", "CapabilityError", "ExecutionPlan",
           "iters_to_tolerance"]


_UNSET = object()     # sentinel distinguishing "not passed" from None

# legacy kwarg -> ExecutionPlan field (the use_kernel rename is the only
# non-identity entry); everything here goes through the deprecation shim
_LEGACY_PLAN_KWARGS = {
    "use_kernel": "kernel", "precision": "precision",
    "warm_state": "warm_state", "factors": "factors", "store": "store",
    "backend": "backend", "mesh": "mesh", "worker_axes": "worker_axes",
    "model_axis": "model_axis", "redundancy": "redundancy",
    "alive_schedule": "alive_schedule",
}


def _coerce_plan(plan: Optional[ExecutionPlan], legacy: Dict[str, Any],
                 *, context: str) -> ExecutionPlan:
    """Resolve the plan/legacy-kwarg split of a solve call.

    Exactly one of the two surfaces may be used: an explicit ``plan=``
    wins, loose legacy kwargs build one through this shim and emit
    exactly ONE ``DeprecationWarning`` per call (however many kwargs
    were passed), and mixing the two is an error — silently merging
    would make the plan lie about what runs.
    """
    given = {k: v for k, v in legacy.items() if v is not _UNSET}
    if plan is not None:
        if given:
            raise ValueError(
                f"{context} was called with both plan= and the legacy "
                f"kwargs {sorted(given)}; put everything on the "
                f"ExecutionPlan")
        if not isinstance(plan, ExecutionPlan):
            raise TypeError(f"plan= must be an ExecutionPlan, got "
                            f"{type(plan).__name__}")
        return plan
    if not given:
        return ExecutionPlan()
    warnings.warn(
        f"passing {sorted(given)} to {context} as loose kwargs is "
        f"deprecated; build an ExecutionPlan and pass plan= instead "
        f"(e.g. plan=ExecutionPlan("
        + ", ".join(f"{_LEGACY_PLAN_KWARGS[k]}=..." for k in sorted(given))
        + "))", DeprecationWarning, stacklevel=3)
    return ExecutionPlan(**{_LEGACY_PLAN_KWARGS[k]: v
                            for k, v in given.items()})


class _LocalPsum:
    """Degenerate psum context for the local backend: a single shard, so
    both reductions are identities.  Lets the LS-mode hooks be written
    once against the MeshContext psum contract and run on both backends."""

    @staticmethod
    def psum_workers(x):
        return x

    @staticmethod
    def psum_model(x):
        return x


LOCAL_PSUM = _LocalPsum()


@dataclasses.dataclass(frozen=True)
class SolveResult:
    """Unified result record returned by every registered solver.

    For ``solve_many`` the leading axis of ``x`` / ``residuals`` /
    ``iters_to_tol`` is the RHS batch and ``errors`` is None.
    """
    name: str                      # registry key of the solver that ran
    x: jnp.ndarray                 # final global estimate (n,) or (k, n)
    state: Any                     # full solver state (checkpoint / warm-start)
    residuals: jnp.ndarray         # (T,) or (k, T)  ||Ax-b|| / ||b|| per iter
    errors: Optional[jnp.ndarray]  # (T,) ||x-x*||/||x*|| if sys.x_true given
    params: Dict[str, float]       # hyper-parameters actually used
    iters_to_tol: Any = -1         # first 1-based iter with residual < tol;
                                   # the sentinel -1 means "never reached"
                                   # (int for solve, (k,) int array for
                                   # solve_many — SAME sentinel in both)
    tol: float = 1e-6              # tolerance iters_to_tol was computed at

    def iters_to(self, tol: float):
        """Iterations needed to push the residual below ``tol``."""
        return iters_to_tolerance(self.residuals, tol)


def iters_to_tolerance(residuals, tol: float):
    """First 1-based iteration whose residual is < tol; -1 = never reached.

    Returns an int for a (T,) history and a (k,) int array for a batched
    (k, T) history — the never-reached sentinel is -1 in BOTH cases, so
    ``solve`` and ``solve_many`` results compare uniformly.
    """
    r = np.asarray(residuals)
    hit = r < tol
    if r.ndim == 1:
        return int(np.argmax(hit)) + 1 if hit.any() else -1
    first = np.argmax(hit, axis=-1) + 1
    return np.where(hit.any(axis=-1), first, -1)


class Solver:
    """Base class / protocol for every registered solver.

    Subclasses override the four lifecycle hooks (and ``default_params``)
    and inherit the shared ``solve`` / ``solve_many`` drivers.
    """

    name: str = "solver"
    paper_name: str = ""           # display name used in the paper's tables
    supports_kernel: bool = False  # Pallas block-projection path available
    param_names: Tuple[str, ...] = ()
    # System classes this solver handles; checked at dispatch against the
    # system's (mode, structure) — see solvers/capability.py.  "square" =
    # a consistent system with an exact solution; "least_squares" = the
    # iteration converges to argmin ||Ax-b|| on inconsistent systems (and
    # the LS hooks below are implemented); "sparse" = the step chain
    # consumes blockops.SparseBlocks operands.
    supports: frozenset = frozenset({"square"})
    # A prior state is a valid warm start for a DIFFERENT right-hand side:
    # the iteration re-reads b every step and the state caches nothing
    # RHS-dependent.  True for the gradient family and Cimmino; False for
    # APC (iterates stay feasible for the OLD b), M-ADMM (caches A^T b),
    # and P-DHBM (caches S b).  The serving layer gates perturbed-RHS warm
    # starts on this flag.
    warm_rhs_ok: bool = False

    # ----- lifecycle hooks (override) -------------------------------------
    def default_params(self, sys: BlockSystem) -> Dict[str, float]:
        """Analysis-time auto-tuning (Theorem 1 / Sec 4 closed forms)."""
        return {}

    def prepare(self, A: jnp.ndarray, params: Dict[str, float]) -> Any:
        """One-time factorization from the (m, p, n) row blocks only.

        MUST be independent of b — solve_many reuses it across a RHS batch.
        """
        raise NotImplementedError

    def init(self, factors: Any, b: jnp.ndarray,
             params: Dict[str, float]) -> Any:
        """Initial state for right-hand side blocks ``b`` of shape (m, p)."""
        raise NotImplementedError

    def step(self, factors: Any, b: jnp.ndarray, state: Any,
             params: Dict[str, float], *, use_kernel: bool = False) -> Any:
        """One synchronous iteration (all workers + master)."""
        raise NotImplementedError

    def step_many(self, factors: Any, Bb: jnp.ndarray, states: Any,
                  params: Dict[str, float], *,
                  use_kernel: bool = False) -> Any:
        """One iteration over a (k,)-batched RHS/state bundle.

        The default vmaps ``step`` over the batch axis; projection-family
        solvers override the ``use_kernel=True`` branch with the true
        multi-RHS Pallas kernels, where ONE read of every A/B tile serves
        the whole batch (``solve_many`` / ``LinsysServer`` hot path).
        """
        return jax.vmap(
            lambda b, s: self.step(factors, b, s, params,
                                   use_kernel=use_kernel),
            in_axes=(0, 0))(Bb, states)

    def extract(self, state: Any) -> jnp.ndarray:
        """The global estimate x (n,) carried by ``state``."""
        raise NotImplementedError

    # A solver that can rebuild a valid state for a NEW partition from
    # nothing but the global estimate sets this and implements
    # ``lift_state``.  This is the cross-partition warm start the elastic
    # runtime uses when the fleet is repartitioned (join/rejoin): states
    # are global-SHAPED but their per-block invariants (e.g. APC's
    # A_i x_i = b_i feasibility) are partition-specific, so a plain
    # ``warm_state=`` handoff across a repartition would be wrong.
    supports_lift: bool = False

    # ``prepare`` factorizes each row block independently and every factor
    # leaf carries a leading worker axis — the contract that lets
    # ``FactorStore.blockwise_factors`` assemble full factors from cached
    # per-block slices after a repartition.
    supports_block_store: bool = False

    def lift_state(self, factors: Any, b: jnp.ndarray,
                   params: Dict[str, float], x: jnp.ndarray) -> Any:
        """A state for THIS partition warm-started from the global
        estimate ``x`` of a previous (differently-partitioned) run.
        Must satisfy every invariant ``init`` establishes; ``extract``
        of the result should be (close to) ``x``."""
        raise NotImplementedError(
            f"solver {self.name!r} cannot lift a state across partitions "
            f"(supports_lift=False)")

    # ----- optional analysis hooks ----------------------------------------
    def theoretical_rate(self, sys: BlockSystem) -> Optional[float]:
        """Closed-form optimal spectral radius rho, if known (Table 1)."""
        return None

    def analyze(self, sys: BlockSystem):
        """(auto-tuned params, theoretical rho) in ONE spectral pass.

        Subclasses whose default_params and theoretical_rate share the same
        eigendecomposition override this to avoid computing it twice.
        """
        return self.default_params(sys), self.theoretical_rate(sys)

    def kernel_factors(self, factors: Any) -> Any:
        """Augment factors with kernel-path precomputation (pinv factors).

        Called once per solve when ``use_kernel=True`` so per-step code
        never refactorizes iteration-invariant quantities.  MUST be
        idempotent: implementations detect already-augmented factors (or
        tag them) and return them unchanged, so cached or user-supplied
        factors passed back into ``solve(use_kernel=True)`` are never
        re-augmented — the ``FactorStore`` relies on this to write the
        augmentation back into the cache slot exactly once.
        """
        return factors

    # ----- fused residual hooks -------------------------------------------
    # A kernel-capable solver whose gather pass already computes the
    # consumed state's residual blocks sets ``supports_fused_residual``
    # and implements the ``*_residual`` step variants, each returning
    # ``(new_state, rsq)`` with ``rsq`` the SQUARED residual norm of the
    # state the step CONSUMED (scalar, or (k,) for the batched variants).
    # The history drivers then record ‖Ax−b‖ per iteration withOUT a
    # second full read of A: the lagged records are shifted by one and the
    # history closes with a single true-A residual of the final state.
    supports_fused_residual: bool = False

    def step_residual(self, factors: Any, b: jnp.ndarray, state: Any,
                      params: Dict[str, float]) -> Tuple[Any, jnp.ndarray]:
        raise NotImplementedError(
            f"solver {self.name!r} does not implement the fused residual")

    def step_many_residual(self, factors: Any, Bb: jnp.ndarray, states: Any,
                           params: Dict[str, float]
                           ) -> Tuple[Any, jnp.ndarray]:
        raise NotImplementedError(
            f"solver {self.name!r} does not implement the fused residual")

    def mesh_step_residual(self, factors: Any, b: jnp.ndarray, state: Any,
                           params: Dict[str, float], ctx
                           ) -> Tuple[Any, jnp.ndarray]:
        raise NotImplementedError(
            f"solver {self.name!r} does not implement the fused residual")

    def mesh_step_many_residual(self, factors: Any, Bb: jnp.ndarray,
                                states: Any, params: Dict[str, float], ctx
                                ) -> Tuple[Any, jnp.ndarray]:
        raise NotImplementedError(
            f"solver {self.name!r} does not implement the fused residual")

    # ----- mixed-precision tile streams -----------------------------------
    def cast_factors(self, factors: Any, precision: str) -> Any:
        """Cast the kernel tile streams to the storage precision.

        ``precision="mixed"`` stores the memory-bound operand streams
        (A/B tiles) in bfloat16 while every kernel contraction still
        accumulates in f32 and the factorization (Cholesky) stays in the
        working precision.  Idempotent — casting already-cast factors is
        a no-op — so store-cached mixed factors round-trip freely.
        """
        if precision == "default":
            return factors
        raise NotImplementedError(
            f"solver {self.name!r} does not implement precision="
            f"{precision!r}")

    def _check_precision(self, precision: str, use_kernel: bool) -> None:
        if precision == "default":
            return
        if precision != "mixed":
            raise ValueError(f"unknown precision {precision!r}; expected "
                             f"'default' or 'mixed'")
        if not (use_kernel and self.supports_kernel):
            raise ValueError(
                "precision='mixed' casts the Pallas kernel tile streams "
                "(bf16 storage, f32 accumulation) and therefore requires "
                "use_kernel=True on a kernel-capable solver; "
                f"{self.name!r} was dispatched with use_kernel="
                f"{use_kernel} (supports_kernel={self.supports_kernel})")

    # ----- least-squares mode hooks ---------------------------------------
    # A solver declaring "least_squares" in ``supports`` implements BOTH
    # hooks (lint rule R008 enforces this).  ``ls_moment`` is the solver's
    # optimality map: the (weighted) normal-equation residual its fixed
    # point zeroes — plain A^T(Ax-b) for the gradient family, the
    # G^{-1}-weighted A^T G^{-1}(Ax-b) for Cimmino.  It is written against
    # the psum context so the same code runs locally (identity psums) and
    # inside shard_map; residual histories in LS mode report
    # ||ls_moment(x)|| / ||ls_moment(0)||, the scale-free LS optimality
    # measure, and ``iters_to_tol`` keys off it.

    def ls_moment(self, factors: Any, A, b: jnp.ndarray, x: jnp.ndarray,
                  params: Dict[str, float], ctx) -> jnp.ndarray:
        """The (n,) optimality vector this solver drives to zero."""
        raise NotImplementedError(
            f"solver {self.name!r} does not support least-squares mode")

    def ls_reference(self, sys: BlockSystem) -> jnp.ndarray:
        """The (n,) solution this solver converges to on an inconsistent
        system — the reference ``errors`` compares against when
        ``sys.x_true`` is absent."""
        raise NotImplementedError(
            f"solver {self.name!r} does not support least-squares mode")

    # ----- mesh-backend hooks (see solvers/mesh.py) ------------------------
    # The mesh backend runs these INSIDE shard_map: every array argument is
    # the device-local shard (worker axis and optionally the n axis cut),
    # and cross-shard reductions go through the MeshContext psum helpers.
    # Specs use ctx.w (worker axis entry) / ctx.n (column axis entry).

    def mesh_factor_specs(self, ctx):
        """PartitionSpec pytree matching ``prepare``'s factor structure."""
        raise NotImplementedError(
            f"solver {self.name!r} does not implement the mesh backend")

    def mesh_state_specs(self, ctx):
        """PartitionSpec pytree matching the solver state structure."""
        raise NotImplementedError(
            f"solver {self.name!r} does not implement the mesh backend")

    def mesh_prepare(self, A: jnp.ndarray, params: Dict[str, float], ctx):
        """On-mesh ``prepare`` from a local (m_loc, p, n_loc) shard of A."""
        raise NotImplementedError(
            f"solver {self.name!r} does not implement the mesh backend")

    def mesh_init(self, factors: Any, b: jnp.ndarray,
                  params: Dict[str, float], ctx) -> Any:
        """On-mesh ``init``; the default reuses ``init``, which is correct
        whenever it contains no cross-worker/cross-column reduction."""
        return self.init(factors, b, params)

    def mesh_step(self, factors: Any, b: jnp.ndarray, state: Any,
                  params: Dict[str, float], ctx) -> Any:
        """One iteration on local shards (collectives via ``ctx``)."""
        raise NotImplementedError(
            f"solver {self.name!r} does not implement the mesh backend")

    def mesh_step_many(self, factors: Any, Bb: jnp.ndarray, states: Any,
                       params: Dict[str, float], ctx, *,
                       use_kernel: bool = False) -> Any:
        """Batched mesh step (RHS axis leading, replicated across shards).

        Default vmaps ``mesh_step``; projection solvers override the
        kernel branch with the multi-RHS Pallas kernels on the local
        (p × n_local) blocks — shard_map composes with Pallas, and the
        psum contract is identical (``use_kernel`` only reaches solvers
        with ``supports_kernel``, so the base may ignore it)."""
        return jax.vmap(
            lambda bb, st: self.mesh_step(factors, bb, st, params, ctx),
            in_axes=(0, 0))(Bb, states)

    def mesh_factors(self, factors: Any) -> Any:
        """Strip host-only fields before reusing factors on the mesh."""
        return factors

    # ----- redundancy hooks (see solvers/redundant.py) ---------------------
    # Straggler-tolerant execution replicates the row blocks r-redundantly
    # (cyclic assignment) and replaces the worker-axis reduction with a
    # masked block-unique one.  ``red_step``/``red_init`` are written ONCE
    # against the MeshContext psum contract: on the local backend the psums
    # are identities, on backend="mesh" they are the usual collectives.
    # Array layouts grow a slot axis: factors/b (m, r, ...), W is the
    # (m, r) selection-weight mask for the iteration.

    supports_redundancy: bool = False

    def red_factors(self, factors: Any, assign) -> Any:
        """Replicate b-independent factors along the cyclic assignment.

        Default: gather every leaf's leading worker axis through
        ``assign.holder`` — correct whenever all factor leaves are
        per-worker (leading axis m)."""
        return jax.tree.map(lambda f: jnp.asarray(f)[assign.holder], factors)

    def red_init(self, factors: Any, b: jnp.ndarray,
                 params: Dict[str, float], W0, ctx) -> Any:
        """Initial GLOBAL-structure state from replicated factors/b and the
        all-alive selection weights ``W0``."""
        raise NotImplementedError(
            f"solver {self.name!r} does not implement redundant execution")

    def red_step(self, factors: Any, b: jnp.ndarray, state: Any,
                 params: Dict[str, float], W, ctx) -> Any:
        """One masked iteration: every replica updates, the master reduce
        takes each block exactly once via ``W``."""
        raise NotImplementedError(
            f"solver {self.name!r} does not implement redundant execution")

    def red_expand(self, state: Any, assign) -> Any:
        """Lift a plain global-shape state to the replicated internal one
        (exactness invariant: replicas are identical copies).  Default:
        identity, for states with no per-block leaves."""
        return state

    def red_collapse(self, state: Any, assign) -> Any:
        """Inverse of ``red_expand``: back to the plain global shape so
        warm starts and checkpoints round-trip with non-redundant runs."""
        return state

    def red_factor_specs(self, ctx):
        """Mesh placement of replicated factors: the slot axis is local to
        its worker, so insert an unsharded dim after the worker axis."""
        from jax.sharding import PartitionSpec as _P
        return jax.tree.map(
            lambda s: _P(tuple(s)[0], None, *tuple(s)[1:]),
            self.mesh_factor_specs(ctx),
            is_leaf=lambda s: isinstance(s, _P))

    def red_state_specs(self, ctx):
        """Mesh placement of the replicated internal state (defaults to the
        plain state specs; override when state gains a slot axis)."""
        return self.mesh_state_specs(ctx)

    # ----- shared drivers --------------------------------------------------
    def resolve_params(self, sys: BlockSystem, **overrides) -> Dict[str, float]:
        """Merge explicit overrides over the auto-tuned defaults.

        The (possibly expensive) spectral analysis in ``default_params`` is
        skipped when the caller pins every required parameter.
        """
        given = {k: v for k, v in overrides.items() if v is not None}
        if self.param_names and all(k in given for k in self.param_names):
            return given
        return {**self.default_params(sys), **given}

    def _check_kernel(self, use_kernel: bool):
        if use_kernel and not self.supports_kernel:
            raise ValueError(
                f"solver {self.name!r} is not projection-based and has no "
                f"Pallas kernel path (use_kernel=True unsupported)")

    def _dispatch_mesh(self, backend: str, use_kernel: bool,
                       mesh: Any) -> bool:
        if backend == "local":
            if mesh is not None:
                raise ValueError("a mesh was passed but backend is 'local' "
                                 "— did you mean backend='mesh'?")
            return False
        if backend != "mesh":
            raise ValueError(f"unknown backend {backend!r}; "
                             "expected 'local' or 'mesh'")
        # use_kernel composes with the mesh backend: shard_map hands each
        # worker shard its local (p, n_local) block and the Pallas kernels
        # run on it unchanged (the psum contract is outside the kernel).
        self._check_kernel(use_kernel)
        return True

    def _store_factors(self, store, sys, factors, params, *,
                       use_kernel: bool = False, resume: bool = False):
        """Route the ``factors is None`` branch through a ``FactorStore``.

        Returns ``(factors, params)`` with params fully resolved when the
        store was consulted (so downstream ``resolve_params`` calls are
        cheap no-ops and the store key matches what actually runs).
        """
        if factors is not None or store is None:
            return factors, params
        prm = self.resolve_params(sys, **params)
        return store.factors(self, sys, use_kernel=use_kernel,
                             resume=resume, **prm), prm

    def solve(self, sys: BlockSystem, *, iters: int = 1000, tol: float = 1e-6,
              plan: Optional[ExecutionPlan] = None,
              use_kernel: Any = _UNSET, precision: Any = _UNSET,
              warm_state: Any = _UNSET,
              factors: Any = _UNSET, store: Any = _UNSET,
              backend: Any = _UNSET, mesh: Any = _UNSET,
              worker_axes: Any = _UNSET, model_axis: Any = _UNSET,
              redundancy: Any = _UNSET, alive_schedule: Any = _UNSET,
              **params) -> SolveResult:
        """End-to-end solve: prepare -> init (or warm-start) -> scan steps.

        The execution surface travels on ONE validated object::

            solve(sys, plan=ExecutionPlan(backend="mesh", kernel=True),
                  iters=500, tol=1e-6, **params)

        ``plan.factors`` (from an earlier ``prepare`` with the same
        params) skips the one-time factorization; ``plan.store``
        (``solvers.FactorStore``) turns the ``factors is None`` branch
        into a content-addressed cache lookup (memory LRU, optional disk
        tier) instead of an unconditional re-``prepare``.  Cached-factor
        serving (``solvers.serve``) and the checkpoint-resume driver use
        these.

        ``backend="mesh"`` runs the identical lifecycle sharded over a
        device mesh (``mesh=None`` builds one over the available
        devices); ``worker_axes``/``model_axis`` choose which mesh axes
        the row blocks and the n dimension shard over.

        ``redundancy=r`` (projection family, both backends) replicates
        the row blocks r-redundantly so iterations tolerate stragglers
        named by ``alive_schedule`` (callable t -> (m,) mask, a mask
        array, or a ``runtime.fault.HeartbeatMonitor``) with EXACT
        semantics — see ``solvers/redundant.py``.

        ``precision="mixed"`` (kernel path only) stores the streamed A/B
        tiles in bfloat16 with f32 accumulation — residual histories hold
        to the bf16 storage tolerance (~1e-2 relative) at half the HBM
        bytes per iteration.

        The loose kwargs (``use_kernel=``, ``backend=``, ...) are a
        DEPRECATED shim: they build the same plan and warn once.
        """
        plan = _coerce_plan(plan, dict(
            use_kernel=use_kernel, precision=precision,
            warm_state=warm_state, factors=factors, store=store,
            backend=backend, mesh=mesh, worker_axes=worker_axes,
            model_axis=model_axis, redundancy=redundancy,
            alive_schedule=alive_schedule), context="solve")
        plan = resolve_plan(self, sys, plan, context="solve")
        resume = plan.warm_state is not None
        if plan.is_redundant:
            factors, params = self._store_factors(
                plan.store, sys, plan.factors, params, resume=resume)
            from . import redundant as red_backend
            return red_backend.solve_redundant(
                self, sys, r=plan.redundancy, iters=iters, tol=tol,
                alive_schedule=plan.alive_schedule,
                warm_state=plan.warm_state, factors=factors,
                backend=plan.backend, mesh=plan.mesh,
                worker_axes=plan.worker_axes, model_axis=plan.model_axis,
                **params)
        if plan.backend == "mesh":
            # the store is threaded INTO the backend: a miss there runs
            # the on-mesh sharded mesh_prepare (no host factorization)
            # and inserts the result, so hits flow both ways
            from . import mesh as mesh_backend
            return mesh_backend.solve_mesh(
                self, sys, mesh=plan.mesh, iters=iters, tol=tol,
                worker_axes=plan.worker_axes, model_axis=plan.model_axis,
                warm_state=plan.warm_state, factors=plan.factors,
                store=plan.store, use_kernel=plan.kernel,
                precision=plan.precision, **params)
        use_kernel, precision = plan.kernel, plan.precision
        warm_state, factors, store = plan.warm_state, plan.factors, plan.store
        prm = self.resolve_params(sys, **params)
        if factors is None:
            if store is not None:
                factors = store.factors(self, sys, use_kernel=use_kernel,
                                        resume=resume, precision=precision,
                                        **prm)
            else:
                if resume:
                    # a warm-start resume silently repaying the full
                    # b-independent prepare is the cost a FactorStore
                    # exists to amortize — make it visible
                    log.info(
                        "solve(warm_state=...) without cached factors: "
                        "re-running the full prepare for %r (pass store= "
                        "to count and amortize this as a cache miss)",
                        self.name)
                factors = self.prepare(sys.A_op, prm)
        if use_kernel:
            factors = self.kernel_factors(factors)
        if precision != "default":
            factors = self.cast_factors(factors, precision)   # idempotent
        state = (self.init(factors, sys.b_blocks, prm)
                 if warm_state is None else warm_state)
        step = lambda f, b, s: self.step(f, b, s, prm, use_kernel=use_kernel)
        residual_fn = self._ls_residual_fn(sys, factors, prm)
        xt = sys.x_true
        if xt is None and sys.mode == "least_squares":
            xt = jnp.asarray(self.ls_reference(sys))
        step_res = None
        if (use_kernel and self.supports_fused_residual
                and residual_fn is None and iters > 0):
            step_res = lambda f, b, s: self.step_residual(f, b, s, prm)
        state, res, err = _history_scan(step, self.extract, factors,
                                        sys.b_blocks, state, sys.A_op,
                                        xt, iters, residual_fn=residual_fn,
                                        step_residual=step_res)
        return SolveResult(
            name=self.name, x=self.extract(state), state=state, residuals=res,
            errors=err if xt is not None else None, params=prm,
            iters_to_tol=iters_to_tolerance(res, tol), tol=tol)

    def _ls_residual_fn(self, sys: BlockSystem, factors: Any,
                        prm: Dict[str, float]):
        """The LS-mode residual closure for the local scan drivers, or
        None in square mode (the plain ``||Ax-b||/||b||`` path)."""
        if sys.mode != "least_squares":
            return None
        A_op, ctx = sys.A_op, LOCAL_PSUM
        zero = jnp.zeros(sys.n, sys.b_blocks.dtype)

        def optim(b, x):
            mom = self.ls_moment(factors, A_op, b, x, prm, ctx)
            return jnp.sqrt(jnp.sum(mom * mom))

        def residual_fn(b, x):
            return optim(b, x) / optim(b, zero)

        return residual_fn

    def solve_many(self, sys: BlockSystem, B, *, iters: int = 1000,
                   tol: float = 1e-6,
                   plan: Optional[ExecutionPlan] = None,
                   use_kernel: Any = _UNSET, precision: Any = _UNSET,
                   factors: Any = _UNSET, store: Any = _UNSET,
                   backend: Any = _UNSET,
                   mesh: Any = _UNSET, worker_axes: Any = _UNSET,
                   model_axis: Any = _UNSET,
                   redundancy: Any = _UNSET, alive_schedule: Any = _UNSET,
                   **params) -> SolveResult:
        """Batched multi-RHS solve sharing ONE ``prepare`` factorization.

        ``B`` is (k, N) — k right-hand sides for the same A.  Returns a
        batched SolveResult: x (k, n), residuals (k, T), errors None.
        The ``plan=`` surface behaves as in ``solve`` (redundancy is
        rejected at plan resolution — run redundant solves per RHS); the
        loose kwargs are the same deprecated shim.
        """
        plan = _coerce_plan(plan, dict(
            use_kernel=use_kernel, precision=precision, factors=factors,
            store=store, backend=backend, mesh=mesh,
            worker_axes=worker_axes, model_axis=model_axis,
            redundancy=redundancy, alive_schedule=alive_schedule),
            context="solve_many")
        plan = resolve_plan(self, sys, plan, context="solve_many")
        if plan.backend == "mesh":
            from . import mesh as mesh_backend
            return mesh_backend.solve_many_mesh(
                self, sys, B, mesh=plan.mesh, iters=iters, tol=tol,
                worker_axes=plan.worker_axes, model_axis=plan.model_axis,
                factors=plan.factors, store=plan.store,
                use_kernel=plan.kernel, precision=plan.precision,
                **params)
        use_kernel, precision = plan.kernel, plan.precision
        factors, store = plan.factors, plan.store
        B = jnp.asarray(B)
        if B.ndim == 1:
            B = B[None, :]
        if B.shape[-1] != sys.N:
            raise ValueError(f"RHS batch has {B.shape[-1]} rows, need N={sys.N}")
        k = B.shape[0]
        Bb = B.reshape(k, sys.m, sys.p)
        prm = self.resolve_params(sys, **params)
        if factors is None:
            if store is not None:
                factors = store.factors(self, sys, use_kernel=use_kernel,
                                        precision=precision, **prm)
            else:
                factors = self.prepare(sys.A_op, prm)  # once, shared
        if use_kernel:
            factors = self.kernel_factors(factors)
        if precision != "default":
            factors = self.cast_factors(factors, precision)   # idempotent
        states = jax.vmap(lambda b: self.init(factors, b, prm))(Bb)
        step_many = lambda f, bb, sts: self.step_many(
            f, bb, sts, prm, use_kernel=use_kernel)
        residual_fn = self._ls_residual_fn(sys, factors, prm)
        step_many_res = None
        if (use_kernel and self.supports_fused_residual
                and residual_fn is None and iters > 0):
            step_many_res = lambda f, bb, sts: self.step_many_residual(
                f, bb, sts, prm)
        states, res = _history_scan_many(
            step_many, self.extract, factors, Bb, states, sys.A_op, iters,
            residual_fn=residual_fn, step_many_residual=step_many_res)
        X = jax.vmap(self.extract)(states)
        return SolveResult(
            name=self.name, x=X, state=states, residuals=res, errors=None,
            params=prm, iters_to_tol=iters_to_tolerance(res, tol), tol=tol)


# ---------------------------------------------------------------------------
# Shared jitted history drivers
# ---------------------------------------------------------------------------


def _history_scan(step, extract, factors, b, state, A, x_true, iters: int,
                  residual_fn=None, step_residual=None):
    """Scan ``step`` for ``iters`` iterations recording residual/error.

    ``A`` is either the dense (m, p, n) stack or a ``SparseBlocks``
    operand; the dense matvec is the identical einsum the driver always
    used, so dense histories are bit-exact.  ``residual_fn(b, x)``
    (LS mode) replaces the plain ``||Ax-b||/||b||`` history.

    ``step_residual(factors, b, state) -> (state, rsq)`` switches to the
    FUSED residual: each step harvests ‖Ax−b‖² of the state it consumed
    from its own gather pass, so the history costs no second full read of
    A per iteration.  The lagged records are shifted by one and the
    history closes with ONE true-A residual of the final state — same
    indexing as the plain path (entry t = residual after step t+1).
    """
    b_norm = jnp.sqrt(jnp.sum(b * b))
    xt = x_true
    xt_norm = None if xt is None else jnp.linalg.norm(xt)

    if step_residual is not None:
        def body(state, _):
            state, rsq = step_residual(factors, b, state)
            res = jnp.sqrt(rsq) / b_norm
            x = extract(state)
            err = (jnp.linalg.norm(x - xt) / xt_norm) if xt is not None \
                else res
            return state, (res, err)

        state, (res, err) = jax.lax.scan(body, state, None, length=iters)
        r = blockops.bmatvec(A, extract(state)) - b
        final = jnp.sqrt(jnp.sum(r * r)) / b_norm
        res = jnp.concatenate([res[1:], final[None]])
        if xt is None:
            err = res          # error channel aliases the shifted history
        return state, res, err

    def body(state, _):
        state = step(factors, b, state)
        x = extract(state)
        if residual_fn is None:
            r = blockops.bmatvec(A, x) - b
            res = jnp.sqrt(jnp.sum(r * r)) / b_norm
        else:
            res = residual_fn(b, x)
        err = (jnp.linalg.norm(x - xt) / xt_norm) if xt is not None else res
        return state, (res, err)

    state, (res, err) = jax.lax.scan(body, state, None, length=iters)
    return state, res, err


def _history_scan_many(step_many, extract, factors, Bb, states, A,
                       iters: int, residual_fn=None,
                       step_many_residual=None):
    """Batched variant: states/Bb carry a leading (k,) RHS axis.

    ``step_many`` is the solver's batched iteration — a vmap of ``step``
    by default, the fused multi-RHS kernel path for the projection family
    under ``use_kernel=True``.  ``residual_fn(b, x)`` is the per-RHS LS
    residual; it is vmapped over the batch.  ``step_many_residual`` is the
    batched fused-residual variant (same lagged-shift contract as
    ``_history_scan``).
    """
    b_norms = jnp.sqrt(jnp.sum(Bb * Bb, axis=(1, 2)))

    if step_many_residual is not None:
        def body(states, _):
            states, rsq = step_many_residual(factors, Bb, states)
            return states, jnp.sqrt(rsq) / b_norms         # (k,)

        states, res = jax.lax.scan(body, states, None, length=iters)
        X = jax.vmap(extract)(states)
        with jax.named_scope("residual"):
            r = blockops.bmatvec_many(A, X) - Bb
            final = jnp.sqrt(jnp.sum(r * r, axis=(1, 2))) / b_norms
        res = jnp.concatenate([res[1:], final[None]], axis=0)
        return states, res.T                               # (k, T)

    def body(states, _):
        states = step_many(factors, Bb, states)
        X = jax.vmap(extract)(states)                      # (k, n)
        with jax.named_scope("residual"):
            if residual_fn is None:
                r = blockops.bmatvec_many(A, X) - Bb
                res = jnp.sqrt(jnp.sum(r * r, axis=(1, 2))) / b_norms
            else:
                res = jax.vmap(residual_fn)(Bb, X)
        return states, res

    states, res = jax.lax.scan(body, states, None, length=iters)
    return states, res.T                                   # (k, T)
