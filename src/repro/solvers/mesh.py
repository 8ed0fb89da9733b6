"""Mesh execution backend: any registered solver, sharded via shard_map.

Every solver in the registry runs distributed on a device mesh through the
same lifecycle it uses on a single host:

    from repro import solvers
    res = solvers.get("dhbm").solve(sys, backend="mesh", mesh=mesh)

Mapping of the paper's roles onto the mesh (generalizing the APC-only
runtime that used to live in ``core/distributed.py``):

  * worker i   -> a slice of the ``data`` mesh axis (the m row blocks shard
                  over one or more ``worker_axes``).
  * taskmaster -> no physical node; every master update is a ``psum`` over
                  the worker axes (mean of x_i for the projection family and
                  M-ADMM, sum of partial gradients A_i^T(A_i x - b_i) for
                  the gradient family, sum of row projections for Cimmino).
  * columns    -> optionally sharded along ``model`` so a (p, n) block with
                  n ~ 10^6+ fits per-device memory; worker-local GEMVs then
                  need one extra p-sized psum over ``model``.

Setup is on-mesh: ``mesh_prepare`` (Gram Cholesky, preconditioners) and
``mesh_init`` run under shard_map, so no host ever materializes the full A.
States use GLOBAL shapes and the same pytree structure as the single-host
path — warm starts and ``repro.checkpoint.ckpt`` round-trip freely between
backends.

Per-solver code lives in the ``mesh_*`` hooks on each Solver subclass (see
``api.Solver``); this module owns placement, the jitted scan with
per-iteration residual/error history, and the unified ``SolveResult``.
"""
from __future__ import annotations

import dataclasses
from typing import Any, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import blockops
from repro.core.partition import BlockSystem

from .api import SolveResult, iters_to_tolerance
from .capability import check_capability, resolve_use_kernel


@dataclasses.dataclass(frozen=True)
class MeshContext:
    """Collective helpers handed to every ``mesh_*`` solver hook.

    ``w`` / ``n`` are the PartitionSpec entries for the worker and column
    (model) dimensions; the ``psum_*`` helpers are the only collectives a
    solver ever needs (the taskmaster is a psum, never a device).
    """
    mesh: Mesh
    worker_axes: Tuple[str, ...]
    model_axis: Optional[str]

    @property
    def w(self):
        """Spec entry for the worker-sharded leading axis."""
        return (self.worker_axes if len(self.worker_axes) > 1
                else self.worker_axes[0])

    @property
    def n(self) -> Optional[str]:
        """Spec entry for the column-sharded n axis (None = replicated)."""
        return self.model_axis

    def psum_workers(self, v):
        """Sum over every worker axis (the Eq. 2b 'taskmaster' reduction)."""
        return jax.lax.psum(v, self.worker_axes)

    def psum_model(self, v):
        """Sum over the column shards (no-op when n is not sharded)."""
        if self.model_axis is None:
            return v
        return jax.lax.psum(v, self.model_axis)

    def workers_total(self, m_local: int) -> int:
        """Global worker count m from a local shard's leading axis."""
        for ax in self.worker_axes:
            m_local = m_local * self.mesh.shape[ax]
        return m_local


def make_context(mesh: Mesh, sys: BlockSystem, *,
                 worker_axes: Sequence[str] = ("data",),
                 model_axis: Optional[str] = "model") -> MeshContext:
    """Validate mesh axes against the system and build a MeshContext.

    Axes the mesh does not have are dropped rather than rejected — the
    defaults name the production axes, and a smaller mesh (e.g. a 1-axis
    test mesh without "model") simply runs unsharded along the missing
    dimension.  Mind the consequence: a misspelled axis name degrades to
    replication silently, so double-check names against mesh.axis_names
    when a solve does not scale the way the mesh shape says it should.
    """
    worker_axes = tuple(a for a in worker_axes if a in mesh.axis_names)
    if not worker_axes:
        raise ValueError(f"mesh {mesh.axis_names} has none of the requested "
                         f"worker axes")
    if model_axis is not None and model_axis not in mesh.axis_names:
        model_axis = None
    if sys.is_sparse:
        # sparse column indices address the GLOBAL n axis, so sparse
        # systems shard over worker axes only (blocks are already
        # column-compressed; a model shard would re-split the support)
        model_axis = None
    ctx = MeshContext(mesh=mesh, worker_axes=worker_axes,
                      model_axis=model_axis)
    wsize = ctx.workers_total(1)
    if sys.m % wsize:
        raise ValueError(f"worker axes {worker_axes} have {wsize} shards, "
                         f"which does not divide m={sys.m}")
    nsize = mesh.shape[model_axis] if model_axis is not None else 1
    if sys.n % nsize:
        raise ValueError(f"model axis {model_axis!r} has {nsize} shards, "
                         f"which does not divide n={sys.n}")
    return ctx


def residual_shard(A, b, x, b_norm, ctx: MeshContext):
    """Relative residual ||Ax-b||/||b|| from local shards (replicated out)."""
    r = ctx.psum_model(blockops.bmatvec(A, x)) - b
    return jnp.sqrt(ctx.psum_workers(jnp.sum(r * r))) / b_norm


def operand_specs(sys: BlockSystem, ctx: MeshContext):
    """PartitionSpec (pytree) for ``sys.A_op``: a single spec for the dense
    stack, a matching ``SparseBlocks`` of specs for sparse operands."""
    if sys.is_sparse:
        return blockops.SparseBlocks(vals=P(ctx.w, None, None),
                                     cols=P(ctx.w, None), span=P(None))
    return P(ctx.w, None, ctx.n)


def _patch_factor_specs(fspecs, a_spec):
    """Swap a sparse operand spec into a factor pytree's ``A`` field."""
    if blockops.is_sparse(a_spec) and hasattr(fspecs, "_replace") \
            and "A" in getattr(fspecs, "_fields", ()):
        return fspecs._replace(A=a_spec)
    return fspecs


def _default_mesh(workers: int) -> Mesh:
    from repro.launch import mesh as mesh_lib
    return mesh_lib.solver_mesh_for(workers)


def _put_tree(tree: Any, specs: Any, mesh: Mesh) -> Any:
    """device_put every leaf with its NamedSharding (global shapes in)."""
    return jax.tree.map(
        lambda x, s: jax.device_put(jnp.asarray(x), NamedSharding(mesh, s)),
        tree, specs)


def _batched_specs(specs: Any) -> Any:
    """Prepend a replicated RHS-batch dimension to every state spec."""
    return jax.tree.map(lambda s: P(*((None,) + tuple(s))), specs,
                        is_leaf=lambda s: isinstance(s, P))


def _factor_specs(solver, ctx: MeshContext, use_kernel: bool):
    """Factor specs, with the kernel-path augmentation (pinv factors)
    included when requested.  ``use_kernel`` only reaches solvers with
    ``supports_kernel``, whose specs hook takes the kwarg."""
    if use_kernel:
        return solver.mesh_factor_specs(ctx, use_kernel=True)
    return solver.mesh_factor_specs(ctx)


def _host_factors(solver, factors, use_kernel: bool):
    """Host-side factor normalization before placement: strip host-only
    fields, or (kernel path) idempotently ensure the pinv augmentation."""
    if use_kernel:
        return solver.mesh_factors(factors, use_kernel=True)
    return solver.mesh_factors(factors)


def _place(solver, sys: BlockSystem, ctx: MeshContext, prm, factors,
           store=None, resume: bool = False, use_kernel: bool = False,
           precision: str = "default"):
    """Shard A/b, run on-mesh prepare (unless factors are given).

    With a ``store``, the ``factors is None`` branch becomes a cache
    lookup; a MISS still runs the on-mesh sharded ``mesh_prepare`` (no
    host ever factorizes the full A) and the result is inserted back, so
    later solves — either backend — hit it.  An entry therefore holds
    whichever mathematically-equivalent factorization first populated it
    (host or on-mesh prepare; for most solvers they are bit-identical).

    ``use_kernel=True`` keeps the pinv factors: a store hit is augmented
    ONCE and written back (``lookup(use_kernel=True)``), an on-mesh miss
    computes them shard-locally inside ``mesh_prepare`` and the inserted
    entry carries them, so later kernel solves on either backend never
    re-run the augmentation.
    """
    mesh = ctx.mesh
    A_spec, b_spec = operand_specs(sys, ctx), P(ctx.w, None)
    fspecs = _patch_factor_specs(_factor_specs(solver, ctx, use_kernel),
                                 A_spec)
    A = _put_tree(sys.A_op, A_spec, mesh)
    b = jax.device_put(sys.b_blocks, NamedSharding(mesh, b_spec))
    if factors is None and store is not None:
        factors = store.lookup(solver, sys, use_kernel=use_kernel,
                               precision=precision, **prm)
    if factors is None:
        prep_fn = ((lambda A_: solver.mesh_prepare(A_, prm, ctx,
                                                   use_kernel=True))
                   if use_kernel
                   else (lambda A_: solver.mesh_prepare(A_, prm, ctx)))
        prep = jax.jit(jax.shard_map(
            prep_fn, mesh=mesh, in_specs=(A_spec,), out_specs=fspecs))
        factors = prep(A)
        if store is not None:
            store.insert(solver, sys, factors, resume=resume,
                         use_kernel=use_kernel, precision=precision, **prm)
    else:
        factors = _put_tree(_host_factors(solver, factors, use_kernel),
                            fspecs, mesh)
    if precision != "default":
        # cast LAST: an elementwise astype preserves each leaf's sharding,
        # and cast_factors is idempotent for store-returned mixed entries
        factors = solver.cast_factors(factors, precision)
    return A, b, A_spec, b_spec, fspecs, factors


class CompiledSolve(NamedTuple):
    """A placed, compile-once mesh solve: call ``run(*args)`` repeatedly.

    ``run`` returns ``(state, residuals, errors)``; ``has_errors`` says
    whether the error channel is real (x_true given) or aliases the
    residuals.  Benchmarks time repeat executions of the SAME callable so
    trace/compile cost drops out; ``solve_mesh`` builds one per call.
    """
    run: Any
    args: Tuple
    params: dict
    has_errors: bool


def compile_solve(solver, sys: BlockSystem, *, mesh: Optional[Mesh] = None,
                  iters: int = 1000,
                  worker_axes: Sequence[str] = ("data",),
                  model_axis: Optional[str] = "model",
                  warm_state: Any = None, factors: Any = None,
                  store: Any = None, use_kernel: bool = False,
                  precision: str = "default",
                  **params) -> CompiledSolve:
    """Placement + on-mesh setup + the jitted scan, without executing it."""
    check_capability(solver, sys, context="solve(mesh)")
    use_kernel = resolve_use_kernel(solver, sys, use_kernel)
    solver._check_precision(precision, use_kernel)
    if mesh is None:
        mesh = _default_mesh(sys.m)
    ctx = make_context(mesh, sys, worker_axes=worker_axes,
                       model_axis=model_axis)
    prm = solver.resolve_params(sys, **params)
    A, b, A_spec, b_spec, fspecs, factors = _place(
        solver, sys, ctx, prm, factors, store=store,
        resume=warm_state is not None, use_kernel=use_kernel,
        precision=precision)
    sspecs = solver.mesh_state_specs(ctx)

    if warm_state is None:
        init_fn = jax.jit(jax.shard_map(
            lambda f, b_: solver.mesh_init(f, b_, prm, ctx), mesh=mesh,
            in_specs=(fspecs, b_spec), out_specs=sspecs))
        state = init_fn(factors, b)
    else:
        state = _put_tree(warm_state, sspecs, mesh)

    xt = sys.x_true
    if xt is None and sys.mode == "least_squares":
        xt = solver.ls_reference(sys)       # error channel vs the LS optimum
    args = (A, b, factors, state)
    in_specs = (A_spec, b_spec, fspecs, sspecs)
    if xt is not None:
        args += (jax.device_put(xt, NamedSharding(mesh, P(ctx.n))),)
        in_specs += (P(ctx.n),)

    step_fn = ((lambda f, b_, st: solver.mesh_step(f, b_, st, prm, ctx,
                                                   use_kernel=True))
               if use_kernel
               else (lambda f, b_, st: solver.mesh_step(f, b_, st, prm,
                                                        ctx)))
    ls_mode = sys.mode == "least_squares"
    fused_res = (use_kernel and solver.supports_fused_residual
                 and not ls_mode and iters > 0)

    def run_body(A_, b_, f_, s_, *rest):
        b_norm = jnp.sqrt(ctx.psum_workers(jnp.sum(b_ * b_)))
        xt_ = rest[0] if rest else None
        xt_norm = (jnp.sqrt(ctx.psum_model(jnp.sum(xt_ * xt_)))
                   if xt_ is not None else None)

        if ls_mode:
            # LS residual channel: ‖AᵀW(Ax−b)‖ relative to x = 0 — the
            # optimality moment of the solver's own LS objective
            def ls_norm(x):
                mom = solver.ls_moment(f_, A_, b_, x, prm, ctx)
                return jnp.sqrt(ctx.psum_model(jnp.sum(mom * mom)))

            ls_denom = ls_norm(jnp.zeros_like(solver.extract(s_)))

        if fused_res:
            # fused residual: every step harvests ‖Ax−b‖ of the state it
            # CONSUMED from its own gather pass; shift the lagged records
            # by one and close with a single true-A residual — no second
            # per-iteration read of A
            def body(st, _):
                st, rsq = solver.mesh_step_residual(f_, b_, st, prm, ctx)
                res = jnp.sqrt(rsq) / b_norm
                if xt_ is not None:
                    dx = solver.extract(st) - xt_
                    err = (jnp.sqrt(ctx.psum_model(jnp.sum(dx * dx)))
                           / xt_norm)
                else:
                    err = res
                return st, (res, err)

            s_, (res, err) = jax.lax.scan(body, s_, None, length=iters)
            final = residual_shard(A_, b_, solver.extract(s_), b_norm, ctx)
            res = jnp.concatenate([res[1:], final[None]])
            if xt_ is None:
                err = res
            return s_, res, err

        def body(st, _):
            st = step_fn(f_, b_, st)
            x = solver.extract(st)
            if ls_mode:
                res = ls_norm(x) / ls_denom
            else:
                res = residual_shard(A_, b_, x, b_norm, ctx)
            if xt_ is not None:
                dx = x - xt_
                err = jnp.sqrt(ctx.psum_model(jnp.sum(dx * dx))) / xt_norm
            else:
                err = res
            return st, (res, err)

        s_, (res, err) = jax.lax.scan(body, s_, None, length=iters)
        return s_, res, err

    # pallas_call has no shard_map replication rule — the kernel path
    # disables the check (the psum contract itself is unchanged)
    run = jax.jit(jax.shard_map(run_body, mesh=mesh, in_specs=in_specs,
                                out_specs=(sspecs, P(), P()),
                                check_vma=not use_kernel))
    return CompiledSolve(run=run, args=args, params=prm,
                         has_errors=xt is not None)


def solve_mesh(solver, sys: BlockSystem, *, mesh: Optional[Mesh] = None,
               iters: int = 1000, tol: float = 1e-6,
               worker_axes: Sequence[str] = ("data",),
               model_axis: Optional[str] = "model",
               warm_state: Any = None, factors: Any = None,
               store: Any = None, use_kernel: bool = False,
               precision: str = "default",
               **params) -> SolveResult:
    """Sharded ``solve``: the mesh twin of ``Solver.solve``.

    Returns the same ``SolveResult`` (full residual/error history,
    warm-startable state with global shapes) as the single-host driver.
    ``use_kernel=True`` (projection family) runs each worker shard's
    update through the Pallas kernels on its local (p × n_local) block.
    """
    cs = compile_solve(solver, sys, mesh=mesh, iters=iters,
                       worker_axes=worker_axes, model_axis=model_axis,
                       warm_state=warm_state, factors=factors, store=store,
                       use_kernel=use_kernel, precision=precision, **params)
    state, res, err = cs.run(*cs.args)
    return SolveResult(
        name=solver.name, x=solver.extract(state), state=state,
        residuals=res, errors=err if cs.has_errors else None,
        params=cs.params, iters_to_tol=iters_to_tolerance(res, tol), tol=tol)


class BatchedRunner(NamedTuple):
    """Compile-once batched executor for one (solver, params, mesh) config.

    ``init``/``run`` are jitted shard_map callables over PLACED arrays —
    calling them repeatedly with same-shape/same-sharding arguments never
    retraces, which is what lets ``solvers.serve.LinsysServer`` keep a
    steady-state serving loop at zero retraces.  ``cache_size()`` exposes
    the underlying jit caches so benchmarks can assert exactly that.
    """
    init: Any           # (factors, Bb)            -> states
    run: Any            # (A, Bb, factors, states) -> (states, X, res (k,T))
    A_spec: Any
    Bb_spec: Any
    factor_specs: Any
    state_specs: Any

    def cache_size(self) -> int:
        return self.init._cache_size() + self.run._cache_size()


def batched_runner(solver, ctx: MeshContext, prm, iters: int,
                   use_kernel: bool = False, *, a_spec: Any = None,
                   ls_mode: bool = False,
                   fused_residual: bool = False) -> BatchedRunner:
    """Build the jitted multi-RHS init/run pair shared by ``solve_many_mesh``
    and the serving layer.  Nothing system-specific is baked in beyond the
    params and the mesh context: A / b / factors / states are arguments, so
    one runner serves every same-shape system.  ``use_kernel=True`` routes
    the batched step through ``mesh_step_many``'s fused multi-RHS Pallas
    path (projection family).  ``a_spec`` overrides the operand spec (a
    ``SparseBlocks`` spec pytree for sparse systems, see ``operand_specs``);
    ``ls_mode`` switches the residual channel to the per-RHS LS optimality
    moment; ``fused_residual`` (kernel path, square mode) harvests the
    per-iteration history from the gather pass instead of a second full
    read of A (lagged-shift contract, see ``api._history_scan``)."""
    mesh = ctx.mesh
    if a_spec is None:
        a_spec = P(ctx.w, None, ctx.n)
    A_spec, Bb_spec = a_spec, P(None, ctx.w, None)
    fspecs = _patch_factor_specs(_factor_specs(solver, ctx, use_kernel),
                                 A_spec)
    sspecs = _batched_specs(solver.mesh_state_specs(ctx))
    fused_residual = (fused_residual and use_kernel and not ls_mode
                      and iters > 0 and solver.supports_fused_residual)

    init_fn = jax.jit(jax.shard_map(
        lambda f, Bb_: jax.vmap(
            lambda bb: solver.mesh_init(f, bb, prm, ctx))(Bb_),
        mesh=mesh, in_specs=(fspecs, Bb_spec), out_specs=sspecs))

    def run_body(A_, Bb_, f_, s_):
        b_norms = jnp.sqrt(ctx.psum_workers(jnp.sum(Bb_ * Bb_, axis=(1, 2))))

        def vstep(Bb__, sts):
            return solver.mesh_step_many(f_, Bb__, sts, prm, ctx,
                                         use_kernel=use_kernel)

        if ls_mode:
            def ls_norm(bb, x):
                mom = solver.ls_moment(f_, A_, bb, x, prm, ctx)
                return jnp.sqrt(ctx.psum_model(jnp.sum(mom * mom)))

            X0 = jax.vmap(solver.extract)(s_)
            ls_denoms = jax.vmap(ls_norm)(Bb_, jnp.zeros_like(X0))

        if fused_residual:
            def body(sts, _):
                sts, rsq = solver.mesh_step_many_residual(f_, Bb_, sts,
                                                          prm, ctx)
                return sts, jnp.sqrt(rsq) / b_norms           # (k,)

            s_, res = jax.lax.scan(body, s_, None, length=iters)
            X = jax.vmap(solver.extract)(s_)
            r = ctx.psum_model(blockops.bmatvec_many(A_, X)) - Bb_
            final = jnp.sqrt(
                ctx.psum_workers(jnp.sum(r * r, axis=(1, 2)))) / b_norms
            res = jnp.concatenate([res[1:], final[None]], axis=0)
            return s_, X, res.T                               # (k, T)

        def body(sts, _):
            sts = vstep(Bb_, sts)
            X = jax.vmap(solver.extract)(sts)                  # (k, n_loc)
            if ls_mode:
                res = jax.vmap(ls_norm)(Bb_, X) / ls_denoms
            else:
                r = ctx.psum_model(blockops.bmatvec_many(A_, X)) - Bb_
                res = jnp.sqrt(
                    ctx.psum_workers(jnp.sum(r * r, axis=(1, 2)))) / b_norms
            return sts, res

        s_, res = jax.lax.scan(body, s_, None, length=iters)
        return s_, jax.vmap(solver.extract)(s_), res.T         # (k, T)

    run = jax.jit(jax.shard_map(run_body, mesh=mesh,
                                in_specs=(A_spec, Bb_spec, fspecs, sspecs),
                                out_specs=(sspecs, P(None, ctx.n), P()),
                                check_vma=not use_kernel))
    return BatchedRunner(init=init_fn, run=run, A_spec=A_spec,
                         Bb_spec=Bb_spec, factor_specs=fspecs,
                         state_specs=sspecs)


def solve_many_mesh(solver, sys: BlockSystem, B, *,
                    mesh: Optional[Mesh] = None, iters: int = 1000,
                    tol: float = 1e-6,
                    worker_axes: Sequence[str] = ("data",),
                    model_axis: Optional[str] = "model",
                    factors: Any = None, store: Any = None,
                    use_kernel: bool = False, precision: str = "default",
                    **params) -> SolveResult:
    """Sharded multi-RHS solve: one on-mesh factorization, k right-hand
    sides batched inside the shard_map body (batch axis replicated) — the
    fused multi-RHS kernels under ``use_kernel=True``."""
    check_capability(solver, sys, context="solve_many(mesh)")
    use_kernel = resolve_use_kernel(solver, sys, use_kernel)
    solver._check_precision(precision, use_kernel)
    if mesh is None:
        mesh = _default_mesh(sys.m)
    ctx = make_context(mesh, sys, worker_axes=worker_axes,
                       model_axis=model_axis)
    B = jnp.asarray(B)
    if B.ndim == 1:
        B = B[None, :]
    if B.shape[-1] != sys.N:
        raise ValueError(f"RHS batch has {B.shape[-1]} rows, need N={sys.N}")
    k = B.shape[0]
    prm = solver.resolve_params(sys, **params)
    A, _, _, _, _, factors = _place(solver, sys, ctx, prm, factors,
                                    store=store, use_kernel=use_kernel,
                                    precision=precision)
    runner = batched_runner(solver, ctx, prm, iters, use_kernel=use_kernel,
                            a_spec=operand_specs(sys, ctx),
                            ls_mode=sys.mode == "least_squares",
                            fused_residual=use_kernel)

    Bb = jax.device_put(B.reshape(k, sys.m, sys.p),
                        NamedSharding(mesh, runner.Bb_spec))
    states = runner.init(factors, Bb)
    states, X, res = runner.run(A, Bb, factors, states)
    return SolveResult(
        name=solver.name, x=X, state=states, residuals=res, errors=None,
        params=prm, iters_to_tol=iters_to_tolerance(res, tol), tol=tol)


class RedundantRunner:
    """Compile-once mesh runner for the r-redundant scan.

    Built by ``redundant.RedundantEngine`` on ``backend="mesh"``: all
    placement and both jits (on-mesh replicated prepare/init plus the
    segment scan) are constructed ONCE here, and ``run`` re-enters the
    SAME compiled shard_map scan with a freshly lowered selection-weight
    schedule of identical shape.  A membership change that keeps the
    partition (a worker death under r-redundancy) therefore costs a
    schedule re-lowering, never a retrace — the property the elastic
    runtime's benchmarks gate on.
    """

    def __init__(self, solver, sys: BlockSystem, assign, prm, *,
                 mesh: Optional[Mesh] = None,
                 worker_axes: Sequence[str] = ("data",),
                 model_axis: Optional[str] = "model",
                 factors: Any = None):
        from . import redundant as red  # lazy: redundant.py imports us

        if mesh is None:
            mesh = _default_mesh(sys.m)
        ctx = make_context(mesh, sys, worker_axes=worker_axes,
                           model_axis=model_axis)
        self.solver, self.assign = solver, assign
        self.mesh, self.ctx, self.prm = mesh, ctx, prm
        A_spec, b_spec = P(ctx.w, None, ctx.n), P(ctx.w, None)
        Arep_spec = P(ctx.w, None, None, ctx.n)
        brep_spec = P(ctx.w, None, None)
        self._W_spec, self._Wseq_spec = P(ctx.w, None), P(None, ctx.w, None)
        fspecs = solver.red_factor_specs(ctx)
        self._sspecs = sspecs = solver.red_state_specs(ctx)

        put = lambda v, s: jax.device_put(v, NamedSharding(mesh, s))
        A_rep, b_rep = red.replicate_system(sys, assign)
        self._A, self._b = put(sys.A_blocks, A_spec), put(sys.b_blocks, b_spec)
        A_rep, self._b_rep = put(A_rep, Arep_spec), put(b_rep, brep_spec)

        if factors is None:
            prep = jax.jit(jax.shard_map(
                lambda Ar: red._red_mesh_prepare(solver, Ar, prm, ctx),
                mesh=mesh, in_specs=(Arep_spec,), out_specs=fspecs))
            self._frep = prep(A_rep)
        else:
            self._frep = _put_tree(
                solver.red_factors(solver.mesh_factors(factors), assign),
                fspecs, mesh)

        self._init = jax.jit(jax.shard_map(
            lambda f, br, W0: solver.red_init(f, br, prm, W0, ctx),
            mesh=mesh, in_specs=(fspecs, brep_spec, self._W_spec),
            out_specs=sspecs))

        xt = sys.x_true
        self._xt = () if xt is None else (put(xt, P(ctx.n)),)
        in_specs = (A_spec, b_spec, brep_spec, fspecs, sspecs,
                    self._Wseq_spec)
        if xt is not None:
            in_specs += (P(ctx.n),)

        def run_body(A_, b_, br_, f_, s_, Ws_, *rest):
            b_norm = jnp.sqrt(ctx.psum_workers(jnp.sum(b_ * b_)))
            xt_ = rest[0] if rest else None
            xt_norm = (jnp.sqrt(ctx.psum_model(jnp.sum(xt_ * xt_)))
                       if xt_ is not None else None)

            def body(st, Wt):
                st = solver.red_step(f_, br_, st, prm, Wt, ctx)
                x = solver.extract(st)
                res = residual_shard(A_, b_, x, b_norm, ctx)
                if xt_ is not None:
                    dx = x - xt_
                    err = jnp.sqrt(ctx.psum_model(jnp.sum(dx * dx))) / xt_norm
                else:
                    err = res
                return st, (res, err)

            s_, (res, err) = jax.lax.scan(body, s_, Ws_)
            return s_, res, err

        self._run = jax.jit(jax.shard_map(run_body, mesh=mesh,
                                          in_specs=in_specs,
                                          out_specs=(sspecs, P(), P())))

    def init_state(self, warm_state, W_all):
        """Fresh ``red_init`` (warm_state None) or a placed ``red_expand``
        of a GLOBAL-shape warm state."""
        if warm_state is None:
            W_all = jax.device_put(W_all,
                                   NamedSharding(self.mesh, self._W_spec))
            return self._init(self._frep, self._b_rep, W_all)
        return _put_tree(self.solver.red_expand(warm_state, self.assign),
                         self._sspecs, self.mesh)

    def run(self, state, W_seq):
        """One segment: re-enters the compiled scan with a new schedule."""
        W_seq = jax.device_put(W_seq,
                               NamedSharding(self.mesh, self._Wseq_spec))
        return self._run(self._A, self._b, self._b_rep, self._frep, state,
                         W_seq, *self._xt)

    def cache_size(self) -> int:
        return self._init._cache_size() + self._run._cache_size()
