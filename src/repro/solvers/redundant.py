"""Redundant, straggler-tolerant execution for the projection family.

The paper's synchronous taskmaster waits for *all* m machines every
iteration — one straggler stalls the fleet.  This backend lowers the same
prepare/init/step lifecycle through an r-redundant cyclic block assignment
in the style of gradient coding [20]: worker i holds blocks
{i, i+1, ..., i+r-1 mod m}, so any iteration can be completed from the
responses of workers whose union of blocks covers {0..m-1}; with
r-redundancy, ANY m - r + 1 workers suffice.

    from repro import solvers
    res = solvers.get("apc").solve(sys, redundancy=2,
                                   alive_schedule=lambda t: mask_t)

``alive_schedule`` may be a callable ``t -> (m,) bool mask``, a static
``(m,)`` or per-iteration ``(iters, m)`` mask array, or a
``runtime.fault.HeartbeatMonitor``.  The whole schedule is lowered to
selection weights ONCE, before the scan launches — a monitor is therefore
a launch-time snapshot (``drop_set()`` queried per iteration index, but
with no solve running in between); drive a long-lived deployment in
warm-started segments to re-sample it.

The master's Eq. (2b) average needs each block's x_j exactly once.  Given
the alive-mask a ∈ {0,1}^m we pick for each block j its lowest-index alive
holder (deterministic, no communication needed — the mask is broadcast with
the heartbeat), expressed as a weight matrix W(a) ∈ {0,1}^{m x r} so the
masked block-unique mean stays a single reduction: locally an einsum inside
one jitted ``lax.scan`` over the precomputed per-iteration weights, on
``backend="mesh"`` the SAME psum over the worker axes that the mesh
contract already uses for the no-failure master update.

Semantics are EXACT, not approximate: an iteration under any covering
alive-mask computes the same x̄(t+1) as a non-redundant iteration over all
m blocks, because each block's update x_j(t+1) only depends on
(x_j(t), x̄(t)) — every replica of block j holds an identical copy of
x_j(t).  (Replicas apply identical deterministic updates from identical
inputs, so they never diverge while alive; a worker that *rejoins* must
refresh its replicas from a live holder — ``HeartbeatMonitor.rejoin``
models that handshake.)  Exactness is also what keeps states GLOBAL-shaped:
the replicated internal state is a pure gather of the plain one, so warm
starts and ``repro.checkpoint`` round-trip freely between redundant/plain
runs and local/mesh backends.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.partition import BlockSystem

from .api import SolveResult, iters_to_tolerance


@dataclasses.dataclass(frozen=True)
class Assignment:
    """Cyclic r-redundant block assignment over m workers."""
    m: int
    r: int

    @property
    def holder(self) -> np.ndarray:
        """(m, r) block id held in slot k of worker i: (i + k) mod m."""
        return (np.arange(self.m)[:, None] + np.arange(self.r)[None, :]) \
            % self.m


class _LocalContext:
    """Degenerate MeshContext twin: the whole fleet is one host, so the
    worker/model psums are identities.  Lets every ``red_*`` solver hook be
    written ONCE against the psum contract and run on both backends."""

    def psum_workers(self, v):
        return v

    def psum_model(self, v):
        return v

    def workers_total(self, m_local: int) -> int:
        return m_local


_LOCAL = _LocalContext()


def schedule_weights(alive: np.ndarray, r: int) -> np.ndarray:
    """Lower a (T, m) alive schedule to (T, m, r) selection-weight masks.

    W[t, i, k] = 1 iff worker i is the designated provider of the block in
    its slot k at iteration t; provider = lowest-index alive holder (ties
    broken by slot), so each block contributes exactly once to the masked
    mean.  Vectorized over T — the whole schedule is precomputed host-side
    and scanned over, nothing per-iteration runs in Python.

    Raises if some block has no alive holder (the fleet lost >= r
    cyclically-adjacent workers); the runtime then falls back to a full
    re-partition (runtime/fault.py).
    """
    alive = np.atleast_2d(np.asarray(alive, dtype=bool))
    T, m = alive.shape
    ks = np.arange(r)
    # block j's slot-k holder is worker (j - k) mod m
    holders = (np.arange(m)[:, None] - ks[None, :]) % m          # (m, r)
    ok = alive[:, holders]                                       # (T, m, r)
    # lexicographic (worker, slot) preference key; +inf-like when dead
    key = np.where(ok, holders * r + ks[None, :], m * r)
    sel = key.argmin(axis=-1)                                    # (T, m)
    covered = np.take_along_axis(ok, sel[..., None], axis=-1)[..., 0]
    if not covered.all():
        t, blk = np.argwhere(~covered)[0]
        raise RuntimeError(
            f"block {blk} unrecoverable at iteration {t}: no alive holder "
            f"(r={r}; lost >= {r} cyclically-adjacent workers)")
    i_sel = (np.arange(m)[None, :] - sel) % m                    # (T, m)
    W = np.zeros((T, m, r))
    W[np.repeat(np.arange(T), m), i_sel.ravel(), sel.ravel()] = 1.0
    return W


def selection_weights(alive: np.ndarray, m: int, r: int) -> np.ndarray:
    """Single-mask form of ``schedule_weights`` (W ∈ {0,1}^{m x r})."""
    alive = np.asarray(alive, dtype=bool).reshape(1, m)
    return schedule_weights(alive, r)[0]


def monitor_schedule(monitor) -> Any:
    """Adapt a ``runtime.fault.HeartbeatMonitor`` into an alive schedule
    excluding its ``drop_set()`` (dead OR straggling workers).  NOTE: the
    schedule is lowered before the scan launches, so this is a launch-time
    snapshot — re-lower (e.g. warm-started solve segments) to track a
    fleet whose health changes mid-run."""
    return lambda t: ~monitor.drop_set()


def resolve_schedule(alive_schedule, m: int, iters: int) -> np.ndarray:
    """Normalize any accepted alive-schedule form to a (iters, m) array."""
    if alive_schedule is None:
        return np.ones((iters, m), dtype=bool)
    from repro.runtime.fault import HeartbeatMonitor
    if isinstance(alive_schedule, HeartbeatMonitor):
        if alive_schedule.n_workers != m:
            raise ValueError(
                f"HeartbeatMonitor tracks {alive_schedule.n_workers} "
                f"workers but the system has m={m} blocks")
        alive_schedule = monitor_schedule(alive_schedule)
    if callable(alive_schedule):
        masks = [np.asarray(alive_schedule(t), dtype=bool)
                 for t in range(iters)]
        alive = np.stack(masks) if masks else np.ones((0, m), bool)
    else:
        alive = np.asarray(alive_schedule, dtype=bool)
        if alive.ndim == 1:
            alive = np.broadcast_to(alive, (iters, m)).copy()
    if alive.shape != (iters, m):
        raise ValueError(f"alive schedule has shape {alive.shape}, "
                         f"need ({iters}, {m})")
    return alive


def replicate_system(sys: BlockSystem, assign: Assignment):
    """(A_rep, b_rep): A_rep[i, k] = A_blocks[(i + k) % m], likewise b."""
    idx = assign.holder
    return (jnp.asarray(sys.A_blocks)[idx], jnp.asarray(sys.b_blocks)[idx])


def _check_solver(solver, sys: BlockSystem, r: int):
    if not getattr(solver, "supports_redundancy", False):
        raise ValueError(
            f"solver {solver.name!r} does not support redundant execution "
            "(projection family only: the coded masked mean needs the "
            "block-local update structure of apc/consensus/cimmino)")
    if sys.is_sparse or sys.mode != "square":
        raise ValueError(
            f"redundant execution is dense-square only: got a "
            f"mode={sys.mode!r}, structure={sys.structure!r} system — the "
            f"replicated (m, r, p, n) factor layout has no sparse variant "
            f"and the straggler theory assumes a consistent system; "
            f"densify()/drop redundancy=r to proceed")
    if not (1 <= r <= sys.m):
        raise ValueError(f"redundancy r={r} must be in [1, m={sys.m}]")


class RedundantEngine:
    """Compile-once, re-enterable segment runner for redundant execution.

    An engine binds the FIXED part of a redundant solve — solver, system
    partition, r, resolved params, backend, mesh placement, replicated
    factors — and compiles the scan ONCE.  Segments then re-enter the
    SAME jitted computation with a new ``(state, W_seq)`` pair: as long
    as shapes match (same partition, same segment length), a membership
    change costs one host-side schedule re-lowering (``lower``) and zero
    retraces.  That is exactly the death path of
    ``solvers.elastic.ElasticRuntime``, which also caches one engine per
    partition signature so a rejoin to a previously-seen fleet size
    reuses the compiled scan too.

    ``solve_redundant`` is a thin wrapper over one engine + one segment,
    so every existing redundant test exercises this code path.
    """

    def __init__(self, solver, sys: BlockSystem, *, r: int,
                 backend: str = "local", mesh: Any = None,
                 worker_axes: Sequence[str] = ("data",),
                 model_axis: Optional[str] = "model",
                 factors: Any = None, **params):
        _check_solver(solver, sys, r)
        self.solver, self.sys = solver, sys
        self.r = int(r)
        self.assign = Assignment(m=sys.m, r=self.r)
        self.backend = backend
        self.prm = solver.resolve_params(sys, **params)
        self.dtype = jnp.asarray(sys.A_blocks).dtype
        self.W_all = jnp.asarray(
            selection_weights(np.ones(sys.m, bool), sys.m, self.r),
            dtype=self.dtype)
        if backend == "mesh":
            from . import mesh as mesh_backend
            self._mesh_runner = mesh_backend.RedundantRunner(
                solver, sys, self.assign, self.prm, mesh=mesh,
                worker_axes=worker_axes, model_axis=model_axis,
                factors=factors)
        else:
            self._mesh_runner = None
            if factors is None:
                factors = solver.prepare(sys.A_blocks, self.prm)
            # strip host-only fields (e.g. kernel pinv factors) before
            # replicating
            self._frep = solver.red_factors(solver.mesh_factors(factors),
                                            self.assign)
            _, self._b_rep = replicate_system(sys, self.assign)
            xt = sys.x_true
            self._xt = () if xt is None else (jnp.asarray(xt),)
            self._run = jax.jit(self._segment)

    def _segment(self, frep, b_rep, A, b, state, W_seq, *rest):
        solver, prm = self.solver, self.prm
        b_norm = jnp.sqrt(jnp.sum(b * b))
        xt = rest[0] if rest else None
        xt_norm = None if xt is None else jnp.linalg.norm(xt)

        def body(st, Wt):
            st = solver.red_step(frep, b_rep, st, prm, Wt, _LOCAL)
            x = solver.extract(st)
            rr = jnp.einsum("mpn,n->mp", A, x) - b
            res = jnp.sqrt(jnp.sum(rr * rr)) / b_norm
            err = (jnp.linalg.norm(x - xt) / xt_norm) if xt is not None \
                else res
            return st, (res, err)

        state, (res, err) = jax.lax.scan(body, state, W_seq)
        return state, res, err

    def lower(self, alive) -> jnp.ndarray:
        """(T, m) alive masks -> (T, m, r) selection weights.  Raises the
        loud ``unrecoverable`` RuntimeError if a block has no alive
        holder — the caller then repartitions or gives up."""
        return jnp.asarray(
            schedule_weights(np.asarray(alive, dtype=bool), self.r),
            dtype=self.dtype)

    def init_state(self, warm_state: Any = None):
        """Fresh ``red_init`` or a replicated expansion of a GLOBAL-shape
        warm state (any backend/redundancy produced it)."""
        if self._mesh_runner is not None:
            return self._mesh_runner.init_state(warm_state, self.W_all)
        if warm_state is None:
            return self.solver.red_init(self._frep, self._b_rep, self.prm,
                                        self.W_all, _LOCAL)
        return self.solver.red_expand(warm_state, self.assign)

    def run(self, state, W_seq):
        """One segment: scan ``red_step`` over ``W_seq`` from ``state``;
        returns ``(state, residuals, errors)``.  Re-entering with a
        same-shaped pair hits the jit cache."""
        if self._mesh_runner is not None:
            return self._mesh_runner.run(state, W_seq)
        return self._run(self._frep, self._b_rep,
                         jnp.asarray(self.sys.A_blocks),
                         jnp.asarray(self.sys.b_blocks), state, W_seq,
                         *self._xt)

    def collapse(self, state):
        """Replicated -> plain GLOBAL-shape state."""
        return self.solver.red_collapse(state, self.assign)

    def cache_size(self) -> int:
        """Total jit-cache entries across the engine's compiled callables
        — the zero-steady-state-retrace benchmarks assert this stays
        flat."""
        if self._mesh_runner is not None:
            return self._mesh_runner.cache_size()
        return self._run._cache_size()


def solve_redundant(solver, sys: BlockSystem, *, r: int, iters: int = 1000,
                    tol: float = 1e-6, alive_schedule=None,
                    warm_state: Any = None, factors: Any = None,
                    backend: str = "local", mesh: Any = None,
                    worker_axes: Sequence[str] = ("data",),
                    model_axis: Optional[str] = "model",
                    **params) -> SolveResult:
    """Shared driver for ``solve(..., redundancy=r, alive_schedule=...)``.

    Lowers the alive schedule to per-iteration selection weights once, then
    runs one ``RedundantEngine`` segment over them — locally or under
    shard_map on ``backend="mesh"``.  The returned ``SolveResult`` carries
    the plain GLOBAL-shape state.
    """
    _check_solver(solver, sys, r)
    alive = resolve_schedule(alive_schedule, sys.m, iters)
    # lower BEFORE the (expensive) engine build so an uncoverable schedule
    # fails loudly without paying for prepare/compile
    W_host = schedule_weights(alive, r)
    engine = RedundantEngine(solver, sys, r=r, backend=backend, mesh=mesh,
                             worker_axes=worker_axes, model_axis=model_axis,
                             factors=factors, **params)
    state = engine.init_state(warm_state)
    state, res, err = engine.run(state,
                                 jnp.asarray(W_host, dtype=engine.dtype))
    state = engine.collapse(state)
    return SolveResult(
        name=solver.name, x=solver.extract(state), state=state,
        residuals=res, errors=err if sys.x_true is not None else None,
        params=engine.prm, iters_to_tol=iters_to_tolerance(res, tol),
        tol=tol)


def _red_mesh_prepare(solver, A_rep, prm, ctx):
    """On-mesh replicated ``prepare``: replicas are just more worker blocks,
    so flatten (m_loc, r) -> m_loc*r, reuse ``mesh_prepare``, and fold the
    slot axis back into every factor leaf."""
    m_loc, r = A_rep.shape[:2]
    flat = solver.mesh_prepare(
        A_rep.reshape((m_loc * r,) + A_rep.shape[2:]), prm, ctx)
    return jax.tree.map(
        lambda f: f.reshape((m_loc, r) + f.shape[1:]), flat)
