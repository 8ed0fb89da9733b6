"""Linear-system request server on the unified solver API.

``LinsysServer`` turns the paper's cost split into a serving loop: a
stream of ``(system_fingerprint, rhs)`` requests is coalesced into
same-system ``solve_many`` batches, every factorization comes from a
content-addressed ``FactorStore`` (memory LRU + optional disk tier), and a
compile-once executor cache keyed by (solver, shapes, params, backend)
means steady-state serving never retraces.

    store = FactorStore(directory="/ckpt/factors")
    srv = LinsysServer(store, solver="apc", iters=500, batch=4)
    fp = srv.register(sys)                      # fingerprint the system
    srv.submit(fp, b1); srv.submit(fp, b2)      # enqueue right-hand sides
    for served in srv.drain():                  # FIFO, coalesced batches
        served.x, served.residual

Batching follows the LM serving driver's queue semantics (``take_group``
lives here and ``repro.launch.serve`` imports it): groups are FIFO, a
short final group is padded by repeating the last request so the compiled
batch shape stays stable, and padding is NEVER counted in throughput.

Kernel serving (``use_kernel=True``, projection solvers): every coalesced
batch runs through the fused multi-RHS Pallas kernels — one read of each
A/B tile serves the whole batch — on either backend; the store entry is
augmented with the pinv factors exactly once.

Warm starts (``warm_start=True``): a system's previous batch state seeds
the next one.  Repeated right-hand sides always qualify (that is exactly
``solve(warm_state=...)`` resume); PERTURBED right-hand sides only
qualify for solvers whose iteration re-reads b every step and whose state
caches nothing RHS-dependent (``Solver.warm_rhs_ok`` — the gradient
family and Cimmino; APC iterates stay feasible for the OLD b, and
M-ADMM / P-DHBM cache transformed right-hand sides in their state, so the
server silently falls back to a cold init for them).
"""
from __future__ import annotations

import dataclasses
import threading
from collections import deque
from contextlib import contextmanager
from typing import Any, Dict, List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding

from repro.core.partition import BlockSystem
from repro.runtime.spans import span

from .api import LOCAL_PSUM, _history_scan_many, iters_to_tolerance
from .capability import (ExecutionPlan, check_capability,
                         resolve_use_kernel)
from .store import FactorStore


def take_group(queue, batch: int):
    """Pop the next slot group off the request queue, FIFO.

    Returns ``(group, n_real)``: up to ``batch`` requests in arrival order,
    padded by repeating the last one so the compiled batch shape is stable.
    Only ``n_real`` requests were actually served — padding must never be
    counted in throughput.
    """
    if batch < 1:
        raise ValueError(f"batch must be >= 1, got {batch}")
    n_real = min(batch, len(queue))
    group = [queue.popleft() for _ in range(n_real)]
    while group and len(group) < batch:
        group.append(group[-1])
    return group, n_real


class Request(NamedTuple):
    rid: int            # server-assigned id, arrival order
    fp: str             # system fingerprint (FactorStore key)
    rhs: np.ndarray     # (N,) right-hand side


class Served(NamedTuple):
    """Per-request result handed back by ``step``/``drain``."""
    rid: int
    fp: str
    x: np.ndarray       # (n,) solution estimate
    residual: float     # final relative residual ||Ax-b||/||b||
    iters_to_tol: int   # -1 sentinel = tolerance never reached
    warm: bool          # batch was warm-started from a prior state


@dataclasses.dataclass
class ServerStats:
    served: int = 0             # real requests completed (padding excluded)
    padded: int = 0             # pad slots run (never counted as traffic)
    batches: int = 0
    warm_batches: int = 0
    executor_builds: int = 0    # compile-once cache misses
    admitted: int = 0           # accepted into the pipeline (async server)
    shed: int = 0               # rejected at admission (async server
                                # backpressure; the sync server never sheds)


@dataclasses.dataclass
class _System:
    """Per-registered-system serving state."""
    sys: BlockSystem
    prm: Dict[str, float]
    dtype: Any                      # A's dtype, read once at register()
    executor_key: Tuple             # compile-once cache key, built once
    use_kernel: bool = False        # per-system resolution (downgraded only
                                    # for solvers with no kernel engine)
    A_placed: Any = None            # backend-placed A blocks
    factors_placed: Any = None      # backend-placed factors
    placed_src: Any = None          # host factors the placement came from
    last_states: Any = None         # prior batch's final states (warm start)
    last_Bb: Optional[np.ndarray] = None


class _Work(NamedTuple):
    """One assembled batch: its system, executor and group, with B placed
    on the device (``Bb`` is the host copy, for warm-start repeats)."""
    fp: str
    ent: Any
    ex: Any
    group: List[Any]
    n_real: int
    Bb: np.ndarray
    Bb_dev: Any
    warm: bool


@contextmanager
def _compile_once(lock: threading.Lock, fn):
    """Around a call of the jitted ``fn``: a call that finds nothing
    compiled holds ``lock`` and runs under the span
    ``repro.linsys.compile`` (its trace, where the engine autotune
    measures, and its compile).  A first call of another thread waits for
    it and then finds ``fn`` compiled, so an executor's programs are
    compiled, autotuned and counted once."""
    if fn._cache_size():
        yield
        return
    with lock:
        if fn._cache_size():
            yield
            return
        with span("repro.linsys.compile"):
            yield


class _LocalExecutor:
    """Compile-once single-host executor: jitted init+scan over a padded
    (batch, m, p) RHS block.  One instance serves every system that shares
    its (shapes, params) key.  ``use_kernel=True`` routes the batched step
    through the fused multi-RHS Pallas kernels (``Solver.step_many``).
    ``ls_mode=True`` (least-squares systems) reports the LS optimality
    moment instead of the raw relative residual."""

    def __init__(self, solver, prm, iters: int, use_kernel: bool = False,
                 ls_mode: bool = False):
        fused_res = (use_kernel and solver.supports_fused_residual
                     and not ls_mode and iters > 0)

        def _residual_fn(A, factors):
            if not ls_mode:
                return None

            def optim(b, x):
                mom = solver.ls_moment(factors, A, b, x, prm, LOCAL_PSUM)
                return jnp.sqrt(jnp.sum(mom * mom))

            return lambda b, x: optim(b, x) / optim(b, jnp.zeros_like(x))

        def _run(A, factors, Bb, states):
            step_many = lambda f, bb, sts: solver.step_many(
                f, bb, sts, prm, use_kernel=use_kernel)
            step_many_res = (lambda f, bb, sts: solver.step_many_residual(
                f, bb, sts, prm)) if fused_res else None
            states, res = _history_scan_many(
                step_many, solver.extract, factors, Bb, states, A, iters,
                residual_fn=_residual_fn(A, factors),
                step_many_residual=step_many_res)
            return states, jax.vmap(solver.extract)(states), res

        def _cold(A, factors, Bb):
            states = jax.vmap(lambda b: solver.init(factors, b, prm))(Bb)
            return _run(A, factors, Bb, states)

        self._cold = jax.jit(_cold)
        self._warm = jax.jit(_run)
        self._compile_lock = threading.Lock()

    def place_system(self, sys: BlockSystem, factors):
        return sys.A_op, factors

    def place_B(self, Bb: np.ndarray):
        # an explicit device_put so the host->device transfer happens on
        # the CALLING thread — the async pipeline runs this on its
        # assembly thread, double-buffering the copy behind execution
        return jax.device_put(jnp.asarray(Bb))

    def run(self, A, factors, Bb, states=None):
        if states is None:
            with _compile_once(self._compile_lock, self._cold):
                return self._cold(A, factors, Bb)
        with _compile_once(self._compile_lock, self._warm):
            return self._warm(A, factors, Bb, states)

    def cache_size(self) -> int:
        return self._cold._cache_size() + self._warm._cache_size()


class _MeshExecutor:
    """Mesh twin: wraps ``mesh.batched_runner`` and owns placement."""

    def __init__(self, solver, prm, iters: int, sys: BlockSystem,
                 mesh, worker_axes, model_axis, use_kernel: bool = False):
        from . import mesh as mesh_backend
        self.solver = solver
        self.use_kernel = use_kernel
        self.mesh = mesh if mesh is not None \
            else mesh_backend._default_mesh(sys.m)
        self.ctx = mesh_backend.make_context(
            self.mesh, sys, worker_axes=worker_axes, model_axis=model_axis)
        self.runner = mesh_backend.batched_runner(
            solver, self.ctx, prm, iters, use_kernel=use_kernel,
            a_spec=mesh_backend.operand_specs(sys, self.ctx),
            ls_mode=sys.mode == "least_squares",
            fused_residual=use_kernel)
        self._compile_lock = threading.Lock()

    def place_system(self, sys: BlockSystem, factors):
        from . import mesh as mesh_backend
        A = mesh_backend._put_tree(sys.A_op, self.runner.A_spec, self.mesh)
        f = mesh_backend._put_tree(
            mesh_backend._host_factors(self.solver, factors,
                                       self.use_kernel),
            self.runner.factor_specs, self.mesh)
        return A, f

    def place_B(self, Bb: np.ndarray):
        return jax.device_put(jnp.asarray(Bb),
                              NamedSharding(self.mesh, self.runner.Bb_spec))

    def run(self, A, factors, Bb, states=None):
        with _compile_once(self._compile_lock, self.runner.run):
            if states is None:
                states = self.runner.init(factors, Bb)
            return self.runner.run(A, Bb, factors, states)

    def cache_size(self) -> int:
        return self.runner.cache_size()


class LinsysServer:
    """Batched linear-system serving on the unified solver lifecycle.

    Requests for the SAME system (by content fingerprint) are coalesced
    into ``solve_many`` batches; the oldest pending request picks which
    system is served next, so no system starves while coalescing still
    fills batches.  All factor acquisition goes through the
    ``FactorStore`` — the first request for a system pays ``prepare``
    (a store miss, or a disk hit after a restart), every later one is a
    cache hit.
    """

    def __init__(self, store: Optional[FactorStore] = None, *,
                 solver="apc", iters: int = 500, tol: float = 1e-6,
                 batch: int = 4, plan: Optional[ExecutionPlan] = None,
                 backend: str = "local", mesh=None,
                 warm_start: bool = False, use_kernel: bool = False,
                 precision: str = "default",
                 worker_axes: Sequence[str] = ("data",),
                 model_axis: Optional[str] = "model", **params):
        if plan is not None:
            if not isinstance(plan, ExecutionPlan):
                raise TypeError(f"plan must be an ExecutionPlan, got "
                                f"{type(plan).__name__}")
            if (backend != "local" or mesh is not None or use_kernel
                    or precision != "default"
                    or tuple(worker_axes) != ("data",)
                    or model_axis != "model"):
                raise ValueError(
                    "pass the execution surface EITHER on plan= OR as "
                    "loose kwargs, not both")
            if plan.is_redundant:
                raise ValueError(
                    "redundant execution is not servable: the coalesced "
                    "solve_many batches have no coded replicated layout; "
                    "run solve(plan=ExecutionPlan(redundancy=..., "
                    "alive_schedule=...)) per right-hand side")
            if plan.warm_state is not None or plan.factors is not None:
                raise ValueError(
                    "a server plan cannot carry warm_state=/factors= — "
                    "warm starts are per-system (warm_start=True) and "
                    "factors flow through the FactorStore")
            if store is None and plan.store is not None:
                store = plan.store
            backend, mesh = plan.backend, plan.mesh
            use_kernel, precision = plan.kernel, plan.precision
            worker_axes, model_axis = plan.worker_axes, plan.model_axis
        else:
            plan = ExecutionPlan(backend=backend, kernel=use_kernel,
                                 precision=precision, mesh=mesh,
                                 worker_axes=tuple(worker_axes),
                                 model_axis=model_axis)
        if backend not in ("local", "mesh"):
            raise ValueError(f"unknown backend {backend!r}; "
                             "expected 'local' or 'mesh'")
        if batch < 1:
            raise ValueError(f"batch must be >= 1, got {batch}")
        from .registry import get
        self.store = store if store is not None else FactorStore()
        self.solver = get(solver) if isinstance(solver, str) else solver
        self.solver._check_kernel(use_kernel)
        self.solver._check_precision(precision, use_kernel)
        self.plan = plan
        self.iters, self.tol, self.batch = iters, tol, batch
        self.backend, self.mesh = backend, mesh
        self.warm_start = warm_start
        self.use_kernel = use_kernel
        self.precision = precision
        self.worker_axes, self.model_axis = tuple(worker_axes), model_axis
        self.params = params
        self.stats = ServerStats()
        self._systems: Dict[str, _System] = {}
        self._queues: Dict[str, deque] = {}
        self._executors: Dict[Tuple, Any] = {}
        self._rid = 0

    # ----- request intake ---------------------------------------------------
    def register(self, sys: BlockSystem, **params) -> str:
        """Fingerprint ``sys`` and make it servable.  Factors are NOT
        prefetched — the first request pays the store miss (or disk hit),
        which is what the cold/warm benchmarks measure.  Per-register
        ``params`` override the server-level ones key by key.

        Capability is checked HERE — an unservable (solver, system-mode)
        pair fails at registration, not on the first request.  The kernel
        flag resolves per system: sparse systems on kernel-capable solvers
        keep the fused path (the compressed-support Pallas pair); only a
        solver with no kernel engine downgrades it, loudly."""
        check_capability(self.solver, sys, context="register")
        use_kernel = resolve_use_kernel(self.solver, sys, self.use_kernel)
        # re-check per system: a sparse downgrade of the kernel flag must
        # not silently serve full-precision under precision="mixed"
        self.solver._check_precision(self.precision, use_kernel)
        prm = self.solver.resolve_params(sys, **{**self.params, **params})
        fp = self.store.key(self.solver, sys, precision=self.precision,
                            **prm)
        dtype = sys.A_blocks.dtype
        # the dispatch identity is the PLAN's signature (backend, kernel,
        # precision, worker/model axes...) with the per-system kernel
        # resolution folded in — plus the shape/params/batch dimensions
        # the compiled executor closes over
        executor_key = (self.solver.name, sys.m, sys.p, sys.n, str(dtype),
                        sys.structure, sys.mode,
                        tuple(sorted(prm.items())),
                        self.plan.replace(kernel=use_kernel).signature(),
                        self.batch, self.iters)
        self._systems[fp] = _System(sys=sys, prm=prm, dtype=dtype,
                                    executor_key=executor_key,
                                    use_kernel=use_kernel)
        self._queues.setdefault(fp, deque())
        return fp

    def _validated(self, fp: str, rhs) -> Tuple[_System, np.ndarray]:
        """Shared admission validation: the fingerprint must have been
        ``register()``-ed and the RHS must match the system's shape.  The
        KeyError names the FULL fingerprint so operators can grep it
        against their registry."""
        ent = self._systems.get(fp)
        if ent is None:
            raise KeyError(f"unknown system fingerprint {fp!r}; "
                           "register() the system first")
        rhs = np.asarray(rhs, dtype=ent.dtype)
        if rhs.shape != (ent.sys.N,):
            raise ValueError(f"rhs has shape {rhs.shape}, need "
                             f"({ent.sys.N},) for this system")
        return ent, rhs

    def submit(self, fp: str, rhs) -> int:
        """Enqueue one right-hand side for a registered system."""
        _, rhs = self._validated(fp, rhs)
        rid = self._rid
        self._rid += 1
        self._queues[fp].append(Request(rid=rid, fp=fp, rhs=rhs))
        return rid

    def pending(self) -> int:
        return sum(len(q) for q in self._queues.values())

    # ----- executors (compile-once cache) -----------------------------------
    def _executor(self, ent: _System):
        key = ent.executor_key
        ex = self._executors.get(key)
        if ex is None:
            self.stats.executor_builds += 1
            if self.backend == "mesh":
                ex = _MeshExecutor(self.solver, ent.prm, self.iters,
                                   ent.sys, self.mesh, self.worker_axes,
                                   self.model_axis,
                                   use_kernel=ent.use_kernel)
            else:
                ex = _LocalExecutor(self.solver, ent.prm, self.iters,
                                    use_kernel=ent.use_kernel,
                                    ls_mode=ent.sys.mode == "least_squares")
            self._executors[key] = ex
        return ex

    def jit_cache_size(self) -> int:
        """Total jit-cache entries across executors.  Constant across
        batches == zero retraces.  (Snapshots the executor dict so the
        async pipeline's assembly thread can add executors while another
        thread reads this.)"""
        return sum(ex.cache_size() for ex in list(self._executors.values()))

    # ----- serving ----------------------------------------------------------
    def _warm_ok(self, ent: _System, Bb: np.ndarray) -> bool:
        if not self.warm_start or ent.last_states is None \
                or ent.last_Bb is None:
            return False
        if np.array_equal(ent.last_Bb, Bb):
            return True                       # repeated RHS: plain resume
        return bool(getattr(self.solver, "warm_rhs_ok", False))

    def step(self):
        """Serve ONE coalesced batch (the oldest pending request's system).

        Returns the list of ``Served`` results for the REAL requests in
        the batch.  With ZERO pending requests this is a true no-op:
        it returns [] before any executor, store, or device work — no
        empty-batch compile, no jit-cache growth, no stats movement.
        """
        # oldest pending request picks the system; coalescing then fills
        # the batch with that system's next requests (which may have
        # arrived later than other systems' — that is the point)
        pending = [(q[0].rid, fp) for fp, q in self._queues.items() if q]
        if not pending:
            return []
        fp = min(pending)[1]
        group, n_real = take_group(self._queues[fp], self.batch)
        w = self._assemble(fp, group, n_real)
        states, X, res = self._run(w)
        w.ent.last_states, w.ent.last_Bb = states, w.Bb

        self.stats.batches += 1
        self.stats.served += n_real
        self.stats.padded += len(group) - n_real
        self.stats.warm_batches += int(w.warm)
        return self._served(w, X, res)

    # ----- the stages of one batch, shared with the async server ------------
    def _assemble(self, fp: str, group, n_real: int) -> _Work:
        """Store lookup, executor acquisition, placement of A (first batch
        or after an eviction) and of the batch's B."""
        ent = self._systems[fp]
        # every factor acquisition goes through the store (hit after the
        # first batch; key precomputed at register() so no re-hash of A;
        # the kernel path augments the cached entry with the pinv factors
        # exactly once — ``kernel_factors`` is idempotent)
        factors = self.store.factors(self.solver, ent.sys, key=fp,
                                     use_kernel=ent.use_kernel,
                                     precision=self.precision, **ent.prm)
        ex = self._executor(ent)
        if ent.placed_src is not factors:     # first batch / post-eviction
            ent.A_placed, ent.factors_placed = ex.place_system(ent.sys,
                                                               factors)
            ent.placed_src = factors
        Bb = np.stack([r.rhs for r in group]).reshape(
            len(group), ent.sys.m, ent.sys.p)
        return _Work(fp=fp, ent=ent, ex=ex, group=list(group),
                     n_real=n_real, Bb=Bb, Bb_dev=ex.place_B(Bb),
                     warm=self._warm_ok(ent, Bb))

    def _run(self, w: _Work):
        """Dispatch the batch and wait for its answers on the host:
        (final states, X, residual histories)."""
        states, X, res = w.ex.run(w.ent.A_placed, w.ent.factors_placed,
                                  w.Bb_dev,
                                  w.ent.last_states if w.warm else None)
        return states, np.asarray(X), np.asarray(res)   # waits for device

    def _served(self, w: _Work, X: np.ndarray, res: np.ndarray):
        """The ``Served`` results of the batch's real requests."""
        to_tol = np.atleast_1d(iters_to_tolerance(res, self.tol))
        return [Served(rid=r.rid, fp=w.fp, x=X[i],
                       residual=float(res[i, -1]),
                       iters_to_tol=int(to_tol[i]), warm=w.warm)
                for i, r in enumerate(w.group[:w.n_real])]

    def drain(self):
        """Serve until every queue is empty; results in served order."""
        out = []
        while True:
            batch = self.step()
            if not batch:
                return out
            out.extend(batch)


class StreamReport(NamedTuple):
    """Outcome of a ``solve_stream`` run."""
    served: list        # Served results, completion order
    batches: int        # coalesced batches executed for this stream
    warm_batches: int   # batches that started from a prior state
    warm_hit_rate: float  # warm_batches / batches (0.0 on an empty stream)


def solve_stream(server, stream, *, drain_every: int = 1) -> StreamReport:
    """Drive a server through an ordered stream of ``(fp, rhs)`` requests.

    The streaming mode of the system layer: clients repeatedly re-solve
    REGISTERED systems under perturbed right-hand sides (sensor updates,
    tracking loops — the serve-traffic scenario).  Requests are submitted
    in order and served every ``drain_every`` submissions, so consecutive
    same-system requests land in the same coalesced batch only when the
    cadence allows it; the report separates warm from cold batches, which
    is the quantity the warm-start gating (``Solver.warm_rhs_ok``) moves.

    Works with both servers: the sync ``LinsysServer`` and the pipelined
    ``AsyncLinsysServer`` (whose ``submit`` may shed under backpressure —
    shed requests simply do not appear in ``served``).
    """
    if drain_every < 1:
        raise ValueError(f"drain_every must be >= 1, got {drain_every}")
    b0, w0 = server.stats.batches, server.stats.warm_batches
    served = []
    for i, (fp, rhs) in enumerate(stream):
        server.submit(fp, rhs)
        if (i + 1) % drain_every == 0:
            served.extend(server.drain())
    served.extend(server.drain())
    batches = server.stats.batches - b0
    warm = server.stats.warm_batches - w0
    return StreamReport(served=served, batches=batches, warm_batches=warm,
                        warm_hit_rate=warm / batches if batches else 0.0)
