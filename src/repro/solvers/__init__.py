"""repro.solvers — the unified distributed-solver API.

One lifecycle (prepare/init/step), one registry, one result type for every
solver in the paper's comparison:

    from repro import solvers
    res = solvers.get("apc").solve(sys, iters=500)      # -> SolveResult
    solvers.available()
    # ['apc', 'cimmino', 'consensus', 'dgd', 'dhbm', 'dnag', 'madmm', 'pdhbm']

Batched serving (one factorization, many right-hand sides):

    res = solvers.get("apc").solve_many(sys, B)          # B: (k, N)

Execution surface: everything beyond iters/tol/params travels on ONE
validated ``ExecutionPlan`` (backend, mesh, kernel, precision,
redundancy, store, warm_state, ...), resolved once at dispatch.  The
old loose kwargs (``backend=``, ``use_kernel=``, ...) still work via a
shim but are deprecated (one ``DeprecationWarning`` per call; lint rule
R009 keeps internal call sites off them):

    from repro.solvers import ExecutionPlan
    res = solvers.get("apc").solve(
        sys, plan=ExecutionPlan(backend="mesh", kernel=True), iters=500)

Warm starts / resume (feeds repro.checkpoint.ckpt):

    r1 = solvers.get("apc").solve(sys, iters=100)
    r2 = solvers.get("apc").solve(
        sys, iters=100, plan=ExecutionPlan(warm_state=r1.state))

Straggler-tolerant redundant execution (projection family, both backends):

    res = solvers.get("apc").solve(
        sys, plan=ExecutionPlan(redundancy=2,
                                alive_schedule=lambda t: mask_t))

Elastic fleet execution (membership changes mid-solve — deaths re-lower
the redundant schedule over the survivors, joins/rejoins repartition and
warm-start with per-block factor reuse, taskmaster loss recovers from
the store's disk tier):

    from repro.runtime.fault import HeartbeatMonitor
    rt = solvers.ElasticRuntime(solvers.get("apc"), sys,
                                plan=ExecutionPlan(redundancy=2),
                                monitor=HeartbeatMonitor(n_workers=sys.m))
    rt.monitor.mark_dead(2)          # death -> re-lower, keep iterating
    rep = rt.run(iters=600)          # rep.reused_blocks / rep.events

Cached factorizations + request serving (the serve-traffic hot path):

    store = solvers.FactorStore(directory="/ckpt/factors")
    res = solvers.get("apc").solve(
        sys, plan=ExecutionPlan(store=store))            # hit after 1st
    srv = solvers.LinsysServer(store, solver="apc", batch=4)

Async pipelined serving (overlapped admission/assembly/execution, per-
request futures, SLO latency report):

    asrv = solvers.AsyncLinsysServer(store, solver="apc", batch=4,
                                     pipeline_depth=2)
    with asrv:
        tickets = [asrv.submit(fp, b) for b in stream]

System modes (dense square / least-squares / block-sparse) flow through
every entry point above; each solver declares ``supports`` and a request
outside it raises ``CapabilityError`` at dispatch:

    ls  = linsys.tall_gaussian(1000, 500, 4, noise=0.01)   # inconsistent LS
    res = solvers.get("dgd").solve(ls, iters=2000)          # optimality res.
    sp  = linsys.banded_system(768, 4, bandwidth=9)         # already sparse
    res = solvers.get("apc").solve(sp, iters=300)           # sparse blockops

Streaming perturbed right-hand sides through a server (warm-start gating):

    rep = solvers.solve_stream(srv, [(fp, b0), (fp, b1), ...])
    rep.warm_hit_rate   # 1.0 for warm_rhs_ok solvers after the first batch

See ``api.Solver`` for the protocol, ``registry.register`` for adding a
new method, ``mesh`` for the sharded backend, ``redundant`` for the
r-redundant straggler-tolerant layer, ``store`` for the content-addressed
factor cache, ``serve`` for the linear-system request server, and
``pipeline`` for its async pipelined twin.
"""
import jax

# A float32 solve means float32 matmuls.  XLA on TPU otherwise multiplies
# f32 matrices in one bf16 pass: on a v5e chip the Gram A Aᵀ of a
# 2048 × 8192 block came out 1.05e-3 off in relative norm (3.4e-7 at
# "highest"), and the solves stalled at ‖Ax − b‖/‖b‖ ≈ 2e-3.  The CPU
# backend multiplies f32 exactly either way.
jax.config.update("jax_default_matmul_precision", "highest")

from .api import Solver, SolveResult, iters_to_tolerance  # noqa: F401, E402
from .capability import (CapabilityError, ExecutionPlan,  # noqa: F401, E402
                         resolve_plan)
from .registry import available, get, register  # noqa: F401, E402

# Importing the implementation modules populates the registry.
from . import admm, gradient, projection  # noqa: F401, E402
from . import mesh  # noqa: F401, E402  (the shard_map execution backend)
from . import redundant  # noqa: F401, E402  (straggler-tolerant layer)
from .store import BlockReuse, FactorStore, fingerprint  # noqa: F401, E402
from .serve import LinsysServer, StreamReport, solve_stream  # noqa: F401, E402
from .pipeline import AsyncLinsysServer, Shed, Ticket  # noqa: F401, E402
from .elastic import ElasticReport, ElasticRuntime  # noqa: F401, E402
