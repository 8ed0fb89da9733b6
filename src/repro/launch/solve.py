"""Distributed solve driver (the paper's workload as a service).

Partitions a linear system across workers, runs ANY registered solver from
``repro.solvers`` (APC by default) with its auto-tuned optimal parameters,
monitors the residual, and checkpoints the solver state for restart; a
checkpointed run resumes via ``--resume`` (warm start from the saved state).
``--use-mesh`` runs the same method through the shard_map mesh backend on
however many devices exist (force more with
``XLA_FLAGS=--xla_force_host_platform_device_count=N``); ``--x64/--no-x64``
pins the float width explicitly so checkpoint dtypes are reproducible
across resumes.  ``--redundancy r`` (projection family, either backend)
replicates blocks r-redundantly for straggler tolerance, and
``--straggler-sim RATE`` stalls one random worker per iteration with that
probability — the run still matches the no-failure one exactly
(``repro.solvers.redundant``).

Usage:
    PYTHONPATH=src python -m repro.launch.solve --problem std_gaussian \
        --workers 4 --iters 500 --method apc
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro import solvers
from repro.core import spectral
from repro.checkpoint import ckpt
from repro.data import linsys
from repro.launch import cache, mesh as mesh_lib


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--problem", default="std_gaussian",
                    choices=sorted(linsys.ALL_PROBLEMS))
    ap.add_argument("--method", default="apc", choices=solvers.available(),
                    help="registered solver")
    ap.add_argument("--workers", type=int, default=4)
    ap.add_argument("--iters", type=int, default=500)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--redundancy", type=int, default=1,
                    help="r-redundant blocks for straggler tolerance "
                         "(projection-family methods, local or mesh)")
    ap.add_argument("--straggler-sim", type=float, default=0.0,
                    metavar="RATE",
                    help="per-iteration probability that one random worker "
                         "stalls (needs --redundancy >= 2 to stay covered)")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--store-dir", default=None,
                    help="disk tier for the factor store — cached "
                         "factorizations survive restarts, so a resumed "
                         "run's prepare becomes a disk hit")
    ap.add_argument("--resume", action="store_true",
                    help="warm-start from the latest checkpoint in --ckpt-dir")
    ap.add_argument("--use-mesh", action="store_true",
                    help="run --method through the shard_map mesh backend")
    ap.add_argument("--use-kernel", action="store_true",
                    help="route the per-worker update through the Pallas "
                         "block-projection kernels (projection-family "
                         "methods, local or mesh backend)")
    ap.add_argument("--x64", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="float64 math (default on; checkpoints record the "
                         "resulting dtypes — resume with the same setting)")
    args = ap.parse_args(argv)

    cache.enable_compile_cache()
    jax.config.update("jax_enable_x64", args.x64)
    sys_ = linsys.ALL_PROBLEMS[args.problem](seed=args.seed)
    # re-partition to the requested worker count, preserving the system's
    # mode (least-squares stays least-squares) and sparse structure
    was_sparse = sys_.is_sparse
    A, b = sys_.dense()
    from repro.core.partition import as_sparse, partition, pad_to_blocks
    A, b = pad_to_blocks(np.asarray(A), np.asarray(b), args.workers)
    sys_ = partition(A, b, args.workers, x_true=sys_.x_true, mode=sys_.mode)
    if was_sparse:
        sys_ = as_sparse(sys_)

    solver = solvers.get(args.method)
    params, rho = solver.analyze(sys_)   # one spectral pass for both
    print(f"problem {args.problem}: N={sys_.N} n={sys_.n} m={sys_.m}  "
          f"method={args.method}")
    print(f"optimal params {({k: round(v, 4) for k, v in params.items()})}"
          + (f"  rho={rho:.6f} "
             f"(T={spectral.convergence_time(rho):.1f} iters/decade)"
             if rho is not None else ""))

    t0 = time.time()
    if args.redundancy > 1 and not solver.supports_redundancy:
        ap.error(f"--redundancy needs a projection-family method "
                 f"(apc/consensus/cimmino); {args.method!r} does not "
                 "support redundant execution")
    alive_schedule = None
    if args.straggler_sim > 0.0:
        if args.redundancy < 2:
            ap.error("--straggler-sim needs --redundancy >= 2 (a stalled "
                     "worker is unrecoverable without a redundant holder)")
        rng = np.random.default_rng(args.seed)
        m, rate = sys_.m, args.straggler_sim

        def alive_schedule(t):
            a = np.ones(m, bool)
            if rng.random() < rate:
                a[rng.integers(0, m)] = False
            return a

    # ALL factor acquisition goes through the content-addressed store:
    # the solve's `factors is None` branch is a cache lookup (memory LRU +
    # the --store-dir disk tier), the resume path reuses the SAME entry
    # for its restore template, and both backends accept the host factors
    # (the redundant layer replicates them itself).  A resume that has to
    # re-prepare is counted as a cache miss (store.stats.resume_misses)
    # instead of silently repaying the b-independent work.
    store = solvers.FactorStore(directory=args.store_dir)
    warm = None
    if args.resume:
        if not args.ckpt_dir:
            ap.error("--resume requires --ckpt-dir")
        step = ckpt.latest_step(args.ckpt_dir)
        if step is None:
            print(f"WARNING: no checkpoint found in {args.ckpt_dir}; "
                  "starting cold")
        else:
            factors = store.factors(solver, sys_, resume=True, **params)
            probe = solver.init(factors, sys_.b_blocks, params)
            warm = ckpt.restore(args.ckpt_dir, probe)
            print(f"resuming from checkpointed state at iter {step} "
                  f"(factor store: {store.stats})")
    if args.redundancy > 1:
        print(f"redundant execution: r={args.redundancy}"
              + (f", straggler rate {args.straggler_sim}"
                 if args.straggler_sim else ", no simulated stragglers"))
    # the whole execution surface travels on ONE validated plan
    mesh = None
    if args.use_mesh:
        mesh = mesh_lib.solver_mesh_for(sys_.m)
        print(f"mesh backend: {tuple(mesh.shape.items())} over "
              f"{len(jax.devices())} device(s)")
    plan = solvers.ExecutionPlan(
        backend="mesh" if args.use_mesh else "local", mesh=mesh,
        kernel=args.use_kernel, redundancy=args.redundancy,
        alive_schedule=alive_schedule, warm_state=warm, store=store)
    res = solver.solve(sys_, iters=args.iters, plan=plan, **params)
    xbar, final_res = res.x, float(res.residuals[-1])
    if res.iters_to_tol != -1:
        print(f"reached residual < {res.tol:.0e} after "
              f"{res.iters_to_tol} iters")
    if args.ckpt_dir:
        total = int(res.state.t) if hasattr(res.state, "t") else args.iters
        ckpt.save(args.ckpt_dir, total, res.state)
        print(f"solver state checkpointed at iter {total}")

    err = (float(np.linalg.norm(np.asarray(xbar) - np.asarray(sys_.x_true)) /
                 np.linalg.norm(np.asarray(sys_.x_true)))
           if sys_.x_true is not None else float("nan"))
    print(f"done in {time.time()-t0:.2f}s: residual {final_res:.3e}  "
          f"rel-error {err:.3e}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
