"""Central allow-list for reprolint.

Every sanctioned rule exception lives HERE, with its justification, so
an audit of "what is exempt and why" is one file.  Entries are
``(path_glob, qualname_glob, why)``; a finding is suppressed when its
repo-relative path matches ``path_glob`` (fnmatch, or suffix match) AND
its qualified name (``Class.method`` nesting, ``""`` at module scope)
matches ``qualname_glob``.

Point-in-code exceptions should prefer the inline
``# repro: allow[RULE]`` comment next to the line; this file is for
STRUCTURAL exemptions — whole files or methods whose job is the thing
the rule exists to contain.
"""
from __future__ import annotations

ALLOW: dict[str, tuple[tuple[str, str, str], ...]] = {
    # R001: jax.jit inside a function body. The rule exists to catch
    # per-call jit construction; these sites construct ONCE and cache.
    "R001": (
        ("src/repro/solvers/mesh.py", "*",
         "compile-once builders: each jit(shard_map) is built once per "
         "CompiledSolve/placement and cached by the caller"),
        ("src/repro/solvers/redundant.py", "*",
         "compile-once redundant-placement builders, same pattern as "
         "mesh.py"),
        ("src/repro/solvers/serve.py", "_LocalExecutor.*",
         "the keyed executor cache itself: one jit per (solver, shape, "
         "param) key, constructed once in __init__ and cached by "
         "LinsysServer._executor — this IS the sanctioned home R001 "
         "points at"),
        ("src/repro/kernels/ops.py", "_engine_candidates",
         "engine autotune measurement: candidate jits are constructed "
         "once per (family, p, n, k, dtype) probe, timed by "
         "_measure_engine, then discarded; the winning engine is served "
         "by the module-scope jitted ops"),
        ("src/repro/core/distributed.py", "*",
         "deprecated shim layer: builds its compiled step once per "
         "DistributedSolve construction (kept for API compat)"),
        ("src/repro/launch/cells.py", "*",
         "dry-run cells lower one jit per (solver, shape) cell to cost "
         "it; each cell is built exactly once per plan"),
        ("src/repro/launch/train.py", "main",
         "training entry point: train_step is jitted once per process "
         "before the epoch loop"),
        ("src/repro/launch/serve.py", "make_decode",
         "the compile-once decode factory: built once per model OUTSIDE "
         "the batch loop, exactly the hoisting R001 demands"),
        ("benchmarks/periter.py", "*",
         "measurement harness: one jit per timed variant, constructed "
         "once before the timing loop"),
        ("benchmarks/straggler.py", "*",
         "measurement harness: one jit per timed variant, constructed "
         "once before the timing loop"),
    ),
    # R003: raw prepare/mesh_prepare callers that ARE the sanctioned
    # factor-acquisition machinery.
    "R003": (
        ("src/repro/solvers/store.py", "*",
         "FactorStore.factors IS the content-addressed owner of the "
         "raw solver.prepare call"),
        ("src/repro/solvers/api.py", "*",
         "Solver.solve/solve_many drivers: the non-served convenience "
         "path computes factors inline by design"),
        ("src/repro/solvers/mesh.py", "*",
         "mesh placement calls solver.mesh_prepare under shard_map; "
         "factors are then cached by the CompiledSolve"),
        ("src/repro/solvers/redundant.py", "*",
         "redundant placement, same ownership as mesh.py"),
        ("src/repro/solvers/elastic.py", "*",
         "elastic repartitioning goes through the FactorStore block "
         "tier when the solver supports it and falls back to a direct "
         "prepare for solvers without per-block factor independence"),
        ("src/repro/core/distributed.py", "*",
         "deprecated shim forwards to the solvers layer (kept for API "
         "compat; new code goes through FactorStore)"),
    ),
}
