#!/usr/bin/env python3
"""Chip smoke: the APC main path at a real per-worker width, on the TPU.

    python chip_smoke.py                  # one chip: phases (a), (b), (c)
    python chip_smoke.py --four-chips     # four chips: phase (d) only
    python chip_smoke.py --N 512 --n 256  # a CPU rehearsal size

The system is a consistent tall Gaussian (b = A x*), N=16384 rows by
n=8192 columns in float32, cut into m=8 row blocks of p=2048: each worker
block has the real width.  The data is generated from ``--seed``.

  (a) ``solvers.get("apc").solve`` on the Pallas projection engine, with
      its factors from a ``FactorStore``.
  (b) a ``LinsysServer`` registers the same system and answers 32
      requests in two batches of 16; the second batch must not retrace.
  (c) one direct ``kernels.ops.block_projection`` call at the block
      shape: its compiled HLO must hold the Pallas kernel, and its output
      must match ``ref.block_projection_ref`` evaluated in float64 numpy.
  (d) ``--four-chips``: ``solve`` and ``solve_many`` (k=16) on the mesh
      backend, data axis over four chips, each against the one-device
      local solve; A's shards must sit on four distinct devices.

Every answer is checked on the host in float64 numpy:
‖Ax − b‖/‖b‖ ≤ 1e-4.  Earlier lines report what ran and what it took; the
last line is one JSON object ``{"ok": ..., "device": {...}}``.  The run
exits 0 only on a TPU with every check passing.  On any other backend it
runs the phases at the size given (and refuses the default size, which is
the chip's), then prints ``"ok": false`` and exits 1.  Warnings are
errors, so a kernel downgrade (``RuntimeWarning``) fails the run.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
import warnings
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro import solvers  # noqa: E402
from repro.analysis.tracecheck import tracecheck  # noqa: E402
from repro.data import linsys  # noqa: E402
from repro.kernels import block_projection as kbp  # noqa: E402
from repro.kernels import ops as kops  # noqa: E402
from repro.kernels import ref as kref  # noqa: E402
from repro.launch import cache  # noqa: E402
from repro.launch import mesh as mesh_lib  # noqa: E402

FULL = {"N": 16384, "n": 8192, "m": 8}
TOL = 1e-4
REQUESTS, BATCH = 32, 16

_COMPILE = {"seconds": 0.0, "cache_hits": 0}


def _on_duration(event, duration, **_):
    if event == "/jax/core/compile/backend_compile_duration":
        _COMPILE["seconds"] += duration


def _on_event(event, **_):
    if event == "/jax/compilation_cache/cache_hits":
        _COMPILE["cache_hits"] += 1


def report(name, value):
    print(f"{name}: {value}", flush=True)


def make_system(N, n, m, seed):
    """The consistent float32 tall Gaussian system the smoke solves."""
    return linsys.tall_gaussian(N=N, n=n, m=m, seed=seed, dtype=jnp.float32)


def host_A(sys_):
    return np.asarray(sys_.A_blocks, np.float64).reshape(sys_.N, sys_.n)


def host_residuals(A64, X, B):
    """‖A x − b‖/‖b‖ per row of X against the rows of B, float64 numpy."""
    X = np.atleast_2d(np.asarray(X, np.float64))
    B = np.atleast_2d(np.asarray(B, np.float64))
    return (np.linalg.norm(X @ A64.T - B, axis=1)
            / np.linalg.norm(B, axis=1))


def hlo_has_kernel(fn, *args) -> bool:
    """Does the compiled program of ``fn(*args)`` hold a Pallas kernel?"""
    return "tpu_custom_call" in jax.jit(fn).lower(*args).compile().as_text()


def timed(fn):
    """(result, wall seconds, backend-compile seconds) of ``fn()``, timed
    until every array in the result is ready."""
    c0 = _COMPILE["seconds"]
    t0 = time.perf_counter()
    out = fn()
    jax.block_until_ready(vars(out) if dataclasses.is_dataclass(out)
                          else out)
    return out, time.perf_counter() - t0, _COMPILE["seconds"] - c0


def engine_report():
    """The engine ``use_fused`` picked and the tiles ``pick_tiles`` picked,
    per shape resolved so far."""
    engines = {f"{k[0]} p={k[1]} n={k[2]} k={k[3]} {k[-1]}":
               "fused" if v else "unfused"
               for k, v in kops.engine_cache().items()}
    bns = kops.bn_cache()
    tiles = {f"k={k[0]} p={k[1]} n={k[2]} {k[3]}":
             {"bn": bns.get((k[1], k[2], k[3])), "bp": v[0], "bk": v[1]}
             for k, v in kops.tile_cache().items()}
    return engines, tiles


def phase_solve(sys_, prm, iters):
    """(a) The plain solve through ``solve(plan=ExecutionPlan(kernel=True,
    store=...))``; the factor prepare is timed apart as a store miss."""
    solver = solvers.get("apc")
    store = solvers.FactorStore()
    factors, prepare_s, _ = timed(lambda: store.factors(
        solver, sys_, use_kernel=True, **prm))
    plan = solvers.ExecutionPlan(kernel=True, store=store)
    res, wall, comp = timed(lambda: solver.solve(sys_, iters=iters,
                                                 plan=plan, **prm))
    state = solver.init(factors, sys_.b_blocks, prm)
    kernel = hlo_has_kernel(
        lambda f, b, s: solver.step_residual(f, b, s, prm),
        factors, sys_.b_blocks, state)
    return {"x": np.asarray(res.x), "factors": factors,
            "device_residual": float(res.residuals[-1]),
            "iters_to_tol": int(res.iters_to(TOL)),
            "prepare_s": prepare_s, "solve_s": wall, "compile_s": comp,
            "kernel_in_step": kernel}


def phase_serve(sys_, prm, iters, seed, A64):
    """(b) 32 requests b = A x through a ``LinsysServer`` in two batches
    of 16; the second batch runs inside a tracecheck window."""
    solver = solvers.get("apc")
    srv = solvers.LinsysServer(solver="apc", iters=iters, batch=BATCH,
                               plan=solvers.ExecutionPlan(kernel=True),
                               **prm)
    fp = srv.register(sys_)
    rng = np.random.default_rng(seed + 1)
    B = rng.standard_normal((REQUESTS, sys_.n)) @ A64.T
    for b in B:
        srv.submit(fp, b)
    first, wall1, comp1 = timed(srv.step)
    with tracecheck() as tc:
        second, wall2, comp2 = timed(srv.step)
    served = sorted(first + second, key=lambda s: s.rid)
    X = np.stack([s.x for s in served])
    factors = srv.store.factors(solver, sys_, key=fp, use_kernel=True, **prm)
    Bb = jnp.asarray(B[:BATCH].reshape(BATCH, sys_.m, sys_.p), jnp.float32)
    states = jax.vmap(lambda b: solver.init(factors, b, prm))(Bb)
    kernel = hlo_has_kernel(
        lambda f, bb, s: solver.step_many_residual(f, bb, s, prm),
        factors, Bb, states)
    return {"residuals": host_residuals(A64, X, B),
            "served": len(served), "pending": srv.pending(),
            "retraces": len(tc.traces()),
            "batch1_s": wall1, "batch1_compile_s": comp1,
            "batch2_s": wall2, "batch2_compile_s": comp2,
            "kernel_in_step": kernel}


def phase_kernel(sys_, factors, gamma, seed):
    """(c) One ``ops.block_projection`` call on worker 0's block."""
    rng = np.random.default_rng(seed + 2)
    A, B = sys_.A_blocks[0], factors.B[0]
    x, xbar = rng.standard_normal((2, sys_.n)).astype(np.float32)
    compiled = kops.block_projection.lower(A, B, x, xbar, gamma).compile()
    y = np.asarray(kops.block_projection(A, B, x, xbar, gamma), np.float64)
    y_ref = kref.block_projection_ref(
        np.asarray(A, np.float64), np.asarray(B, np.float64),
        x.astype(np.float64), xbar.astype(np.float64), float(gamma))
    return {"tpu_custom_call": "tpu_custom_call" in compiled.as_text(),
            "rel_err": float(np.linalg.norm(y - y_ref)
                             / np.linalg.norm(y_ref))}


def phase_mesh(sys_, prm, iters, seed, A64, k=BATCH):
    """(d) ``solve`` and ``solve_many`` on the mesh backend against the
    one-device local solves, plus where A's shards sit."""
    solver = solvers.get("apc")
    mesh = mesh_lib.solver_mesh_for(sys_.m)
    mstore = solvers.FactorStore()
    on_mesh = solvers.ExecutionPlan(backend="mesh", mesh=mesh, kernel=True,
                                    store=mstore)
    local = solvers.ExecutionPlan(kernel=True, store=solvers.FactorStore())
    rng = np.random.default_rng(seed + 3)
    B = rng.standard_normal((k, sys_.n)) @ A64.T
    out = {"mesh": dict(mesh.shape)}
    b = np.asarray(sys_.b_blocks, np.float64).reshape(-1)
    for name, run, rhs in (
            ("solve", lambda p: solver.solve(sys_, iters=iters, plan=p,
                                             **prm), b),
            ("solve_many", lambda p: solver.solve_many(
                sys_, B, iters=iters, plan=p, **prm), B)):
        rm, wall_m, comp_m = timed(lambda: run(on_mesh))
        rl, wall_l, comp_l = timed(lambda: run(local))
        xm, xl = np.asarray(rm.x, np.float64), np.asarray(rl.x, np.float64)
        out[name] = {
            "agree": float(np.max(np.linalg.norm(np.atleast_2d(xm - xl),
                                                 axis=1)
                                  / np.linalg.norm(np.atleast_2d(xl),
                                                   axis=1))),
            "residuals_mesh": host_residuals(A64, xm, rhs),
            "residuals_local": host_residuals(A64, xl, rhs),
            "mesh_s": wall_m, "mesh_compile_s": comp_m,
            "local_s": wall_l, "local_compile_s": comp_l}
    A_op = mstore.lookup(solver, sys_, use_kernel=True, **prm).A
    out["shards"] = [(s.device.id, tuple(s.data.shape))
                     for s in A_op.addressable_shards]
    return out


def _peak_bytes():
    stats = jax.devices()[0].memory_stats()
    return "not reported" if not stats else stats.get("peak_bytes_in_use")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded mesh phase (d) and its "
                         "local comparison")
    ap.add_argument("--N", type=int, default=FULL["N"])
    ap.add_argument("--n", type=int, default=FULL["n"])
    ap.add_argument("--m", type=int, default=FULL["m"])
    ap.add_argument("--iters", type=int, default=100)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    warnings.simplefilter("error")

    report("compile cache", cache.enable_compile_cache())
    jax.config.update("jax_enable_x64", False)
    jax.monitoring.register_event_duration_secs_listener(_on_duration)
    jax.monitoring.register_event_listener(_on_event)
    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    on_tpu = device["platform"] == "tpu"
    report("platform", device["platform"])
    report("device_kind", device["kind"])
    report("device count", device["count"])
    report("default_interpret()", kbp.default_interpret())
    checks = {"tpu": on_tpu, "compiled kernels": not kbp.default_interpret()}

    size = {"N": args.N, "n": args.n, "m": args.m}
    if not on_tpu and size == FULL:
        report("refused", "no TPU, and the default size is the chip's; "
               "pass --N/--n/--m for a CPU rehearsal")
        print(json.dumps({"ok": False, "device": device}))
        return 1

    t0 = time.perf_counter()
    sys_ = make_system(args.N, args.n, args.m, args.seed)
    A64 = host_A(sys_)
    gen_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    solver = solvers.get("apc")
    prm, rho = solver.analyze(sys_)
    analyze_s = time.perf_counter() - t0
    report("system", f"N={sys_.N} n={sys_.n} m={sys_.m} p={sys_.p} "
                     f"{sys_.A_blocks.dtype} seed={args.seed}")
    report("params", f"gamma={prm['gamma']!r} eta={prm['eta']!r} "
                     f"rho={rho!r} kappa(X)={((1 + rho) / (1 - rho)) ** 2!r}")
    report("host set-up s", f"generate={gen_s!r} analyze={analyze_s!r}")

    if args.four_chips:
        out = phase_mesh(sys_, prm, args.iters, args.seed, A64)
        report("(d) mesh", out["mesh"])
        for name in ("solve", "solve_many"):
            o = out[name]
            report(f"(d) {name} mesh vs local rel diff", repr(o["agree"]))
            report(f"(d) {name} residuals mesh",
                   [float(r) for r in o["residuals_mesh"]])
            report(f"(d) {name} residuals local",
                   [float(r) for r in o["residuals_local"]])
            report(f"(d) {name} seconds",
                   f"mesh={o['mesh_s']!r} (compile {o['mesh_compile_s']!r}) "
                   f"local={o['local_s']!r} "
                   f"(compile {o['local_compile_s']!r})")
            checks[f"(d) {name} agree"] = o["agree"] <= TOL
            checks[f"(d) {name} residuals"] = bool(
                np.all(o["residuals_mesh"] <= TOL)
                and np.all(o["residuals_local"] <= TOL))
        report("(d) A shards (device, shape)", out["shards"])
        data = out["mesh"]["data"]
        checks["(d) four-way data axis"] = data == 4
        checks["(d) A sharded"] = (
            len({d for d, _ in out["shards"]}) == data
            and all(s == (sys_.m // data, sys_.p, sys_.n)
                    for _, s in out["shards"]))
    else:
        a = phase_solve(sys_, prm, args.iters)
        ra = float(host_residuals(A64, a["x"], np.asarray(
            sys_.b_blocks).reshape(-1))[0])
        report("(a) factor prepare s (host hash + device factorize)",
               repr(a["prepare_s"]))
        report("(a) solve s", f"{a['solve_s']!r} (compile "
                              f"{a['compile_s']!r})")
        report("(a) residual", f"host f64 {ra!r}, device history "
                               f"{a['device_residual']!r}, iters to "
                               f"{TOL:g}: {a['iters_to_tol']}")
        report("(a) tpu_custom_call in compiled step", a["kernel_in_step"])
        engines, tiles = engine_report()
        report("(a) engine", engines)
        report("(a) tiles", tiles)
        checks["(a) residual"] = ra <= TOL

        b = phase_serve(sys_, prm, args.iters, args.seed, A64)
        report("(b) served", f"{b['served']} requests, {b['pending']} "
                             f"pending")
        report("(b) batch seconds",
               f"first={b['batch1_s']!r} (compile {b['batch1_compile_s']!r})"
               f" second={b['batch2_s']!r} "
               f"(compile {b['batch2_compile_s']!r})")
        report("(b) retraces on the second batch", b["retraces"])
        report("(b) residuals", [float(r) for r in b["residuals"]])
        report("(b) tpu_custom_call in compiled step", b["kernel_in_step"])
        engines, tiles = engine_report()
        report("(b) engine", engines)
        report("(b) tiles", tiles)
        for key, val in engines.items():
            if val == "unfused":
                report("note", f"the engine autotune picked UNFUSED for "
                               f"{key}: that step runs no Pallas kernel")
        checks["(b) all served"] = b["served"] == REQUESTS \
            and b["pending"] == 0
        checks["(b) zero retraces"] = b["retraces"] == 0
        checks["(b) residuals"] = bool(np.all(b["residuals"] <= TOL))

        c = phase_kernel(sys_, a["factors"], prm["gamma"], args.seed)
        report("(c) tpu_custom_call in block_projection",
               c["tpu_custom_call"])
        report("(c) rel err vs float64 reference", repr(c["rel_err"]))
        checks["(c) kernel compiled"] = c["tpu_custom_call"]
        checks["(c) matches reference"] = c["rel_err"] <= TOL

    report("compile s total", f"{_COMPILE['seconds']!r} "
                              f"({_COMPILE['cache_hits']} persistent-cache "
                              f"hits)")
    report("peak_bytes_in_use", _peak_bytes())
    failed = [k for k, v in checks.items() if not v]
    report("failed checks", failed or "none")
    ok = not failed
    print(json.dumps({"ok": ok, "device": device}))
    return 0 if ok else 1


if __name__ == "__main__":
    raise SystemExit(main())
