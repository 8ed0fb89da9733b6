"""System-mode scenarios smoke: the three system classes — dense square,
least-squares, and block-sparse — end-to-end through the unified API on
BOTH backends (4 forced host devices, 2x2 data x model mesh), plus the
streaming mode: solve_stream drives 100 perturbed-b requests through the
sync and async servers with zero steady-state retraces and warm hits on
every warm_rhs_ok batch after the first."""
import os

os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=4")

import time  # noqa: E402

import _path  # noqa: F401

import jax  # noqa: E402

jax.config.update("jax_enable_x64", True)

import numpy as np  # noqa: E402

from repro import solvers  # noqa: E402
from repro.data import linsys  # noqa: E402
from repro.launch.mesh import make_mesh  # noqa: E402
from repro.solvers import (AsyncLinsysServer, CapabilityError,  # noqa: E402
                           FactorStore, LinsysServer, solve_stream)

N_REQ = 100


def _rel(x, ref):
    return float(np.linalg.norm(np.asarray(x) - np.asarray(ref))
                 / np.linalg.norm(np.asarray(ref)))


def sparse_scenario(mesh):
    sys_ = linsys.banded_system(n=256, m=4, bandwidth=8, seed=0)
    assert sys_.is_sparse and sys_.sparsity > 0.8
    for name in ("apc", "cimmino", "dgd"):
        s = solvers.get(name)
        prm = s.resolve_params(sys_)
        r_sp = s.solve(sys_, iters=150, **prm)
        r_dn = s.solve(sys_.densified(), iters=150, **prm)
        assert np.allclose(np.asarray(r_sp.residuals),
                           np.asarray(r_dn.residuals),
                           rtol=1e-6, atol=1e-12), name
        r_mesh = s.solve(sys_, iters=150,
                         plan=solvers.ExecutionPlan(backend="mesh",
                                                    mesh=mesh), **prm)
        assert np.allclose(np.asarray(r_mesh.x), np.asarray(r_sp.x),
                           rtol=1e-8, atol=1e-10), name
    try:
        solvers.get("pdhbm").solve(sys_, iters=5)
    except CapabilityError:
        pass
    else:
        raise AssertionError("pdhbm accepted a sparse system")
    return f"sparse OK ({sys_.sparsity:.0%} zero, local+mesh parity)"


def ls_scenario(mesh):
    sys_ = linsys.tall_gaussian(N=320, n=160, m=4, seed=0, noise=0.05)
    assert sys_.mode == "least_squares"
    A, b = map(np.asarray, sys_.dense())
    x_ls, *_ = np.linalg.lstsq(A, b, rcond=None)
    for name in ("dgd", "dhbm"):
        s = solvers.get(name)
        prm = s.resolve_params(sys_)
        for plan in (solvers.ExecutionPlan(),
                     solvers.ExecutionPlan(backend="mesh", mesh=mesh)):
            r = s.solve(sys_, iters=800, plan=plan, **prm)
            assert _rel(r.x, x_ls) < 1e-6, (name, plan.backend)
            assert r.residuals[-1] < 1e-8, (name, plan.backend)
    # Cimmino's Gram-weighted fixed point, against its own reference
    s = solvers.get("cimmino")
    r = s.solve(sys_, iters=800, **s.resolve_params(sys_))
    assert _rel(r.x, s.ls_reference(sys_)) < 1e-6
    try:
        solvers.get("apc").solve(sys_, iters=5)
    except CapabilityError:
        pass
    else:
        raise AssertionError("apc accepted a least-squares system")
    return "least-squares OK (lstsq parity, local+mesh)"


def stream_scenario():
    sys_ = linsys.conditioned_gaussian(n=64, m=4, cond=10.0, seed=0)
    rng = np.random.default_rng(0)
    b0 = rng.standard_normal(64)
    msgs = []
    for tag, srv in (
        ("sync", LinsysServer(FactorStore(), solver="dhbm", iters=150,
                              batch=1, warm_start=True)),
        ("async", AsyncLinsysServer(FactorStore(), solver="dhbm",
                                    iters=150, batch=1, warm_start=True)),
    ):
        fp = srv.register(sys_)
        stream = [(fp, b0 + 1e-3 * rng.standard_normal(64))
                  for _ in range(N_REQ)]
        # prime the cold AND warm executor paths (one batch each), then
        # the steady-state jit cache must not grow
        solve_stream(srv, stream[:2])
        cache0 = srv.jit_cache_size()
        rep = solve_stream(srv, stream[2:])
        if hasattr(srv, "close"):
            srv.close()
        assert len(rep.served) == N_REQ - 2, tag
        assert rep.warm_batches == rep.batches, tag   # every batch warm
        assert all(r.warm for r in rep.served), tag
        assert all(r.residual < 1e-8 for r in rep.served), tag
        cache1 = srv.jit_cache_size()
        assert cache0 < 0 or cache1 == cache0, \
            f"{tag}: steady-state retrace, jit cache {cache0} -> {cache1}"
        msgs.append(f"{tag} warm rate {rep.warm_hit_rate:.0%}")
    return f"stream OK ({N_REQ} perturbed-b requests, " + ", ".join(msgs) + ")"


def main():
    t0 = time.time()
    assert len(jax.devices()) == 4, jax.devices()
    mesh = make_mesh((2, 2), ("data", "model"))
    lines = [sparse_scenario(mesh), ls_scenario(mesh), stream_scenario()]
    for ln in lines:
        print("  " + ln)
    print(f"scenarios smoke OK in {time.time()-t0:.1f}s")


if __name__ == "__main__":
    main()
