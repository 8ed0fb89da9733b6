"""Straggler smoke: apc r=2 under a rotating straggler is EXACT (equal to
the no-failure run) on the local backend and a forced 2x2 mesh."""
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


def main():
    t0 = time.time()
    assert len(jax.devices()) == 4, jax.devices()
    sys_ = linsys.conditioned_gaussian(n=64, m=4, cond=10.0, seed=3)
    mesh = make_mesh((2, 2), ("data", "model"))
    sched = lambda t: np.array([i != (t % 4) for i in range(4)])
    s = solvers.get("apc")
    prm = s.resolve_params(sys_)
    r0 = s.solve(sys_, iters=120, **prm)                       # no failures
    rl = s.solve(sys_, iters=120,
                 plan=solvers.ExecutionPlan(redundancy=2,
                                            alive_schedule=sched), **prm)
    rm = s.solve(sys_, iters=120,
                 plan=solvers.ExecutionPlan(redundancy=2,
                                            alive_schedule=sched,
                                            backend="mesh", mesh=mesh),
                 **prm)
    for r, tag in ((rl, "local"), (rm, "mesh")):
        assert np.allclose(np.asarray(r.residuals),
                           np.asarray(r0.residuals),
                           rtol=1e-6, atol=1e-12), tag
        assert np.allclose(np.asarray(r.x), np.asarray(r0.x),
                           rtol=1e-8, atol=1e-10), tag
    print(f"straggler smoke OK: apc r=2 exact under a rotating straggler "
          f"on local and {mesh} in {time.time()-t0:.1f}s")


if __name__ == "__main__":
    main()
