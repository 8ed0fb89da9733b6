"""Mesh-backend smoke: every registered solver sharded on a forced
4-host-device 2x2 (data x model) mesh matches the local driver."""
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
    for name in solvers.available():
        s = solvers.get(name)
        prm = s.resolve_params(sys_)
        rl = s.solve(sys_, iters=120, **prm)
        rm = s.solve(sys_, iters=120,
                     plan=solvers.ExecutionPlan(backend="mesh", mesh=mesh),
                     **prm)
        assert np.allclose(np.asarray(rm.residuals),
                           np.asarray(rl.residuals),
                           rtol=1e-6, atol=1e-12), name
        assert rm.errors is not None and rm.residuals.shape == (120,), name
    print(f"mesh smoke OK: {solvers.available()} sharded on {mesh} "
          f"in {time.time()-t0:.1f}s")


if __name__ == "__main__":
    main()
