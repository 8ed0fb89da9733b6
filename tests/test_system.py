"""End-to-end system tests: drivers, distributed equivalence (subprocess,
multi-device), consensus combinator, APC probe head."""
import os
import subprocess
import sys

import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ENV = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))


def _run(code, extra_env=None, timeout=600):
    env = dict(ENV, **(extra_env or {}))
    return subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=timeout)


@pytest.mark.slow
def test_distributed_apc_equals_reference_subprocess():
    """shard_map APC on an 8-device (4 data x 2 model) mesh == vmap APC."""
    code = """
import jax
jax.config.update('jax_enable_x64', True)
import numpy as np
from repro.data import linsys
from repro.core import apc, distributed
from repro.launch.mesh import make_mesh
mesh = make_mesh((4, 2), ('data', 'model'))
sys_ = linsys.conditioned_gaussian(n=128, m=4, cond=20.0, seed=1)
xbar, res = distributed.solve_on_mesh(mesh, sys_, iters=200)
ref = apc.solve(sys_, iters=200)
d = float(np.linalg.norm(np.asarray(xbar) - np.asarray(ref.x)))
assert d < 1e-10, d
assert res < 1e-9, res
print('OK')
"""
    r = _run(code, {"XLA_FLAGS": "--xla_force_host_platform_device_count=8"})
    assert "OK" in r.stdout, r.stderr[-2000:]


@pytest.mark.slow
def test_dryrun_single_cell_subprocess():
    """The dry-run entrypoint lowers+compiles a cell on the 512-device
    multi-pod mesh (the minimal multi-pod contract check in CI)."""
    r = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun", "--arch",
         "whisper-tiny", "--shape", "train_4k", "--multi-pod"],
        env=ENV, capture_output=True, text=True, timeout=900)
    assert "0 FAILED" in r.stdout, r.stdout[-2000:] + r.stderr[-2000:]


@pytest.mark.slow
def test_train_driver_checkpoints_and_resumes(tmp_path):
    d = str(tmp_path / "ck")
    args = ["-m", "repro.launch.train", "--arch", "mamba2-130m", "--smoke",
            "--steps", "6", "--batch", "2", "--seq", "32",
            "--ckpt-dir", d, "--ckpt-every", "3"]
    r1 = subprocess.run([sys.executable] + args, env=ENV,
                        capture_output=True, text=True, timeout=900)
    assert "checkpoint" in r1.stdout, r1.stderr[-2000:]
    args[args.index("6")] = "8"
    r2 = subprocess.run([sys.executable] + args, env=ENV,
                        capture_output=True, text=True, timeout=900)
    assert "resumed from step 6" in r2.stdout, r2.stdout


@pytest.mark.slow
def test_elastic_remesh_resume_subprocess(tmp_path):
    """Full fault-tolerance cycle: solve on a 4-worker-shard mesh,
    checkpoint, 'lose' half the devices, resume the SAME solver state on a
    2-shard mesh — final iterate matches an uninterrupted run."""
    code = f"""
import jax
jax.config.update('jax_enable_x64', True)
import numpy as np
import jax.numpy as jnp
from repro.checkpoint import ckpt
from repro.core import distributed, spectral
from repro.data import linsys
from repro.launch.mesh import make_mesh
from repro.runtime import fault

sys_ = linsys.conditioned_gaussian(n=64, m=4, cond=10.0, seed=5)
prm = spectral.apc_optimal(*spectral.mu_extremes(spectral.x_matrix(sys_)))

def run(mesh_shape, x, xbar, iters):
    mesh = make_mesh(mesh_shape, ('data', 'model'))
    s = distributed.make_sharded_apc(mesh, gamma=prm.gamma, eta=prm.eta)
    A_, b, chol, x0, xb0 = distributed.prepare_on_mesh(s, sys_)
    step = s.step_fn()
    if x is None:
        x, xbar = x0, xb0
    else:
        x, xbar = jnp.asarray(x), jnp.asarray(xbar)

    @jax.jit
    def many(A_, chol, x, xbar):
        def body(carry, _):
            x, xbar = carry
            return step(A_, chol, x, xbar), None
        (x, xbar), _ = jax.lax.scan(body, (x, xbar), None, length=iters)
        return x, xbar

    x, xbar = many(A_, chol, x, xbar)
    return np.asarray(x), np.asarray(xbar)

# uninterrupted reference: 100 iters on the big mesh
xr, xbr = run((4, 1), None, None, 100)
# interrupted: 50 iters, checkpoint, device loss -> plan -> resume on (2,1)
x1, xb1 = run((4, 1), None, None, 50)
ckpt.save('{tmp_path}', 50, {{'x': x1, 'xbar': xb1}})
plan = fault.ElasticPlan.shrink(n_devices_left=2, model=1)
assert (plan.data, plan.model) == (2, 1)
st = ckpt.restore('{tmp_path}', {{'x': x1 * 0, 'xbar': xb1 * 0}})
x2, xb2 = run((plan.data, plan.model), st['x'], st['xbar'], 50)
d = float(np.abs(xb2 - xbr).max())
assert d < 1e-9, d
print('OK', d)
"""
    r = _run(code, {"XLA_FLAGS": "--xla_force_host_platform_device_count=4"})
    assert "OK" in r.stdout, r.stdout[-1000:] + r.stderr[-2000:]


def test_solve_driver_inline():
    from repro.launch import solve
    assert solve.main(["--problem", "ash608", "--workers", "4",
                       "--iters", "200"]) == 0


def test_consensus_combinator_reproduces_apc():
    """core/consensus.py with the APC local step == core/apc.py."""
    from repro.core import apc, consensus
    from repro.data import linsys
    sys_ = linsys.conditioned_gaussian(n=48, m=4, cond=8.0, seed=2)
    factors = apc.prepare(sys_)
    state = apc.init_state(factors)
    gamma, eta = 1.3, 1.2

    def local_step(ctx, xi, xbar):
        A, L = ctx
        d = xbar - xi
        return xi + gamma * apc.project_nullspace(A, L, d)

    xs = factors.x0
    xbar = jnp.mean(factors.x0, axis=0)
    xs, xbar = consensus.run_consensus(local_step, xs, xbar, eta=eta,
                                       rounds=50,
                                       context=(factors.A, factors.chol))
    s = state
    for _ in range(50):
        s = apc.apc_step(factors, s, gamma, eta)
    np.testing.assert_allclose(np.asarray(xbar), np.asarray(s.xbar),
                               rtol=1e-10, atol=1e-12)


def test_apc_probe_head_fits_ridge():
    """optim/apc_head: APC solves the normal equations of a ridge probe to
    the same solution as the closed form."""
    from repro.optim import apc_head
    rng = np.random.default_rng(0)
    T, n = 256, 32
    H = jnp.asarray(rng.standard_normal((T, n)))
    w_true = jnp.asarray(rng.standard_normal(n))
    y = H @ w_true + 0.01 * jnp.asarray(rng.standard_normal(T))
    w, res = apc_head.fit_probe(H, y, m=4, lam=1e-2, iters=400)
    A, b = apc_head.normal_system(H, y, 1e-2)
    w_ref = jnp.linalg.solve(A, b)
    np.testing.assert_allclose(np.asarray(w), np.asarray(w_ref), rtol=1e-6,
                               atol=1e-8)
