"""Redundant straggler-tolerant execution (repro.solvers.redundant).

Contract under test (ISSUE 3 / ROADMAP "Redundant execution"):
``solve(sys, redundancy=r, alive_schedule=...)`` matches the no-failure
run to <= 1e-6 relative for every projection-family solver on BOTH
backends, states stay global-shaped so warm starts and checkpoints
round-trip across redundancy settings and backends, and uncoverable
alive-masks fail loudly.
"""
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from repro import solvers
from repro.checkpoint import ckpt
from repro.data import linsys
from repro.launch import mesh as mesh_lib
from repro.runtime import fault
from repro.solvers import redundant

PROJ = ["apc", "consensus", "cimmino"]
ITERS = 150

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ENV = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))


@pytest.fixture(scope="module")
def sys_():
    return linsys.conditioned_gaussian(n=64, m=4, cond=10.0, seed=3)


@pytest.fixture(scope="module")
def mesh():
    return mesh_lib.solver_mesh(1, 1)


def rotating_straggler(m):
    """Covering schedule: worker t mod m stalls at iteration t."""
    return lambda t: np.array([i != (t % m) for i in range(m)])


def _assert_match(r_red, r_ref):
    np.testing.assert_allclose(np.asarray(r_red.x), np.asarray(r_ref.x),
                               rtol=1e-8, atol=1e-10)
    # rtol 1e-6 is the contract; atol covers the converged noise floor.
    np.testing.assert_allclose(np.asarray(r_red.residuals),
                               np.asarray(r_ref.residuals),
                               rtol=1e-6, atol=1e-12)


@pytest.mark.parametrize("name", PROJ)
def test_redundant_local_matches_no_failure(sys_, name):
    """Exactness: a covered straggler every iteration changes nothing."""
    s = solvers.get(name)
    prm = s.resolve_params(sys_)
    r_ref = s.solve(sys_, iters=ITERS, **prm)
    r_red = s.solve(sys_, iters=ITERS, redundancy=2,
                    alive_schedule=rotating_straggler(sys_.m), **prm)
    assert r_red.name == name
    assert r_red.residuals.shape == (ITERS,)
    assert r_red.errors is not None
    _assert_match(r_red, r_ref)
    np.testing.assert_allclose(np.asarray(r_red.errors),
                               np.asarray(r_ref.errors),
                               rtol=1e-6, atol=1e-12)
    assert r_red.iters_to_tol == r_ref.iters_to_tol


@pytest.mark.parametrize("name", PROJ)
def test_redundant_mesh_matches_no_failure(sys_, mesh, name):
    s = solvers.get(name)
    prm = s.resolve_params(sys_)
    r_ref = s.solve(sys_, iters=ITERS, **prm)
    r_red = s.solve(sys_, iters=ITERS, redundancy=2, backend="mesh",
                    mesh=mesh, alive_schedule=rotating_straggler(sys_.m),
                    **prm)
    _assert_match(r_red, r_ref)
    assert r_red.errors is not None


@pytest.mark.parametrize("name", PROJ)
def test_redundant_state_is_global_shaped(sys_, name):
    """The SolveResult state has the PLAIN structure/shapes — replication
    is internal — so it is interchangeable with non-redundant states."""
    s = solvers.get(name)
    r_plain = s.solve(sys_, iters=10)
    r_red = s.solve(sys_, iters=10, redundancy=3)
    plain_shapes = jax.tree.map(lambda a: np.shape(a), r_plain.state)
    red_shapes = jax.tree.map(lambda a: np.shape(a), r_red.state)
    assert plain_shapes == red_shapes


def test_warm_start_roundtrips_across_redundancy(sys_):
    """plain -> redundant and redundant -> plain resume exactly."""
    s = solvers.get("apc")
    prm = s.resolve_params(sys_)
    sched = rotating_straggler(sys_.m)
    full = s.solve(sys_, iters=100, **prm)

    half = s.solve(sys_, iters=50, **prm)
    res = s.solve(sys_, iters=50, redundancy=2, alive_schedule=sched,
                  warm_state=half.state, **prm)
    np.testing.assert_allclose(np.asarray(res.x), np.asarray(full.x),
                               rtol=1e-8, atol=1e-10)
    assert int(res.state.t) == 100

    half_r = s.solve(sys_, iters=50, redundancy=2, alive_schedule=sched,
                     **prm)
    res2 = s.solve(sys_, iters=50, warm_state=half_r.state, **prm)
    np.testing.assert_allclose(np.asarray(res2.x), np.asarray(full.x),
                               rtol=1e-8, atol=1e-10)


def test_warm_start_roundtrips_across_backends(sys_, mesh):
    """redundant mesh <-> plain local warm starts agree with the
    uninterrupted plain run."""
    s = solvers.get("apc")
    prm = s.resolve_params(sys_)
    sched = rotating_straggler(sys_.m)
    full = s.solve(sys_, iters=100, **prm)

    half_m = s.solve(sys_, iters=50, redundancy=2, alive_schedule=sched,
                     backend="mesh", mesh=mesh, **prm)
    res_l = s.solve(sys_, iters=50, warm_state=jax.device_get(half_m.state),
                    **prm)
    np.testing.assert_allclose(np.asarray(res_l.x), np.asarray(full.x),
                               rtol=1e-8, atol=1e-10)

    half_l = s.solve(sys_, iters=50, **prm)
    res_m = s.solve(sys_, iters=50, redundancy=2, alive_schedule=sched,
                    backend="mesh", mesh=mesh, warm_state=half_l.state,
                    **prm)
    np.testing.assert_allclose(np.asarray(res_m.x), np.asarray(full.x),
                               rtol=1e-8, atol=1e-10)


def test_checkpoint_roundtrips_across_redundancy(sys_, tmp_path):
    s = solvers.get("apc")
    prm = s.resolve_params(sys_)
    r1 = s.solve(sys_, iters=40, redundancy=2,
                 alive_schedule=rotating_straggler(sys_.m), **prm)
    ckpt.save(str(tmp_path), 40, r1.state)
    restored = ckpt.restore(str(tmp_path), r1.state)
    r2 = s.solve(sys_, iters=40, redundancy=3, warm_state=restored, **prm)
    full = s.solve(sys_, iters=80, **prm)
    np.testing.assert_allclose(np.asarray(r2.x), np.asarray(full.x),
                               rtol=1e-8, atol=1e-10)


def test_heartbeat_monitor_drives_alive_mask(sys_):
    """A HeartbeatMonitor passed as alive_schedule: its drop_set() is the
    mask source, and a dead worker still yields the exact solution."""
    import time
    mon = fault.HeartbeatMonitor(n_workers=sys_.m, timeout=60.0)
    now = time.monotonic()
    for w in range(sys_.m):
        mon.beat(w, now=now, duration=1.0)
    mon.mark_dead(2)
    s = solvers.get("apc")
    prm = s.resolve_params(sys_)
    r_ref = s.solve(sys_, iters=ITERS, **prm)
    r_mon = s.solve(sys_, iters=ITERS, redundancy=2, alive_schedule=mon,
                    **prm)
    _assert_match(r_mon, r_ref)
    with pytest.raises(ValueError, match="HeartbeatMonitor"):
        wrong = fault.HeartbeatMonitor(n_workers=sys_.m + 1)
        s.solve(sys_, iters=5, redundancy=2, alive_schedule=wrong)


def test_array_schedules(sys_):
    """Static (m,) and per-iteration (T, m) mask arrays are accepted."""
    s = solvers.get("apc")
    prm = s.resolve_params(sys_)
    r_ref = s.solve(sys_, iters=60, **prm)
    static = np.array([True, False, True, True])   # worker 1 always out
    r1 = s.solve(sys_, iters=60, redundancy=2, alive_schedule=static, **prm)
    _assert_match(r1, r_ref)
    per_t = np.stack([np.roll(static, t) for t in range(60)])
    r2 = s.solve(sys_, iters=60, redundancy=2, alive_schedule=per_t, **prm)
    _assert_match(r2, r_ref)
    with pytest.raises(ValueError, match="shape"):
        s.solve(sys_, iters=60, redundancy=2,
                alive_schedule=np.ones((10, sys_.m), bool))


def test_uncoverable_mask_raises(sys_):
    s = solvers.get("apc")
    # r=2, workers 0 and 1 adjacent and both dead -> block 1 has no holder
    dead_pair = np.array([False, False, True, True])
    with pytest.raises(RuntimeError, match="unrecoverable"):
        s.solve(sys_, iters=10, redundancy=2, alive_schedule=dead_pair)
    # r=1 tolerates nothing: any straggler is fatal
    with pytest.raises(RuntimeError, match="unrecoverable"):
        s.solve(sys_, iters=10, redundancy=1,
                alive_schedule=rotating_straggler(sys_.m))
    # on the mesh backend too (lowering happens before placement)
    with pytest.raises(RuntimeError, match="unrecoverable"):
        s.solve(sys_, iters=10, redundancy=2, backend="mesh",
                alive_schedule=dead_pair)


def test_validation_errors(sys_):
    s = solvers.get("apc")
    with pytest.raises(ValueError, match="redundancy"):
        s.solve(sys_, iters=5, redundancy=sys_.m + 1)
    with pytest.raises(ValueError, match="use_kernel"):
        s.solve(sys_, iters=5, redundancy=2, use_kernel=True)
    with pytest.raises(ValueError, match="redundant"):
        solvers.get("dgd").solve(sys_, iters=5, redundancy=2)
    # solve_many must reject rather than silently drop the kwargs into
    # **params and run the batch without straggler tolerance
    B = np.ones((2, sys_.N))
    with pytest.raises(ValueError, match="solve_many"):
        s.solve_many(sys_, B, iters=5, redundancy=2)
    with pytest.raises(ValueError, match="solve_many"):
        s.solve_many(sys_, B, iters=5,
                     alive_schedule=rotating_straggler(sys_.m))


def test_selection_weights_match_legacy_semantics():
    """Vectorized lowering picks the lowest-index alive holder, each block
    exactly once, dead workers contributing nothing (the coding.py rule)."""
    m, r = 6, 3
    holder = redundant.Assignment(m=m, r=r).holder
    for trial in range(20):
        rng = np.random.default_rng(trial)
        alive = rng.random(m) > 0.3
        if not fault.covering_ok(alive, r):
            continue
        W = redundant.selection_weights(alive, m, r)
        per_block = np.zeros(m)
        np.add.at(per_block, holder.ravel(), W.ravel())
        np.testing.assert_allclose(per_block, 1.0)
        assert W[~alive].sum() == 0.0
        # lowest-index preference: the provider of block j is the first
        # alive worker in {j, j-1, ...} scanned by worker index
        for blk in range(m):
            cands = sorted((int((blk - k) % m), k) for k in range(r)
                           if alive[(blk - k) % m])
            i, k = cands[0]
            assert W[i, k] == 1.0


@pytest.mark.slow
def test_redundant_mesh_parity_2x2_subprocess():
    """Acceptance check: projection family, r=2, rotating straggler, on a
    4-device 2 x 2 (data x model) mesh — matches the no-failure local
    run's residual history."""
    code = """
import jax
jax.config.update('jax_enable_x64', True)
import numpy as np
from repro import solvers
from repro.data import linsys
from repro.launch.mesh import make_mesh

assert len(jax.devices()) == 4
sys_ = linsys.conditioned_gaussian(n=64, m=4, cond=10.0, seed=3)
mesh = make_mesh((2, 2), ('data', 'model'))
sched = lambda t: np.array([i != (t % 4) for i in range(4)])
for name in ['apc', 'consensus', 'cimmino']:
    s = solvers.get(name)
    prm = s.resolve_params(sys_)
    rl = s.solve(sys_, iters=150, **prm)
    rm = s.solve(sys_, iters=150, redundancy=2, alive_schedule=sched,
                 backend='mesh', mesh=mesh, **prm)
    assert np.allclose(np.asarray(rm.residuals), np.asarray(rl.residuals),
                       rtol=1e-6, atol=1e-12), name
    assert np.allclose(np.asarray(rm.x), np.asarray(rl.x),
                       rtol=1e-8, atol=1e-10), name
print('OK')
"""
    r = subprocess.run(
        [sys.executable, "-c", code],
        env=dict(ENV, XLA_FLAGS="--xla_force_host_platform_device_count=4"),
        capture_output=True, text=True, timeout=900)
    assert "OK" in r.stdout, r.stdout[-1000:] + r.stderr[-2000:]
