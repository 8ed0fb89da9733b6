"""Elastic runtime (repro.solvers.elastic).

Contract under test (ISSUE 10 / ROADMAP "Elastic runtime"):
``ElasticRuntime`` keeps a solve making progress across membership
events from the ``HeartbeatMonitor`` stream —

  * permanent DEATH re-lowers the selection-weight schedule over the
    survivors and continues from the live state, matching the
    uninterrupted oracle run (and bit-matching the fixed-schedule
    redundant path) on the local backend and a forced 2x2 mesh;
  * a JOIN that grows the fleet repartitions the global system, lifts
    the iterate into the new layout, and reuses per-block factors
    through the FactorStore block tier (reuse vs refactorization counts
    are part of the contract); a returnee to the current fleet size is
    a pure reassignment — state and compiled scan untouched;
  * TASKMASTER LOSS recovers from the store's disk tier plus the
    checkpointed iterate, counting the factor rebuild as block reuse;
  * an uncoverable survivor set fails LOUDLY with a RuntimeError;
  * membership changes never cost a steady-state retrace: one engine
    per fleet size, cache sizes flat across segments.
"""
import os
import subprocess
import sys

import numpy as np
import pytest

from repro import solvers
from repro.checkpoint import ckpt
from repro.data import linsys
from repro.runtime.fault import HeartbeatMonitor
from repro.solvers.capability import CapabilityError, ExecutionPlan
from repro.solvers.store import FactorStore

PROJ = ["apc", "consensus", "cimmino"]
ITERS = 150

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ENV = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))


@pytest.fixture(scope="module")
def sys_():
    return linsys.conditioned_gaussian(n=64, m=4, cond=10.0, seed=3)


def _runtime(solver, sys_, *, redundancy=2, segment=25, monitor=None,
             plan=None, **kw):
    monitor = HeartbeatMonitor(n_workers=sys_.m) if monitor is None \
        else monitor
    plan = ExecutionPlan(redundancy=redundancy) if plan is None else plan
    prm = solver.resolve_params(sys_)
    return solvers.ElasticRuntime(solver, sys_, plan=plan, monitor=monitor,
                                  segment=segment, **prm, **kw), monitor


# ----------------------------------------------------------------- death
@pytest.mark.parametrize("name", PROJ)
def test_death_relower_continues_exactly(sys_, name):
    """Death mid-run: the schedule re-lowers over the survivors and the
    residual history equals the uninterrupted oracle's."""
    s = solvers.get(name)
    prm = s.resolve_params(sys_)
    oracle = s.solve(sys_, iters=ITERS, plan=ExecutionPlan(), **prm)
    rt, mon = _runtime(s, sys_)
    rep1 = rt.run(iters=50)
    assert rep1.relowerings == 0 and rep1.segments == 2
    mon.mark_dead(2)
    rep2 = rt.run(iters=ITERS - 50)
    assert rep2.relowerings == 1
    assert rep2.iters == ITERS
    assert [e.kind for e in rep2.events] == ["died"]
    res = np.concatenate([np.asarray(rep1.residuals),
                          np.asarray(rep2.residuals)])
    np.testing.assert_allclose(res, np.asarray(oracle.residuals),
                               rtol=1e-6, atol=1e-12)
    np.testing.assert_allclose(np.asarray(rep2.x), np.asarray(oracle.x),
                               rtol=1e-8, atol=1e-10)


def test_death_bit_matches_fixed_schedule_path(sys_):
    """The elastic death path and the one-shot solve(redundancy=2,
    alive_schedule=...) lower IDENTICAL weight schedules — bit-equal x."""
    s = solvers.get("apc")
    prm = s.resolve_params(sys_)
    mask = np.array([True, True, False, True])
    sched = np.stack([np.ones(4, bool)] * 50 + [mask] * 100)
    ref = s.solve(sys_, iters=ITERS,
                  plan=ExecutionPlan(redundancy=2, alive_schedule=sched),
                  **prm)
    rt, mon = _runtime(s, sys_)
    rt.run(iters=50)
    mon.mark_dead(2)
    rep = rt.run(iters=100)
    assert np.array_equal(np.asarray(rep.x), np.asarray(ref.x))


def test_rejoin_same_size_is_pure_reassignment(sys_):
    """A returnee to the current fleet size changes holders only: no
    repartition, no state perturbation, oracle parity still holds."""
    s = solvers.get("apc")
    prm = s.resolve_params(sys_)
    oracle = s.solve(sys_, iters=ITERS, plan=ExecutionPlan(), **prm)
    rt, mon = _runtime(s, sys_)
    rt.run(iters=50)
    mon.mark_dead(1)
    rt.run(iters=50)
    mon.rejoin(1, resynced=True)
    rep = rt.run(iters=50)
    assert rep.repartitions == 0 and rep.relowerings == 1
    assert rep.fleet == (0, 1, 2, 3)
    np.testing.assert_allclose(np.asarray(rep.x), np.asarray(oracle.x),
                               rtol=1e-8, atol=1e-10)
    # the same engine served all three runs: exactly one per fleet size
    assert list(rt.engine_cache_sizes()) == [4]


# ------------------------------------------------------------------ join
def test_join_repartitions_lifts_and_counts_factor_work(sys_):
    """Fleet growth repartitions the rows, warm-starts via lift_state,
    and reports factor reuse vs refactorization exactly."""
    s = solvers.get("apc")
    rt, mon = _runtime(s, sys_)
    assert rt.prepared_blocks == sys_.m and rt.reused_blocks == 0
    rt.run(iters=100)
    w = mon.join(resynced=True)
    assert w == sys_.m
    rep = rt.run(iters=200)
    assert rep.repartitions == 1
    assert rep.fleet == (0, 1, 2, 3, 4)
    assert rt.sys.m == 5
    # 4 blocks prepared at construction + 5 for the new layout (padded
    # rows -> new fingerprints, so zero block reuse on a fresh store)
    assert rep.prepared_blocks == 9 and rep.reused_blocks == 0
    x = np.asarray(rep.x)
    xt = np.asarray(sys_.x_true)
    assert np.linalg.norm(x - xt) / np.linalg.norm(xt) <= 1e-6
    # revisiting a fleet size reuses its cached engine: sizes stay flat
    sizes = dict(rt.engine_cache_sizes())
    mon.mark_dead(4)
    rt.run(iters=25)
    mon.rejoin(4, resynced=True)
    rep2 = rt.run(iters=25)
    assert rep2.repartitions == 1          # cumulative: no new repartition
    assert dict(rt.engine_cache_sizes()) == sizes


# ------------------------------------------------- taskmaster loss
def test_taskmaster_recovery_from_disk_tier(sys_, tmp_path):
    """A fresh process rebuilds the runtime from the store's disk tier
    (all blocks come back as reuse) plus the checkpointed iterate."""
    s = solvers.get("apc")
    store_dir, ck_dir = str(tmp_path / "store"), str(tmp_path / "ck")
    prm = s.resolve_params(sys_)
    oracle = s.solve(sys_, iters=300, plan=ExecutionPlan(), **prm)

    rt, _ = _runtime(s, sys_,
                     plan=ExecutionPlan(redundancy=2,
                                        store=FactorStore(directory=store_dir)),
                     checkpoint_dir=ck_dir)
    rt.run(iters=150)
    del rt                                          # the taskmaster dies

    rt2 = solvers.ElasticRuntime.recover(
        s, sys_, ck_dir,
        plan=ExecutionPlan(redundancy=2,
                           store=FactorStore(directory=store_dir)),
        monitor=HeartbeatMonitor(n_workers=sys_.m), **prm)
    assert rt2.reused_blocks == sys_.m and rt2.prepared_blocks == 0
    rep = rt2.run(iters=150)
    assert rep.iters == 300                         # cumulative across loss
    x = np.asarray(rep.x)
    np.testing.assert_allclose(x, np.asarray(oracle.x),
                               rtol=1e-6, atol=1e-10)
    assert float(rep.residuals[-1]) <= 1e-6


def test_checkpoint_roundtrips_across_membership_change(sys_, tmp_path):
    """checkpoint() after a join still restores onto a FRESH base-size
    fleet: the iterate is global-shaped, so the partition lifts it."""
    s = solvers.get("apc")
    prm = s.resolve_params(sys_)
    d = str(tmp_path)
    rt, mon = _runtime(s, sys_, checkpoint_dir=d)
    rt.run(iters=50)
    mon.join(resynced=True)
    rep = rt.run(iters=50)
    assert rep.repartitions == 1 and rt.sys.m == 5
    assert ckpt.latest_step(d) == 100

    rt2 = solvers.ElasticRuntime.recover(
        s, sys_, d, plan=ExecutionPlan(redundancy=2),
        monitor=HeartbeatMonitor(n_workers=sys_.m), **prm)
    assert rt2.sys.m == sys_.m                      # fresh 4-worker fleet
    rep2 = rt2.run(iters=200)
    assert rep2.iters == 300
    x, xt = np.asarray(rep2.x), np.asarray(sys_.x_true)
    assert np.linalg.norm(x - xt) / np.linalg.norm(xt) <= 1e-6


# ------------------------------------------------------- loud failures
def test_uncoverable_survivors_raise(sys_):
    s = solvers.get("apc")
    rt, mon = _runtime(s, sys_)
    rt.run(iters=25)
    mon.mark_dead(0)
    mon.mark_dead(1)                   # r=2: adjacent pair -> block lost
    with pytest.raises(RuntimeError, match="uncoverable"):
        rt.run(iters=25)


def test_validation(sys_):
    s = solvers.get("apc")
    mon = HeartbeatMonitor(n_workers=sys_.m)
    with pytest.raises(TypeError, match="ExecutionPlan"):
        solvers.ElasticRuntime(s, sys_, plan={"redundancy": 2}, monitor=mon)
    with pytest.raises(ValueError, match="alive_schedule"):
        solvers.ElasticRuntime(
            s, sys_, monitor=mon,
            plan=ExecutionPlan(redundancy=2,
                               alive_schedule=np.ones(4, bool)))
    with pytest.raises(CapabilityError, match="kernel"):
        solvers.ElasticRuntime(
            s, sys_, monitor=mon,
            plan=ExecutionPlan(redundancy=2, kernel=True))
    with pytest.raises(ValueError, match="monitor|workers"):
        solvers.ElasticRuntime(
            s, sys_, monitor=HeartbeatMonitor(n_workers=sys_.m + 1),
            plan=ExecutionPlan(redundancy=2))


# ---------------------------------------------------------------- mesh
@pytest.mark.slow
def test_elastic_death_parity_2x2_subprocess():
    """Acceptance: death -> re-lower -> continue on a forced 4-device
    2 x 2 (data x model) mesh matches the uninterrupted local oracle."""
    code = """
import jax
jax.config.update('jax_enable_x64', True)
import numpy as np
from repro import solvers
from repro.data import linsys
from repro.launch.mesh import make_mesh
from repro.runtime.fault import HeartbeatMonitor

assert len(jax.devices()) == 4
sys_ = linsys.conditioned_gaussian(n=64, m=4, cond=10.0, seed=3)
mesh = make_mesh((2, 2), ('data', 'model'))
for name in ['apc', 'consensus', 'cimmino']:
    s = solvers.get(name)
    prm = s.resolve_params(sys_)
    oracle = s.solve(sys_, iters=150, plan=solvers.ExecutionPlan(), **prm)
    mon = HeartbeatMonitor(n_workers=4)
    rt = solvers.ElasticRuntime(
        s, sys_, monitor=mon, segment=25,
        plan=solvers.ExecutionPlan(redundancy=2, backend='mesh', mesh=mesh),
        **prm)
    r1 = rt.run(iters=50)
    mon.mark_dead(2)
    r2 = rt.run(iters=100)
    assert r2.relowerings == 1, name
    res = np.concatenate([np.asarray(r1.residuals), np.asarray(r2.residuals)])
    assert np.allclose(res, np.asarray(oracle.residuals),
                       rtol=1e-6, atol=1e-12), name
    assert np.allclose(np.asarray(r2.x), np.asarray(oracle.x),
                       rtol=1e-8, atol=1e-10), name
print('OK')
"""
    r = subprocess.run(
        [sys.executable, "-c", code],
        env=dict(ENV, XLA_FLAGS="--xla_force_host_platform_device_count=4"),
        capture_output=True, text=True, timeout=900)
    assert "OK" in r.stdout, r.stdout[-1000:] + r.stderr[-2000:]
