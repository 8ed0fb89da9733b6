"""AsyncLinsysServer: pipelined serving must preserve every contract of
the sync server — grouping, results, warm gating, zero-retrace — while
adding backpressure (explicit Shed), per-request futures, and the SLO
latency report."""
import os
import re
import subprocess
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis import tracecheck
from repro.data import linsys
from repro.runtime import spans
from repro.solvers.pipeline import AsyncLinsysServer, Shed
from repro.solvers.serve import LinsysServer
from repro.solvers.store import FactorStore

ROOT = Path(__file__).resolve().parents[1]
PRM = {"gamma": 1.0, "eta": 1.0}     # shared explicit params: one
                                     # executor across same-shape systems


@pytest.fixture(scope="module")
def sys_a():
    return linsys.conditioned_gaussian(n=48, m=4, cond=10.0, seed=0)


@pytest.fixture(scope="module")
def sys_b():
    return linsys.conditioned_gaussian(n=48, m=4, cond=10.0, seed=1)


def _drive(srv, fps, order, rhs):
    """Submit everything, then drain: with the full backlog queued before
    the pipeline starts, the assembly thread's grouping is deterministic
    and identical to the sync step() loop."""
    tickets = [srv.submit(fps[i], b) for i, b in zip(order, rhs)]
    out = srv.drain()
    srv.close()
    return tickets, out


# ---------------------------------------------------------------------------
# parity with the sync server
# ---------------------------------------------------------------------------


def test_async_matches_sync_bit_equal(sys_a, sys_b):
    rng = np.random.default_rng(0)
    order = [0, 0, 1, 0, 1, 1, 0, 1]
    rhs = [rng.standard_normal(48) for _ in order]

    sync = LinsysServer(FactorStore(), solver="apc", iters=40, batch=2,
                        **PRM)
    fps = [sync.register(sys_a), sync.register(sys_b)]
    for i, b in zip(order, rhs):
        sync.submit(fps[i], b)
    ref = {r.rid: r for r in sync.drain()}

    asrv = AsyncLinsysServer(FactorStore(), solver="apc", iters=40,
                             batch=2, pipeline_depth=2, **PRM)
    afps = [asrv.register(sys_a), asrv.register(sys_b)]
    _, out = _drive(asrv, afps, order, rhs)

    assert [r.rid for r in out] == list(range(len(order)))
    for r in out:
        assert np.array_equal(r.x, ref[r.rid].x)
        assert r.residual == ref[r.rid].residual
        assert r.fp == ref[r.rid].fp
    assert asrv.stats.served == len(order)
    assert asrv.stats.shed == 0


def test_ticket_futures_stream_results(sys_a):
    srv = AsyncLinsysServer(FactorStore(), solver="apc", iters=20, batch=2,
                            **PRM)
    fp = srv.register(sys_a)
    rng = np.random.default_rng(1)
    with srv:
        tickets = [srv.submit(fp, rng.standard_normal(48))
                   for _ in range(4)]
        results = [t.result(timeout=60) for t in tickets]
    for t, r in zip(tickets, results):
        assert r.rid == t.rid and r.fp == fp
        assert np.isfinite(r.residual)
    rep = srv.latency_report()
    assert rep["count"] == 4
    assert rep["p99_ms"] >= rep["p50_ms"] > 0


# ---------------------------------------------------------------------------
# admission control / backpressure
# ---------------------------------------------------------------------------


def test_backpressure_sheds_exactly_beyond_capacity(sys_a):
    srv = AsyncLinsysServer(FactorStore(), solver="apc", iters=10, batch=2,
                            admit_capacity=4, **PRM)
    fp = srv.register(sys_a)
    rng = np.random.default_rng(2)
    # 10 submits against capacity 4 BEFORE the pipeline starts: exactly
    # the first 4 admit, the other 6 shed with already-resolved futures
    tickets = [srv.submit(fp, rng.standard_normal(48)) for _ in range(10)]
    for t in tickets[4:]:
        assert t.future.done()
        assert isinstance(t.result(), Shed)
    assert srv.stats.admitted == 4 and srv.stats.shed == 6

    out = srv.drain()
    srv.close()
    assert [r.rid for r in out] == list(range(10))      # rid order kept
    assert all(not isinstance(r, Shed) for r in out[:4])
    assert all(isinstance(r, Shed) for r in out[4:])
    assert srv.stats.served == 4
    # latency is recorded for ADMITTED requests only
    assert srv.latency_report()["count"] == 4


def test_capacity_frees_as_requests_complete(sys_a):
    srv = AsyncLinsysServer(FactorStore(), solver="apc", iters=10, batch=2,
                            admit_capacity=2, **PRM)
    fp = srv.register(sys_a)
    rng = np.random.default_rng(3)
    with srv:
        first = [srv.submit(fp, rng.standard_normal(48)) for _ in range(2)]
        for t in first:
            assert not isinstance(t.result(timeout=60), Shed)
        # the pipeline drained: capacity is available again
        again = srv.submit(fp, rng.standard_normal(48))
        assert not isinstance(again.result(timeout=60), Shed)
    assert srv.stats.shed == 0 and srv.stats.served == 3


def test_async_validation_shares_sync_guards(sys_a):
    srv = AsyncLinsysServer(FactorStore(), solver="apc", iters=5, batch=2,
                            **PRM)
    fp = srv.register(sys_a)
    with pytest.raises(KeyError, match="deadbeef"):
        srv.submit("deadbeef", np.zeros(48))
    with pytest.raises(ValueError, match="shape"):
        srv.submit(fp, np.zeros(7))
    with pytest.raises(ValueError, match="pipeline_depth"):
        AsyncLinsysServer(FactorStore(), pipeline_depth=0)
    with pytest.raises(ValueError, match="admit_capacity"):
        AsyncLinsysServer(FactorStore(), admit_capacity=0)


# ---------------------------------------------------------------------------
# lifecycle
# ---------------------------------------------------------------------------


def test_empty_drain_and_close_are_noops():
    srv = AsyncLinsysServer(FactorStore(), solver="apc", iters=5, **PRM)
    assert srv.drain() == []
    srv.close()                                   # never started: no-op
    assert srv._assembler is None                 # no threads were spun up
    assert srv.stats.executor_builds == 0


def test_step_is_not_part_of_the_async_surface(sys_a):
    srv = AsyncLinsysServer(FactorStore(), solver="apc", iters=5, **PRM)
    with pytest.raises(RuntimeError, match="submit"):
        srv.step()


def test_context_manager_drains_on_exit(sys_a):
    srv = AsyncLinsysServer(FactorStore(), solver="apc", iters=10, batch=2,
                            **PRM)
    fp = srv.register(sys_a)
    rng = np.random.default_rng(4)
    with srv:
        tickets = [srv.submit(fp, rng.standard_normal(48))
                   for _ in range(3)]
    # __exit__ drained the pipeline: every future resolved
    assert all(t.future.done() for t in tickets)
    assert srv.stats.served == 3


# ---------------------------------------------------------------------------
# zero steady-state retraces
# ---------------------------------------------------------------------------


def test_async_zero_retrace_steady_state(sys_a, sys_b):
    srv = AsyncLinsysServer(FactorStore(), solver="apc", iters=10, batch=2,
                            pipeline_depth=2, **PRM)
    fps = [srv.register(sys_a), srv.register(sys_b)]
    rng = np.random.default_rng(5)
    with srv:
        # warmup: one group per system compiles the shared executor
        for fp in fps:
            ts = [srv.submit(fp, rng.standard_normal(48)) for _ in range(2)]
            for t in ts:
                t.result(timeout=60)
        # steady state: a retrace ANYWHERE in the pipeline (assembly
        # thread or device pool) fails with its attributed call site
        with tracecheck(steady_state=True):
            for i in range(5):
                ts = [srv.submit(fps[i % 2], rng.standard_normal(48))
                      for _ in range(2)]
                for t in ts:
                    t.result(timeout=60)
    assert srv.stats.executor_builds == 1


# ---------------------------------------------------------------------------
# warm starts through the pipeline
# ---------------------------------------------------------------------------


def test_async_warm_chaining_repeated_rhs(sys_a):
    srv = AsyncLinsysServer(FactorStore(), solver="apc", iters=30, batch=1,
                            warm_start=True, **PRM)
    fp = srv.register(sys_a)
    b = np.random.default_rng(6).standard_normal(48)
    with srv:
        first = srv.submit(fp, b).result(timeout=60)
        second = srv.submit(fp, b).result(timeout=60)
    # warm chaining serialized the same-system batches: the repeat resumed
    assert not first.warm and second.warm
    assert second.residual < first.residual
    assert srv.stats.warm_batches == 1


def test_async_warm_mixed_traffic_matches_sync(sys_a):
    """Interleaved repeated/perturbed RHS through BOTH servers: identical
    warm/cold gating and bit-equal solutions step by step."""
    rng = np.random.default_rng(7)
    b0 = rng.standard_normal(48)
    b1 = b0 + 1e-3 * rng.standard_normal(48)
    seq = [b0, b0, b1, b1, b0]            # repeat, perturb, repeat, back

    sync = LinsysServer(FactorStore(), solver="apc", iters=30, batch=1,
                        warm_start=True, **PRM)
    fs = sync.register(sys_a)
    ref = []
    for b in seq:
        sync.submit(fs, b)
        ref.append(sync.drain()[0])

    asrv = AsyncLinsysServer(FactorStore(), solver="apc", iters=30,
                             batch=1, warm_start=True, **PRM)
    fa = asrv.register(sys_a)
    with asrv:
        out = [asrv.submit(fa, b).result(timeout=60) for b in seq]

    # APC gates perturbed RHS cold; repeats chain warm — same pattern,
    # bit-equal states either way
    assert [r.warm for r in out] == [r.warm for r in ref] == \
        [False, True, False, True, False]
    for r, e in zip(out, ref):
        assert np.array_equal(r.x, e.x)
        assert r.residual == e.residual


# ---------------------------------------------------------------------------
# backend / kernel composition
# ---------------------------------------------------------------------------


def test_async_mesh_matches_local(sys_a):
    rng = np.random.default_rng(8)
    rhs = [rng.standard_normal(48) for _ in range(4)]
    out = {}
    for backend in ("local", "mesh"):
        srv = AsyncLinsysServer(FactorStore(), solver="apc", iters=60,
                                batch=2, backend=backend, **PRM)
        fp = srv.register(sys_a)
        _, out[backend] = _drive(srv, [fp] * 4, [0] * 4, rhs)
    for rl, rm in zip(out["local"], out["mesh"]):
        assert np.allclose(rl.x, rm.x, rtol=1e-8, atol=1e-10)
        assert rm.residual == pytest.approx(rl.residual, rel=1e-6)


def test_async_use_kernel_matches_sync(sys_a):
    rng = np.random.default_rng(9)
    rhs = [rng.standard_normal(48) for _ in range(4)]

    sync = LinsysServer(FactorStore(), solver="apc", iters=40, batch=2,
                        use_kernel=True, **PRM)
    fp = sync.register(sys_a)
    for b in rhs:
        sync.submit(fp, b)
    ref = sync.drain()

    asrv = AsyncLinsysServer(FactorStore(), solver="apc", iters=40,
                             batch=2, use_kernel=True, **PRM)
    afp = asrv.register(sys_a)
    _, out = _drive(asrv, [afp] * 4, [0] * 4, rhs)
    for r, e in zip(out, ref):
        assert np.array_equal(r.x, e.x)
        assert r.residual == e.residual


# ---------------------------------------------------------------------------
# per-request stage clocks
# ---------------------------------------------------------------------------


def test_stage_stamps_are_ordered(sys_a):
    srv = AsyncLinsysServer(FactorStore(), solver="apc", iters=10, batch=2,
                            **PRM)
    fp = srv.register(sys_a)
    rng = np.random.default_rng(10)
    with srv:
        tickets = [srv.submit(fp, rng.standard_normal(48))
                   for _ in range(5)]
        for t in tickets:
            t.result(timeout=60)
    st = srv.stages()
    assert sorted(st["rid"].tolist()) == [t.rid for t in tickets]
    assert np.all(st["t_submit"] <= st["t_taken"])
    assert np.all(st["t_taken"] <= st["t_dispatch"])
    assert np.all(st["t_dispatch"] <= st["t_done"])
    submitted = {t.rid: t.t_submit for t in tickets}
    assert [submitted[r] for r in st["rid"]] == st["t_submit"].tolist()
    # one batch id per group of two, shared by its requests' stamps
    for b in np.unique(st["batch"]):
        same = st["batch"] == b
        assert 1 <= same.sum() <= 2
        for name in ("t_taken", "t_dispatch", "t_done"):
            assert np.unique(st[name][same]).size == 1
    srv.reset_metrics()
    assert all(v.size == 0 for v in srv.stages().values())
    assert np.isnan(srv.stage_means()["queue_ms"])


class _FixedExecutor:
    """Stand-in executor: every batch takes ``seconds``, answers zeros."""

    def __init__(self, seconds, n, iters):
        self.seconds, self.n, self.iters = seconds, n, iters

    def place_system(self, sys, factors):
        return None, None

    def place_B(self, Bb):
        return Bb

    def run(self, A, factors, Bb, states=None):
        time.sleep(self.seconds)
        k = Bb.shape[0]
        return None, np.zeros((k, self.n)), np.ones((k, self.iters))

    def cache_size(self):
        return 1


def test_stages_add_up_to_the_latency_under_a_fixed_executor(sys_a):
    seconds = 0.05
    srv = AsyncLinsysServer(FactorStore(), solver="apc", iters=3, batch=2,
                            pipeline_depth=1, **PRM)
    ex = _FixedExecutor(seconds, sys_a.n, 3)
    srv._executor = lambda ent: ex
    fp = srv.register(sys_a)
    rng = np.random.default_rng(11)
    # the whole backlog queued before the pipeline starts: three groups
    _drive(srv, [fp], [0] * 6, [rng.standard_normal(48) for _ in range(6)])
    st = srv.stages()
    queue = st["t_taken"] - st["t_submit"]
    hold = st["t_dispatch"] - st["t_taken"]
    run = st["t_done"] - st["t_dispatch"]
    lat = srv.latencies()
    np.testing.assert_allclose(queue + hold + run, lat, rtol=0, atol=1e-12)
    np.testing.assert_array_equal(lat, st["t_done"] - st["t_submit"])
    assert np.all(run >= seconds)
    # one executor slot: a group waits for the batch ahead, in the queue
    # or in the hold, so every later batch starts after the one before
    starts = np.unique(st["t_dispatch"])
    assert starts.size == 3 and np.all(np.diff(starts) >= seconds)
    means = srv.stage_means()
    assert sum(means.values()) == pytest.approx(1e3 * lat.mean(), rel=1e-9)
    assert means["run_ms"] >= 1e3 * seconds


def test_latency_report_reads_the_stage_record_without_shed_requests(sys_a):
    srv = AsyncLinsysServer(FactorStore(), solver="apc", iters=10, batch=2,
                            admit_capacity=3, **PRM)
    fp = srv.register(sys_a)
    rng = np.random.default_rng(12)
    tickets = [srv.submit(fp, rng.standard_normal(48)) for _ in range(5)]
    srv.drain()
    srv.close()
    st = srv.stages()
    assert sorted(st["rid"].tolist()) == [0, 1, 2]      # the shed are absent
    rep = srv.latency_report()
    lat = st["t_done"] - st["t_submit"]
    assert rep["count"] == 3 == srv.stats.served
    assert rep["mean_ms"] == pytest.approx(1e3 * lat.mean(), rel=1e-12)
    assert rep["max_ms"] == pytest.approx(1e3 * lat.max(), rel=1e-12)
    assert rep["p50_ms"] == pytest.approx(
        1e3 * np.percentile(lat, 50), rel=1e-12)
    assert all(isinstance(t.result(), Shed) for t in tickets[3:])


def test_mixed_precision_async_caches_and_answers_as_sync(sys_a):
    """``precision="mixed"`` reaches the async server's store miss: both
    servers cache the same cast entry and give the same answers."""
    rng = np.random.default_rng(13)
    rhs = [rng.standard_normal(48) for _ in range(4)]
    kw = dict(solver="apc", iters=30, batch=2, use_kernel=True,
              precision="mixed", **PRM)

    sync_store = FactorStore()
    sync = LinsysServer(sync_store, **kw)
    fp = sync.register(sys_a)
    for b in rhs:
        sync.submit(fp, b)
    ref = sync.drain()

    async_store = FactorStore()
    asrv = AsyncLinsysServer(async_store, **kw)
    afp = asrv.register(sys_a)
    _, out = _drive(asrv, [afp] * 4, [0] * 4, rhs)

    assert afp == fp
    cached = {name: store._mem[fp] for name, store in
              (("sync", sync_store), ("async", async_store))}
    for f in cached.values():
        assert f.A.dtype == jnp.bfloat16 and f.B.dtype == jnp.bfloat16
    for a, b in zip(jax.tree_util.tree_leaves(cached["sync"]),
                    jax.tree_util.tree_leaves(cached["async"])):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a, np.float64),
                                      np.asarray(b, np.float64))
    for r, e in zip(out, ref):
        assert np.array_equal(r.x, e.x)
        assert r.residual == e.residual


def test_serve_cli_prints_the_stage_means():
    env = {**os.environ, "PYTHONPATH": "src", "JAX_PLATFORMS": "cpu"}
    out = subprocess.run(
        [sys.executable, "-m", "repro.launch.serve_linsys", "--async",
         "--requests", "6", "--systems", "1", "--batch", "2", "--iters",
         "20", "--n", "32", "--workers", "2", "--tol", "1e-1"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    line = next(ln for ln in out.stdout.splitlines()
                if ln.startswith("mean stages:"))
    ms = [float(v) for v in re.findall(r"(\d+\.\d+) ms", line)]
    assert len(ms) == 3 and all(v >= 0 for v in ms)


def test_concurrent_first_batches_compile_once(sys_a):
    """The benchmark's warm-up pattern: two groups queued at once, two
    executor slots, so both executor threads make a first call of the
    same cold executor together.  It is traced and compiled once, under
    one ``repro.linsys.compile`` span; the other call waits for it."""
    srv = AsyncLinsysServer(FactorStore(), solver="apc", iters=10, batch=2,
                            pipeline_depth=2, **PRM)
    fp = srv.register(sys_a)
    rng = np.random.default_rng(14)
    spans.reset()
    _, out = _drive(srv, [fp], [0] * 4,
                    [rng.standard_normal(48) for _ in range(4)])
    assert len(out) == 4 and srv.stats.batches == 2
    st = srv.stages()
    # both batches were dispatched before either was done: the two first
    # calls overlapped
    assert np.unique(st["t_dispatch"]).size == 2
    assert st["t_dispatch"].max() < st["t_done"].min()
    assert spans.totals()["repro.linsys.compile"].count == 1
    assert srv._executors[srv._systems[fp].executor_key].cache_size() == 1
