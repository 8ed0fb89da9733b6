"""Program spans: totals per name, self time under nesting, exceptions,
threads, reset, and the cost of a span with the profiler off."""
import os
import sys
import threading
import time

import numpy as np
import pytest

from repro.runtime import spans


@pytest.fixture(autouse=True)
def clean():
    spans.reset()
    yield
    spans.reset()


def _busy(seconds):
    end = time.perf_counter() + seconds
    while time.perf_counter() < end:
        pass


def test_nesting_subtracts_children_from_self_time():
    with spans.span("repro.test.outer", batch=1):
        _busy(0.01)
        with spans.span("repro.test.inner", batch=1):
            _busy(0.02)
        with spans.span("repro.test.inner", batch=1):
            _busy(0.02)
    t = spans.totals()
    outer, inner = t["repro.test.outer"], t["repro.test.inner"]
    assert (outer.count, inner.count) == (1, 2)
    assert inner.self_s == inner.total_s           # a leaf is all self
    assert outer.total_s >= inner.total_s + 0.01
    assert outer.self_s == pytest.approx(outer.total_s - inner.total_s,
                                         abs=1e-9)
    assert 0.01 <= outer.self_s < 0.02


def test_a_span_closes_on_an_exception():
    with pytest.raises(KeyError):
        with spans.span("repro.test.outer"):
            with spans.span("repro.test.inner"):
                raise KeyError("x")
    t = spans.totals()
    assert t["repro.test.outer"].count == 1 and t["repro.test.inner"].count == 1
    # the stack unwound: a later span on this thread is a root again
    with spans.span("repro.test.after"):
        _busy(0.005)
    after = spans.totals()["repro.test.after"]
    assert after.self_s == after.total_s
    assert spans.totals()["repro.test.outer"].count == 1


def test_totals_stay_right_under_threads():
    """More threads than cores, switching often: no update is lost and
    each thread's children are subtracted from its own parents only."""
    threads_n, n = 2 * (os.cpu_count() or 4), 500
    prev = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        def work():
            for _ in range(n):
                with spans.span("repro.test.outer"):
                    with spans.span("repro.test.inner"):
                        pass

        threads = [threading.Thread(target=work) for _ in range(threads_n)]
        for th in threads:
            th.start()
        for th in threads:
            th.join(timeout=60)
        assert not any(th.is_alive() for th in threads)
    finally:
        sys.setswitchinterval(prev)
    t = spans.totals()
    assert t["repro.test.outer"].count == threads_n * n
    assert t["repro.test.inner"].count == threads_n * n
    outer, inner = t["repro.test.outer"], t["repro.test.inner"]
    assert outer.self_s == pytest.approx(outer.total_s - inner.total_s,
                                         rel=1e-6, abs=1e-9)


def test_reset_clears_the_totals():
    with spans.span("repro.test.outer"):
        pass
    snap = spans.totals()
    assert "repro.test.outer" in snap
    spans.reset()
    assert spans.totals() == {}
    assert snap["repro.test.outer"].count == 1     # a snapshot is a copy


def test_names_carry_the_program_prefix():
    with pytest.raises(ValueError, match="repro."):
        spans.span("bench.window")


class _Bare:
    """A context manager that does nothing: the floor a span is held to."""

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return None


def test_a_span_costs_under_five_microseconds_with_the_profiler_off():
    """10⁵ spans in ten batches, each beside a batch of bare ``with``
    blocks of a no-op context manager, so that a loaded host slows both
    alike.  A span costs a few of those (a timestamp pair, a thread-local
    parent, one dict update under a lock): it stays under ten of them,
    and under 5 µs on an unloaded host (the least batch mean)."""
    span, batches, n = spans.span, 10, 10_000
    cost, bare = [], []
    for _ in range(batches):
        t0 = time.perf_counter()
        for _ in range(n):
            with span("repro.test.cost", batch=3):
                pass
        t1 = time.perf_counter()
        for _ in range(n):
            with _Bare():
                pass
        t2 = time.perf_counter()
        cost.append((t1 - t0) / n)
        bare.append((t2 - t1) / n)
    assert spans.totals()["repro.test.cost"].count == batches * n
    assert min(c / b for c, b in zip(cost, bare)) < 10
    print(f"span {1e6 * min(cost):.2f} us, bare with "
          f"{1e6 * min(bare):.2f} us")


def test_a_span_lands_in_the_profiler_trace(tmp_path):
    """With a profiler active a span is also a host event of the trace,
    under its name; the totals count it as any other."""
    import jax
    from jax.profiler import ProfileData

    with jax.profiler.trace(str(tmp_path)):
        with spans.span("repro.test.traced", batch=5):
            _busy(0.002)
    path = next(tmp_path.rglob("*.xplane.pb"))
    names = {e.name for plane in ProfileData.from_file(str(path)).planes
             for line in plane.lines for e in line.events}
    assert any(n.startswith("repro.test.traced") for n in names)
    assert spans.totals()["repro.test.traced"].count == 1


def test_setup_and_the_served_path_open_their_spans():
    """Analyze, register and two batches of the sync server open each
    set-up span once (the store miss and the executor's compile on the
    first batch); later batches, served from the store and the compiled
    executor, open none."""
    from repro import solvers
    from repro.data import linsys
    from repro.solvers.serve import LinsysServer
    from repro.solvers.store import FactorStore

    sys_ = linsys.conditioned_gaussian(n=48, m=4, cond=10.0, seed=0)
    prm, _ = solvers.get("apc").analyze(sys_)
    srv = LinsysServer(FactorStore(), solver="apc", iters=5, batch=2, **prm)
    fp = srv.register(sys_)
    rng = np.random.default_rng(0)
    for _ in range(4):
        srv.submit(fp, rng.standard_normal(sys_.N))
    assert len(srv.drain()) == 4
    t = spans.totals()
    once = ("repro.spectral.x_matrix", "repro.spectral.eig",
            "repro.store.fingerprint", "repro.store.prepare",
            "repro.linsys.compile")
    assert {n: t[n].count for n in t} == dict.fromkeys(once, 1)
    assert all(t[n].self_s == t[n].total_s for n in once)
    for _ in range(4):
        srv.submit(fp, rng.standard_normal(sys_.N))
    assert len(srv.drain()) == 4
    assert spans.totals() == t
