"""``"loop": "closed"``: one caller with one request in flight through
``LinsysServer.submit`` and ``step``.  With ``"next_rhs": "time_step"``
the next right-hand side is built from the last answer, as an implicit
time stepper builds it: ``A x`` scaled to ‖b₀‖ plus a seeded forcing term
``A g`` of the same norm, so each request waits on the one before.

The record's ``latency_s`` holds one latency per served step: from just
before ``srv.submit`` to the answer in hand after ``srv.step()`` (a host
array).  The caller's own ``next_rhs`` work between steps is left out.

Parameters: ``batch``, ``next_rhs``.
"""
from __future__ import annotations

import time
from contextlib import contextmanager

import jax
import numpy as np
from jax.profiler import TraceAnnotation

from bench import numerics
from bench.load import FORCING_STREAM, RHS_STREAM, WARM_STREAM, Traffic
from repro import solvers


@contextmanager
def _persistent_cache_off():
    """JAX's persistent compilation cache neither read nor written inside;
    JAX decides once whether it uses the cache, so its decision is reset
    on entry and on exit."""
    from jax._src import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


class Loop(Traffic):
    def __init__(self, problem, cfg, mix, seed, seconds):
        super().__init__(problem, cfg, mix, seed, seconds)
        if mix.get("next_rhs", "time_step") != "time_step":
            raise ValueError(f"unknown next_rhs {mix['next_rhs']!r}")
        self.b0 = problem.random_rhs(1, RHS_STREAM)[0]
        self.warm_b = problem.random_rhs(1, WARM_STREAM)[0]
        self.beta = float(np.linalg.norm(self.b0))
        self.rng = numerics.host_rng(seed, FORCING_STREAM)

    def _make_server(self):
        return solvers.LinsysServer(**self._server_kw())

    def _warm(self) -> None:
        self._warm_step()
        # The executor closes over the seed's γ and η: a new seed compiles
        # it, a seed run before in this cache loads it, and a process that
        # compiled no program serves each step about 4 ms slower (PERF.md).
        # So every run compiles the window's programs: drop the in-memory
        # ones and warm again with the persistent cache off.
        jax.clear_caches()
        with _persistent_cache_off():
            self._warm_step()
        self.server.stats = type(self.server.stats)(
            executor_builds=self.server.stats.executor_builds)

    def _warm_step(self) -> None:
        self.server.submit(self.fp, self.warm_b)
        out = self.server.step()
        # the next right-hand side may run on the device: compile it too,
        # with a generator of its own so the window's forcing is the seed's
        self.next_rhs(out[0].x, numerics.host_rng(self.seed, WARM_STREAM))

    def next_rhs(self, x: np.ndarray, rng=None) -> np.ndarray:
        """``A x`` of the last answer scaled to ‖b₀‖, plus ``A g`` of a seeded
        forcing term ``g`` scaled to the same norm."""
        g = (self.rng if rng is None else rng).standard_normal(x.shape[0])
        u, f = self.problem.to_rhs(np.stack([x, g]).astype(np.float32))
        return (u * (self.beta / max(np.linalg.norm(u), 1e-30))
                + f * (self.beta / np.linalg.norm(f))).astype(np.float32)

    def window(self) -> dict:
        srv = self.server
        X, B, to_tol, status, lat = [], [], [], [], []
        b = self.b0
        t0 = time.perf_counter()
        with TraceAnnotation("bench.window"):
            while True:
                t_sub = time.perf_counter()
                with TraceAnnotation("bench.submit"):
                    srv.submit(self.fp, b)
                with TraceAnnotation("bench.step"):
                    out = srv.step()
                if len(out) != 1:
                    status.append("error")
                    break
                lat.append(time.perf_counter() - t_sub)
                status.append("served")
                X.append(out[0].x)
                B.append(b)
                to_tol.append(out[0].iters_to_tol)
                if time.perf_counter() - t0 >= self.seconds:
                    break
                with TraceAnnotation("bench.next_rhs"):
                    b = self.next_rhs(out[0].x)
        t_end = time.perf_counter()
        return {"attempted": len(status),
                "status": status, "latency_s": np.asarray(lat),
                "window_s": t_end - t0,
                "steps": len(X), "stats": self.counters(srv.stats),
                "iters_to_tol": to_tol, "X": X, "B": B}

    def replay(self, solve, steps=None):
        """``steps`` steps of the stream, each next right-hand side made
        from ``solve``'s own last answer."""
        if steps is None:
            raise ValueError("a closed stream's replay needs steps")
        X, B, b = [], [], self.b0
        for _ in range(steps):
            x = solve(b[None])[0]
            X.append(x)
            B.append(b)
            b = self.next_rhs(x)
        return X, B
