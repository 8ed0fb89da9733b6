"""``"loop": "open"``: independent requests on a Poisson schedule drawn
from the seed, submitted to ``AsyncLinsysServer`` at their due time
whatever the server is doing; each answer comes back through its
``Ticket``.  A request's latency runs from its due time to its answer.

Parameters: ``batch``, ``rate_per_s``.
"""
from __future__ import annotations

import functools
import time
from concurrent.futures import TimeoutError as FutureTimeout
from typing import List, Optional

import numpy as np
from jax.profiler import TraceAnnotation

from bench import numerics
from bench.load import ANSWER_WAIT_S, RHS_STREAM, WARM_STREAM, Traffic
from repro.solvers.pipeline import AsyncLinsysServer, Shed


def arrival_offsets(rate: float, seconds: float, seed: int) -> np.ndarray:
    """Due times, in seconds from the window's start, of a Poisson stream.

    ``round(rate · seconds)`` requests whose gaps are the stratified
    quantiles of an exponential distribution of mean ``1 / rate``, put in
    an order drawn from the seed: every seed gets the same number of
    requests and the same set of gaps; only the order (where the bursts of
    the Poisson process fall) changes.
    """
    if not (rate > 0 and seconds > 0):
        raise ValueError(f"need rate > 0 and seconds > 0; got {rate}, "
                         f"{seconds}")
    events = max(1, int(round(rate * seconds)))
    u = (np.arange(events) + 0.5) / events
    gaps = numerics.host_rng(seed, 1).permutation(-np.log1p(-u) / rate)
    return np.concatenate([[0.0], np.cumsum(gaps[:-1])])


class Loop(Traffic):
    def __init__(self, problem, cfg, mix, seed, seconds):
        super().__init__(problem, cfg, mix, seed, seconds)
        self.schedule(float(mix["rate_per_s"]), seed, RHS_STREAM)
        self.warm_B = problem.random_rhs(self.batch + 1, WARM_STREAM)

    def schedule(self, rate: float, seed: int, stream: int) -> None:
        """The window's due times and right-hand sides."""
        self.due = arrival_offsets(rate, self.seconds, seed)
        self.B = self.problem.random_rhs(len(self.due), stream)

    def _make_server(self):
        return AsyncLinsysServer(**self._server_kw()).start()

    def _warm(self) -> None:
        # one full batch (compile, autotune) and one padded batch
        tickets = [self.server.submit(self.fp, b)
                   for b in self.warm_B[:self.batch]]
        for tk in tickets:
            tk.result()
        self.server.submit(self.fp, self.warm_B[self.batch]).result()
        self.server.reset_metrics()

    def window(self) -> dict:
        srv, n = self.server, len(self.due)
        done = np.full(n, np.nan)
        sent = np.full(n, np.nan)

        def stamp(i, _fut):
            done[i] = time.perf_counter()

        tickets = []
        t0 = time.perf_counter()
        with TraceAnnotation("bench.window"):
            for i in range(n):
                wait = t0 + self.due[i] - time.perf_counter()
                if wait > 0:
                    with TraceAnnotation("bench.sleep"):
                        time.sleep(wait)
                sent[i] = time.perf_counter()
                with TraceAnnotation("bench.submit"):
                    tk = srv.submit(self.fp, self.B[i])
                tk.future.add_done_callback(functools.partial(stamp, i))
                tickets.append(tk)
            deadline = time.perf_counter() + ANSWER_WAIT_S
            results: List[Optional[object]] = []
            with TraceAnnotation("bench.drain"):
                for tk in tickets:
                    try:
                        exc = tk.future.exception(
                            max(deadline - time.perf_counter(), 0.0))
                    except FutureTimeout:
                        results.append(None)
                        continue
                    results.append(exc if exc is not None else tk.result())
        t_end = time.perf_counter()
        due_abs = t0 + self.due
        status, X, B, to_tol = [], [], [], []
        for i, r in enumerate(results):
            if r is None:
                status.append("unanswered")
            elif isinstance(r, Shed):
                status.append("shed")
            elif isinstance(r, Exception):
                status.append("error")
            else:
                status.append("served")
                X.append(r.x)
                B.append(self.B[i])
                to_tol.append(r.iters_to_tol)
        served = np.array([s == "served" for s in status])
        lat = np.where(served & np.isfinite(done), done - due_abs,
                       t_end - due_abs)
        return {"attempted": n,
                "status": status, "latency_s": lat,
                "late_s": sent - due_abs, "window_s": t_end - t0,
                "stats": self.counters(srv.stats),
                "iters_to_tol": to_tol, "X": X, "B": B}

    def replay(self, solve, steps=None):
        """The window's own requests, answered by ``solve``."""
        return list(solve(self.B)), list(self.B)

    def close(self) -> None:
        if self.server is not None:
            self.server.close()
        super().close()
