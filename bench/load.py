"""The load generator's shared part.  A traffic mix is data
(``bench/traffic/<mix>.json``, merged with the cell's ``params``); its
``loop`` names the module that drives it, ``bench/traffic/<loop>.py``,
whose ``Loop`` class subclasses ``Traffic`` here.  A new kind of loop is a
new file there; this one does not change.

Set-up, the same for every loop: the program's own ``Solver.analyze``
gives γ and η, the server is built on ``ExecutionPlan(kernel=True)`` with
no engine, tile or autotune setting of its own, the system is registered,
and the loop's ``_warm`` serves first batches so that every shape the
window uses is compiled before it opens.

A loop module's ``Loop`` provides ``_make_server()``, ``_warm()``,
``window() -> dict`` (the run's record: ``attempted``,
``status`` per request, ``window_s``, ``stats``, ``iters_to_tol``, and the
answers ``X`` with their right-hand sides ``B``) and ``replay(solve,
steps)``, which answers the same requests with ``solve`` in the program's
place, for the control.
"""
from __future__ import annotations

import time
from typing import Dict

from repro import solvers

ANSWER_WAIT_S = 60.0          # how long past the window an answer may come
RHS_STREAM, WARM_STREAM, FORCING_STREAM = 2, 3, 4


class Traffic:
    """Set-up shared by every loop; a loop module's ``Loop`` makes the
    server, warms it and runs the window."""

    def __init__(self, problem, cfg: dict, mix: dict, seed: int,
                 seconds: float):
        self.problem, self.cfg, self.mix, self.seed = problem, cfg, mix, seed
        self.seconds = seconds
        self.batch = int(mix["batch"])
        self.server = None
        self.fp = None
        self.analyze_s = self.warm_s = float("nan")
        self.params: Dict[str, float] = {}

    def _server_kw(self) -> dict:
        return dict(solver="apc", iters=int(self.cfg["iters"]),
                    tol=float(self.cfg["tol"]), batch=self.batch,
                    plan=solvers.ExecutionPlan(kernel=True), **self.params)

    def setup(self) -> None:
        solver = solvers.get("apc")
        t = time.perf_counter()
        self.params, _ = solver.analyze(self.problem.system)
        self.analyze_s = time.perf_counter() - t
        self.server = self._make_server()
        t = time.perf_counter()
        self.fp = self.server.register(self.problem.system)
        self._warm()
        self.warm_s = time.perf_counter() - t

    def close(self) -> None:
        self.server = None

    @staticmethod
    def counters(stats) -> dict:
        return {"served": stats.served, "padded": stats.padded,
                "batches": stats.batches, "shed": stats.shed}


def make_traffic(files, problem, cfg: dict, mix: dict, seed: int,
                 seconds: float) -> Traffic:
    """The mix's loop, found by name under ``bench/traffic``."""
    try:
        mod = files.module("traffic", mix["loop"])
    except (KeyError, FileNotFoundError):
        raise ValueError(f"mix names loop {mix.get('loop')!r}: no "
                         f"bench/traffic/<loop>.py holds it") from None
    return mod.Loop(problem, cfg, mix, seed, seconds)
