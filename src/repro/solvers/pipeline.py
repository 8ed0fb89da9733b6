"""Async pipelined linear-system serving: overlapped admission → batch
assembly → execution → streaming result return.

``LinsysServer`` is a synchronous ``step()``/``drain()`` loop — admission,
batch assembly, device execution, and result return all serialize, so the
taskmaster throughput is one batch at a time.  ``AsyncLinsysServer``
decomposes the same serving contract into pipeline stages connected by
bounded queues:

  1. **Admission with backpressure** — ``submit(fp, rhs)`` returns a
     ``Ticket`` whose future streams the result back.  Admission is
     bounded by ``admit_capacity`` requests in the system (queued or in
     flight): a full pipeline REJECTS the request with an explicit
     ``Shed`` result instead of queueing unboundedly — overload degrades
     availability (shed rate), never correctness or latency of admitted
     work.
  2. **Batch assembly on a host thread** — the identical FIFO
     oldest-pending-system rule and ``take_group`` coalescing/padding
     semantics as the sync server (reused, not reimplemented), plus
     factor acquisition through the shared ``FactorStore`` and the
     host→device transfer (``jax.device_put`` via ``Executor.place_B``)
     so the copy of batch B+1 overlaps the execution of batch B.
  3. **A pool of in-flight executors** — up to ``pipeline_depth`` batches
     execute concurrently on the compile-once executor cache inherited
     from ``LinsysServer`` (same keys, same zero-steady-state-retrace
     invariant, ``jit_cache_size()`` constant under load); system A's
     solve overlaps system B's assembly and readback.
  4. **Streaming result return** — each request's future resolves to a
     ``Served`` (or ``Shed``) the moment its batch completes.

Every served request leaves a stage record (``stages()``): when it was
submitted, when the assembly thread took its group off the queue, when
an executor began its batch, and when the batch's answers were on the
host.  The SLO report (``latency_report()``: submit → result) and the
three stage means (``stage_means()``: queue, hold, run) read it.

Everything the synchronous lifecycle guarantees composes unchanged:
``use_kernel=True`` (fused multi-RHS Pallas kernels), ``warm_start=True``
gated by ``Solver.warm_rhs_ok`` (warm chaining serializes same-system
batches so state hand-off is exact), and ``backend="mesh"`` through
``mesh.batched_runner``.

    srv = AsyncLinsysServer(store, solver="apc", batch=4,
                            pipeline_depth=2, admit_capacity=64)
    fp = srv.register(sys)
    with srv:                                   # start()/close()
        tickets = [srv.submit(fp, b) for b in stream]
        for t in tickets:
            r = t.result()                      # Served or Shed
    srv.latency_report()                        # p50/p95/p99 ms, count
    srv.stage_means()                           # queue/hold/run mean ms
"""
from __future__ import annotations

import threading
import time
from concurrent.futures import Future, ThreadPoolExecutor
from typing import Dict, List, NamedTuple, Optional, Tuple, Union

import numpy as np

from .serve import LinsysServer, Served, _Work, take_group
from .store import FactorStore


class Shed(NamedTuple):
    """Explicit overload result: the request was REJECTED at admission
    because ``admit_capacity`` requests were already in the pipeline."""
    rid: int
    fp: str


Result = Union[Served, Shed]


class Ticket(NamedTuple):
    """Admission receipt: the future resolves to ``Served`` (success) or
    ``Shed`` (rejected at admission — resolved immediately)."""
    rid: int
    fp: str
    future: Future
    t_submit: float

    def result(self, timeout: Optional[float] = None) -> Result:
        return self.future.result(timeout)


class _AsyncRequest(NamedTuple):
    rid: int
    fp: str
    rhs: np.ndarray
    future: Future
    t_submit: float


# a stage record's fields, one row per served request; the clocks are
# ``time.perf_counter`` seconds
STAGE_FIELDS = ("rid", "batch", "t_submit", "t_taken", "t_dispatch",
                "t_done")


class AsyncLinsysServer(LinsysServer):
    """Pipelined twin of ``LinsysServer``: same registration, coalescing,
    store, executor-cache, and warm-start semantics — decomposed into
    admission / assembly / execution stages so they overlap.

    ``pipeline_depth`` bounds concurrently-executing batches (the
    executor pool size AND the assembly→execution queue bound);
    ``admit_capacity`` bounds requests in the system — queued plus in
    flight — beyond which ``submit`` sheds.  ``step()`` is not part of
    this server's surface (serving happens on the pipeline threads);
    ``drain()`` blocks until every ticket since the last drain resolved
    and returns the results in submission (rid) order.
    """

    def __init__(self, store: Optional[FactorStore] = None, *,
                 pipeline_depth: int = 2,
                 admit_capacity: Optional[int] = None, **kw):
        super().__init__(store, **kw)
        if pipeline_depth < 1:
            raise ValueError(
                f"pipeline_depth must be >= 1, got {pipeline_depth}")
        if admit_capacity is None:
            # enough for every executor slot plus a full assembly backlog
            admit_capacity = 8 * self.batch * pipeline_depth
        if admit_capacity < 1:
            raise ValueError(
                f"admit_capacity must be >= 1, got {admit_capacity}")
        self.pipeline_depth = pipeline_depth
        self.admit_capacity = admit_capacity
        self._admit_base = admit_capacity   # full-fleet capacity; see
                                            # on_membership()
        self._lock = threading.RLock()
        self._work = threading.Condition(self._lock)   # assembly wakeups
        self._idle = threading.Condition(self._lock)   # drain/close wakeups
        self._in_system = 0       # admitted and not yet completed
        self._inflight = 0        # batches dispatched and not yet completed
        self._busy = set()        # fps serialized for warm-state chaining
        self._tickets: List[Ticket] = []
        self._stages: List[Tuple] = []       # STAGE_FIELDS per served rid
        self._bid = 0                        # next batch id
        self._stopping = False
        self._assembler: Optional[threading.Thread] = None
        self._pool: Optional[ThreadPoolExecutor] = None
        # bounded assembly->execution hand-off: acquiring a slot blocks the
        # assembly thread once pipeline_depth batches are in flight
        self._slots = threading.Semaphore(pipeline_depth)

    # ----- lifecycle --------------------------------------------------------
    def start(self) -> "AsyncLinsysServer":
        """Start the assembly thread and the executor pool (idempotent)."""
        with self._lock:
            if self._assembler is not None:
                return self
            self._stopping = False
            self._pool = ThreadPoolExecutor(
                max_workers=self.pipeline_depth,
                thread_name_prefix="linsys-exec")
            self._assembler = threading.Thread(
                target=self._assemble_loop, name="linsys-assembly",
                daemon=True)
            self._assembler.start()
        return self

    def close(self, drain: bool = True) -> None:
        """Drain the pipeline (default) and stop the stage threads."""
        with self._lock:
            started = self._assembler is not None
            has_work = self._in_system > 0
        if not started:
            if has_work and drain:
                self.start()
            elif not has_work:
                return
        if drain:
            with self._idle:
                while self._in_system or self._inflight:
                    self._idle.wait(0.05)
        with self._lock:
            self._stopping = True
            self._work.notify_all()
            assembler, pool = self._assembler, self._pool
            self._assembler, self._pool = None, None
        if assembler is not None:
            assembler.join()
        if pool is not None:
            pool.shutdown(wait=True)

    def __enter__(self) -> "AsyncLinsysServer":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.close()

    # ----- stage 1: admission with backpressure -----------------------------
    def submit(self, fp: str, rhs) -> Ticket:        # type: ignore[override]
        """Admit one request, or shed it with an explicit overload result.

        Validation (unknown fingerprint -> KeyError naming it, shape
        mismatch -> ValueError) is the sync server's, shared.  A full
        pipeline (``admit_capacity`` requests queued or in flight)
        resolves the ticket's future IMMEDIATELY with ``Shed`` — callers
        always get an answer, and admitted requests keep their latency.
        """
        _, rhs = self._validated(fp, rhs)
        fut: Future = Future()
        t = time.perf_counter()
        with self._lock:
            rid = self._rid
            self._rid += 1
            tk = Ticket(rid=rid, fp=fp, future=fut, t_submit=t)
            self._tickets.append(tk)
            if self._in_system >= self.admit_capacity:
                self.stats.shed += 1
                shed = True
            else:
                self.stats.admitted += 1
                self._in_system += 1
                self._queues[fp].append(_AsyncRequest(
                    rid=rid, fp=fp, rhs=rhs, future=fut, t_submit=t))
                self._work.notify()
                shed = False
        if shed:
            fut.set_result(Shed(rid=rid, fp=fp))
        return tk

    def in_system(self) -> int:
        """Requests admitted and not yet completed (queued + in flight)."""
        with self._lock:
            return self._in_system

    def on_membership(self, alive: int, total: int) -> int:
        """Scale admission to the live fraction of the worker fleet.

        The elastic integration point: when the fleet shrinks (deaths
        reported by a ``HeartbeatMonitor`` / ``ElasticRuntime`` event
        stream), per-batch latency rises — so admission must shrink with
        it or queueing delay grows unboundedly.  Overload under a
        shrunken fleet therefore degrades AVAILABILITY (explicit ``Shed``
        at admission), never correctness or the latency of admitted work.
        Capacity recovers automatically when the fleet does (call again
        with the new alive count); it never drops below 1, so the server
        keeps serving as long as any worker lives.  Returns the new
        ``admit_capacity``.
        """
        if total < 1:
            raise ValueError(f"total workers must be >= 1, got {total}")
        if not 0 <= alive <= total:
            raise ValueError(
                f"alive={alive} must be within [0, total={total}]")
        with self._lock:
            self.admit_capacity = max(
                1, int(self._admit_base * alive / total))
            return self.admit_capacity

    # ----- stage 2: batch assembly (host thread) ----------------------------
    def _next_group(self):
        """Under the lock: oldest-pending eligible system -> FIFO group.

        The selection rule and the ``take_group`` coalescing/padding are
        the sync server's.  With ``warm_start`` on, a system whose batch
        is still in flight is skipped (its next batch needs that batch's
        final states) — other systems keep the pipeline full meanwhile.
        """
        pending = [(q[0].rid, fp) for fp, q in self._queues.items()
                   if q and fp not in self._busy]
        if not pending:
            return None
        fp = min(pending)[1]
        group, n_real = take_group(self._queues[fp], self.batch)
        if self.warm_start:
            self._busy.add(fp)
        bid, self._bid = self._bid, self._bid + 1
        return fp, group, n_real, bid, time.perf_counter()

    def _assemble_loop(self):
        while True:
            with self._work:
                item = self._next_group()
                while item is None:
                    if self._stopping:
                        return
                    self._work.wait(0.05)
                    item = self._next_group()
            fp, group, n_real, bid, t_taken = item
            try:
                # the sync step()'s own assembly (one assembly thread, so
                # the per-system placement cache and the executor cache
                # need no extra locking); place_B runs on THIS thread, so
                # the transfer of the next batch double-buffers behind the
                # executing one
                work = self._assemble(fp, group, n_real)
            except Exception as e:               # noqa: BLE001 — stage must
                self._complete_error(fp, group[:n_real], e)   # not die
                continue
            # bounded hand-off: blocks while pipeline_depth batches are in
            # flight — THE backpressure between assembly and execution
            self._slots.acquire()
            with self._lock:
                self._inflight += 1
            self._pool.submit(self._execute, work, bid, t_taken)

    # ----- stage 3+4: execution pool, streaming completion ------------------
    def _execute(self, w: _Work, bid: int, t_taken: float) -> None:
        t_dispatch = time.perf_counter()
        try:
            states, X, res = self._run(w)
            t_done = time.perf_counter()         # the answers are on the host
            out = self._served(w, X, res)
            real = w.group[:w.n_real]
            with self._lock:
                if self.warm_start:
                    w.ent.last_states, w.ent.last_Bb = states, w.Bb
                    self._busy.discard(w.fp)     # unblocks warm chaining
                self.stats.batches += 1
                self.stats.served += w.n_real
                self.stats.padded += len(w.group) - w.n_real
                self.stats.warm_batches += int(w.warm)
                self._stages.extend(
                    (r.rid, bid, r.t_submit, t_taken, t_dispatch,
                     t_done) for r in real)
                self._in_system -= w.n_real
                self._inflight -= 1
                self._work.notify_all()
                self._idle.notify_all()
            for r, s in zip(real, out):
                r.future.set_result(s)
        except Exception as e:                   # noqa: BLE001
            with self._lock:
                self._busy.discard(w.fp)
                self._in_system -= w.n_real
                self._inflight -= 1
                self._work.notify_all()
                self._idle.notify_all()
            for r in w.group[:w.n_real]:
                if not r.future.done():
                    r.future.set_exception(e)
        finally:
            self._slots.release()

    def _complete_error(self, fp, requests, exc) -> None:
        with self._lock:
            self._busy.discard(fp)
            self._in_system -= len(requests)
            self._work.notify_all()
            self._idle.notify_all()
        for r in requests:
            if not r.future.done():
                r.future.set_exception(exc)

    # ----- draining / reporting ---------------------------------------------
    def step(self):
        raise RuntimeError(
            "AsyncLinsysServer serves on its pipeline threads: submit() "
            "returns a Ticket whose future streams the result; use "
            "drain() (or ticket.result()) instead of step()")

    def drain(self) -> List[Result]:
        """Block until every ticket since the last drain resolved; return
        the results in submission (rid) order — ``Served`` for admitted
        requests, ``Shed`` for rejected ones.  With zero outstanding
        tickets this is a true no-op ([] — no threads started, no
        executor compile, jit cache unchanged)."""
        with self._lock:
            tickets, self._tickets = self._tickets, []
            has_work = self._in_system > 0
        if not tickets:
            return []
        if has_work:
            self.start()
        return [t.future.result() for t in tickets]

    def stages(self) -> Dict[str, np.ndarray]:
        """The stage record of every request served since the last
        ``reset_metrics()``, in completion order: one array per
        ``STAGE_FIELDS`` name.  A request's queue wait is ``t_taken −
        t_submit``; its hold (assembly, ``place_B``, the wait for an
        executor slot) ``t_dispatch − t_taken``; its run (behind the batch
        ahead on the device, its own iterations, the readback) ``t_done −
        t_dispatch``.  Shed requests have no record."""
        with self._lock:
            rows = list(self._stages)
        cols = list(zip(*rows)) if rows else [()] * len(STAGE_FIELDS)
        return {name: np.asarray(col, dtype=int if name in ("rid", "batch")
                                 else float)
                for name, col in zip(STAGE_FIELDS, cols)}

    def stage_means(self) -> Dict[str, float]:
        """Mean queue, hold and run milliseconds over the stage record
        (they add up to the mean latency; NaN before any request)."""
        st = self.stages()
        parts = {"queue_ms": st["t_taken"] - st["t_submit"],
                 "hold_ms": st["t_dispatch"] - st["t_taken"],
                 "run_ms": st["t_done"] - st["t_dispatch"]}
        return {k: float(v.mean() * 1e3) if v.size else float("nan")
                for k, v in parts.items()}

    def latencies(self) -> np.ndarray:
        """Per-request submit→result latencies (seconds) so far."""
        st = self.stages()
        return st["t_done"] - st["t_submit"]

    def reset_metrics(self) -> None:
        """Clear the stage record and traffic counters (keeps executors,
        placements, and warm states — benchmarks prime then measure)."""
        with self._lock:
            self._stages = []
            builds = self.stats.executor_builds
            self.stats = type(self.stats)(executor_builds=builds)

    def latency_report(self) -> dict:
        """The SLO view: count, p50/p95/p99/mean/max in milliseconds."""
        lat = self.latencies()
        if lat.size == 0:
            return {"count": 0, "p50_ms": float("nan"),
                    "p95_ms": float("nan"), "p99_ms": float("nan"),
                    "mean_ms": float("nan"), "max_ms": float("nan")}
        q = np.percentile(lat, [50, 95, 99]) * 1e3
        return {"count": int(lat.size), "p50_ms": float(q[0]),
                "p95_ms": float(q[1]), "p99_ms": float(q[2]),
                "mean_ms": float(lat.mean() * 1e3),
                "max_ms": float(lat.max() * 1e3)}
