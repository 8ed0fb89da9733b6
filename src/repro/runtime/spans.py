"""Program spans: named host intervals on the profiler's clock, with
process-wide totals.

``span(name, **ids)`` is a context manager.  On exit it adds the interval
to a process-wide total kept per name: how many spans closed, their
seconds, and their self seconds (the seconds no child span on the same
thread covered).  While a profiler is active it also opens a
``jax.profiler.TraceAnnotation(name, **ids)``, so that in a traced run the
interval lands on the host plane of the trace, on the same clock as the
device's ops.

    with span("repro.ops.autotune", what="tiles"):
        ...

There is no switch: the annotation is made only while a profiler is
active, and a total is one dict update under a lock.  Individual span
events live only in the profiler's trace; this module keeps aggregates.
A span is host code: it never goes inside a jitted or scanned body.
"""
from __future__ import annotations

import threading
import time
from typing import Dict, List, NamedTuple, Optional

from jax.profiler import TraceAnnotation

PREFIX = "repro."


class Total(NamedTuple):
    count: int          # spans closed
    total_s: float      # their seconds
    self_s: float       # their seconds not covered by a child span


class _Open(threading.local):
    top: Optional["span"] = None          # the thread's innermost open span


_now = time.perf_counter
_lock = threading.Lock()
_totals: Dict[str, List] = {}            # name -> [count, total_s, self_s]
_open = _Open()


class span:
    """One timed span; see the module docstring."""

    __slots__ = ("name", "_ids", "_ann", "_t0", "_child_s", "_parent")

    def __init__(self, name: str, **ids):
        if not name.startswith(PREFIX):
            raise ValueError(f"span name {name!r} must start with "
                             f"{PREFIX!r}")
        self.name, self._ids = name, ids

    def __enter__(self) -> "span":
        self._parent, _open.top = _open.top, self
        self._child_s = 0.0
        self._ann = (TraceAnnotation(self.name, **self._ids)
                     if TraceAnnotation.is_enabled() else None)
        if self._ann is not None:
            self._ann.__enter__()
        self._t0 = _now()
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        dur = _now() - self._t0
        if self._ann is not None:
            self._ann.__exit__(exc_type, exc, tb)
        parent = _open.top = self._parent
        if parent is not None:
            parent._child_s += dur
        own = dur - self._child_s
        with _lock:
            t = _totals.get(self.name)
            if t is None:
                _totals[self.name] = [1, dur, own]
            else:
                t[0] += 1
                t[1] += dur
                t[2] += own


def totals() -> Dict[str, Total]:
    """A snapshot of every span name's totals since the last ``reset``."""
    with _lock:
        return {name: Total(*t) for name, t in _totals.items()}


def reset() -> None:
    """Clear the totals (spans still open add to the fresh ones)."""
    with _lock:
        _totals.clear()
