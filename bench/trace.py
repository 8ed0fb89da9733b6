"""Reduction of a ``jax.profiler`` trace to device busy time, op times and
idle gaps.

The profiler writes ``<dir>/plugins/profile/<time>/<host>.xplane.pb``.  On
a TPU the planes named ``/device:TPU:<i>`` hold a line ``XLA Ops`` whose
events are the operations that ran on that chip; the plane ``/host:CPU``
holds the host threads, among them the spans this benchmark writes with
``jax.profiler.TraceAnnotation`` (names starting ``bench.``).  The measured
window is the span ``bench.window``.

- busy: the union of the op intervals of one chip inside the window,
  averaged over the chips that ran any op;
- device ops: self seconds per op name (the HLO instruction name, e.g.
  ``fusion.12``), longest first.  An op that holds others, such as the
  ``while`` of a scan around its body's ops, counts only the time none of
  them covers;
- idle gaps: the holes in the union, longest first, each labelled by the
  benchmark span that overlaps it most (what the host was doing).
"""
from __future__ import annotations

import glob
import os
from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"
TOP = 10

Interval = Tuple[float, float, str]          # (start_ns, end_ns, name)


class Trace(NamedTuple):
    device: Dict[str, List[Interval]]        # plane name -> op intervals
    spans: List[Interval]                    # benchmark host spans


class Summary(NamedTuple):
    busy_s: float                # union of op time, mean over chips
    window_s: float
    chips: int                   # chips that ran at least one op
    device_ops: List[Tuple[str, float]]      # (op name, seconds), top
    idle_gaps: List[Tuple[str, float]]       # (host span, seconds), top


def op_name(event_name: str) -> str:
    """``%fusion.12 = f32[...] fusion(...)`` -> ``fusion.12``."""
    return event_name.split(" = ", 1)[0].strip().lstrip("%")


def find_xplane(log_dir: str) -> str:
    files = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return max(files, key=os.path.getmtime)


def read_xplane(path: str) -> Trace:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    device: Dict[str, List[Interval]] = {}
    spans: List[Interval] = []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            ops = device.setdefault(plane.name, [])
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for e in line.events:
                    ops.append((e.start_ns, e.start_ns + e.duration_ns,
                                op_name(e.name)))
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        spans.append((e.start_ns, e.start_ns + e.duration_ns,
                                      e.name))
    return Trace(device=device, spans=spans)


def merge(intervals: Sequence[Tuple[float, float]]
          ) -> List[Tuple[float, float]]:
    """Sorted union of ``(start, end)`` intervals."""
    out: List[List[float]] = []
    for s, e in sorted((s, e) for s, e, *_ in intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _clip(intervals, lo: float, hi: float):
    for s, e, *rest in intervals:
        s, e = max(s, lo), min(e, hi)
        if e > s:
            yield (s, e, *rest)


def _self_ns(ops) -> Dict[str, float]:
    """Per op name, the time of its ops that no op nested in them covers
    (ops of one chip nest, as a loop holds its body's ops)."""
    stack: List[list] = []                  # [end, name, self] still open
    done: List[list] = []
    for s, e, name in sorted(ops, key=lambda op: (op[0], -op[1])):
        while stack and stack[-1][0] < e:   # ended, or only overlapping
            stack.pop()
        if stack:                           # nested in the top op
            stack[-1][2] -= e - s
        entry = [e, name, e - s]
        stack.append(entry)
        done.append(entry)
    out: Dict[str, float] = {}
    for _, name, self_ns in done:
        out[name] = out.get(name, 0.0) + self_ns
    return out


def _gaps(busy: List[Tuple[float, float]], lo: float, hi: float):
    t = lo
    for s, e in busy:
        if s > t:
            yield (t, s)
        t = max(t, e)
    if hi > t:
        yield (t, hi)


def _label(gap: Tuple[float, float], spans: List[Interval]) -> str:
    best, best_overlap = "no bench span", 0.0
    for s, e, name in spans:
        overlap = min(e, gap[1]) - max(s, gap[0])
        if overlap > best_overlap:
            best, best_overlap = name, overlap
    return best


def window_of(trace: Trace) -> Optional[Tuple[float, float]]:
    wins = [(s, e) for s, e, name in trace.spans if name == WINDOW_SPAN]
    if wins:
        return min(s for s, _ in wins), max(e for _, e in wins)
    ops = [iv for ivs in trace.device.values() for iv in ivs]
    if not ops:
        return None
    return min(s for s, *_ in ops), max(e for _, e, *_ in ops)


def reduce(trace: Trace, window: Optional[Tuple[float, float]] = None,
           top: int = TOP) -> Optional[Summary]:
    """Busy time, op totals and labelled gaps inside ``window`` (ns); the
    ``bench.window`` span by default.  None where no op ran on a device."""
    window = window or window_of(trace)
    if window is None:
        return None
    lo, hi = window
    spans = [iv for iv in trace.spans if iv[2] != WINDOW_SPAN]
    busy_ns, op_ns, gaps = [], {}, []
    for ops in trace.device.values():
        ops = list(_clip(ops, lo, hi))
        if not ops:
            continue
        union = merge(ops)
        busy_ns.append(sum(e - s for s, e in union))
        for name, ns in _self_ns(ops).items():
            op_ns[name] = op_ns.get(name, 0.0) + ns
        gaps.extend(_gaps(union, lo, hi))
    if not busy_ns:
        return None
    device_ops = sorted(op_ns.items(), key=lambda kv: -kv[1])[:top]
    gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:top]   # longest first
    return Summary(busy_s=sum(busy_ns) / len(busy_ns) / 1e9,
                   window_s=(hi - lo) / 1e9, chips=len(busy_ns),
                   device_ops=[(n, ns / 1e9) for n, ns in device_ops],
                   idle_gaps=[(_label(g, spans), (g[1] - g[0]) / 1e9)
                              for g in gaps])
