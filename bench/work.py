"""Operations and bytes of one APC iteration, computed from shapes alone.

One iteration of APC on m row blocks of p rows each needs, per worker, the
gather u = A_i (x̄ − x_i) and the scatter x_i + γ (d − B_i u) with
B_i = A_iᵀ G_i⁻¹: one read of the stored operand A_i and one read of a
projection operand of the same size.  ``width`` is n for a dense block and
the padded column support w for a compressed sparse one.  The count is of
the work the algorithm needs, whatever engine runs it, so a change of
engine can never make a share of it read above 100%: work that an engine
adds (a second pass over A for the residual, two triangular solves in
place of B) is not in it.

    flops = 4 · k · m · p · width          (two multiply-adds per entry
                                            of A_i and of B_i, per RHS)
    bytes = 2 · m · p · width · itemsize   (A and B once each)
          + 2 · k · (m + 1) · n · itemsize (the iterates x_i and x̄,
                                            read and written)

with k the real right-hand sides only (padding slots do no useful work).
The least time is for the cell's chips: where several share the work,
the one chip's least time is divided by their number
(``harness.Run.least_seconds``).
"""
from __future__ import annotations

import json
from pathlib import Path
from typing import NamedTuple

PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"


class Work(NamedTuple):
    flops: float
    bytes: float


class Bound(NamedTuple):
    seconds: float
    by: str             # "memory" or "compute"


def apc_iteration(*, m: int, p: int, n: int, width: int, k: float,
                  itemsize: int = 4) -> Work:
    """The work of one APC iteration over k real right-hand sides."""
    flops = 4.0 * k * m * p * width
    nbytes = 2.0 * m * p * width * itemsize + 2.0 * k * (m + 1) * n * itemsize
    return Work(flops=flops, bytes=nbytes)


def load_peaks(device_kind: str, path: Path = PEAKS_FILE) -> dict:
    """The peaks of ``device_kind``; a device not in the table is an error."""
    table = json.loads(Path(path).read_text())
    try:
        return table["devices"][device_kind]
    except KeyError:
        raise KeyError(f"device_kind {device_kind!r} is not in {path}; "
                       f"known: {sorted(table['devices'])}") from None


def least_time(work: Work, peaks: dict) -> Bound:
    """The least time the chip could take: the larger of bytes over the HBM
    bandwidth and operations over the peak rate (the bf16 peak bounds any
    precision's rate, so this is a true lower bound)."""
    mem = work.bytes / peaks["hbm_bytes_per_s"]
    comp = work.flops / peaks["flops_per_s"]
    return Bound(seconds=max(mem, comp),
                 by="memory" if mem >= comp else "compute")
