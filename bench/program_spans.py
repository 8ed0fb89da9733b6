"""The program's own span totals (``repro.runtime.spans``), read by the
set-up metrics.

The totals are process-wide, and ``bench/run.py`` makes one run per
process.  The set-up spans read here fire only in set-up: ``register``
fingerprints A once, the window's batches hit the factor store, and
nothing compiles or autotunes inside the window (``compiles_in_window``
says so for each run).  So the totals at read time are set-up's.  Where
the program keeps no span totals, each reader returns None.
"""
from __future__ import annotations

from typing import Optional


def seconds(name: str, field: str = "total_s") -> Optional[float]:
    """``field`` (``total_s`` or ``self_s``) of the span ``name`` summed
    over the process; 0 where no such span closed, None where the program
    has no spans module."""
    try:
        from repro.runtime import spans
    except ImportError:
        return None
    t = spans.totals().get(name)
    return 0.0 if t is None else float(getattr(t, field))
