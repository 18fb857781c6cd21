"""Shared arithmetic of the host-layer metrics: the program's own spans
(``repro.obs``), which the program keeps in memory while the profiler
records, i.e. in the traced window alone.

A reader gives None where the run has nothing to read: no trace, a program
without ``repro.obs``, records dropped past the recorder's capacity (a sum
would read low), or no record of the reader's name.
"""

from __future__ import annotations

from typing import List, Optional

from chipbench.metrics._device import per_call_ms

COMPILE = "repro.compile."


def program_records(r) -> Optional[List]:
    """The program's closed records of the traced window, or None."""
    if r.trace is None:
        return None
    try:
        from repro import obs
    except ImportError:  # a program that records no spans
        return None
    if obs.dropped():
        return None
    return [rec for rec in obs.records() if rec.end_ns is not None]


def span_ms(r, name: str) -> Optional[float]:
    """Milliseconds per evaluation in the spans named ``name``."""
    durs = [rec.end_ns - rec.start_ns for rec in program_records(r) or ()
            if rec.name == name]
    if not durs:
        return None
    return per_call_ms(sum(durs), r.calls)
