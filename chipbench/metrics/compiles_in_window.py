"""``compiles_in_window``: retraces of the program's jitted engines inside
the traced window, counted from its ``repro.compile.<engine>`` marks (not
divided by calls).  None where the program recorded nothing in the window."""

from chipbench.metrics._spans import COMPILE, program_records


def read(r):
    recs = program_records(r)
    if not recs:
        return None
    return sum(rec.name.startswith(COMPILE) for rec in recs)
