"""``layout_hit_share``: of the evaluations in the traced window that built
a batch from a buffer, the share that reused the buffer's padded layout
(``repro.layout.hit`` marks ÷ those and ``repro.layout.build`` marks).
None where the program left neither mark in the window."""

from chipbench.metrics._spans import program_records

HIT, BUILD = "repro.layout.hit", "repro.layout.build"


def read(r):
    names = [rec.name for rec in program_records(r) or ()]
    hits, builds = names.count(HIT), names.count(BUILD)
    if not hits + builds:
        return None
    return hits / (hits + builds)
