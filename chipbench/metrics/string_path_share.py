"""``string_path_share``: of the run rows the program tokenized in the
traced window, the share that went through the exact string pipeline,
Σ ``repro.tokenize.strings`` ÷ Σ ``repro.tokenize.rows`` (one of each per
tokenized chunk).  None where the program left no such count, or counted
no rows."""

from chipbench.metrics._spans import program_records

ROWS, STRINGS = "repro.tokenize.rows", "repro.tokenize.strings"


def read(r):
    recs = program_records(r) or ()
    rows = sum(rec.value for rec in recs if rec.name == ROWS)
    strings = sum(rec.value for rec in recs if rec.name == STRINGS)
    if not rows:
        return None
    return strings / rows
