"""``fetch_copies_per_call``: device-to-host copies the program started per
evaluation in the traced window, its ``repro.fetch.copy`` counts (one per
copy) / its ``repro.evaluate`` spans.  None where the program left no such
count."""

from chipbench.metrics._spans import program_records

COPY, EVALUATE = "repro.fetch.copy", "repro.evaluate"


def read(r):
    names = [rec.name for rec in program_records(r) or ()]
    copies, calls = names.count(COPY), names.count(EVALUATE)
    if not copies or not calls:
        return None
    return copies / calls
