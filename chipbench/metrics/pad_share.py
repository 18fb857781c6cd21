"""``pad_share``: the share of padding in the batches the program sent to
the device in the traced window, 1 - real rows / padded cells, summed over
its ``repro.batch.rows`` and ``repro.batch.cells`` counts (one of each per
batch).  None where the program left no such count."""

from chipbench.metrics._spans import program_records

ROWS, CELLS = "repro.batch.rows", "repro.batch.cells"


def read(r):
    recs = program_records(r) or ()
    rows = sum(rec.value for rec in recs if rec.name == ROWS)
    cells = sum(rec.value for rec in recs if rec.name == CELLS)
    if not cells:
        return None
    return 1.0 - rows / cells
