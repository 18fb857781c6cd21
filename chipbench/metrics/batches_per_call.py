"""``batches_per_call``: batches the program sent to the device per
evaluation in the traced window, its ``repro.batch.cells`` counts (one per
batch) / its ``repro.evaluate`` spans.  None where the program left no
such count."""

from chipbench.metrics._spans import program_records

CELLS, EVALUATE = "repro.batch.cells", "repro.evaluate"


def read(r):
    names = [rec.name for rec in program_records(r) or ()]
    batches, calls = names.count(CELLS), names.count(EVALUATE)
    if not batches or not calls:
        return None
    return batches / calls
