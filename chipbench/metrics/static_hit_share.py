"""``static_hit_share``: of the depth-class batches sent to the device in
the traced window, the share whose static slabs were on the device already
(``repro.transfer.static_hit`` marks ÷ those and
``repro.transfer.static_upload`` marks).  None where the program left
neither mark in the window."""

from chipbench.metrics._spans import program_records

HIT, UPLOAD = "repro.transfer.static_hit", "repro.transfer.static_upload"


def read(r):
    names = [rec.name for rec in program_records(r) or ()]
    hits, uploads = names.count(HIT), names.count(UPLOAD)
    if not hits + uploads:
        return None
    return hits / (hits + uploads)
