"""``transfer_mb_per_call``: megabytes the program sent to the device per
evaluation in the traced window, the bytes its ``repro.transfer.bytes``
counts carry (one count per ``jax.device_put``) ÷ its ``repro.evaluate``
spans ÷ 1e6.  None where the program left no such count."""

from chipbench.metrics._spans import program_records

BYTES, EVALUATE = "repro.transfer.bytes", "repro.evaluate"


def read(r):
    recs = program_records(r) or ()
    sent = [rec.value for rec in recs if rec.name == BYTES]
    calls = sum(rec.name == EVALUATE for rec in recs)
    if not sent or not calls:
        return None
    return sum(sent) / calls / 1e6
