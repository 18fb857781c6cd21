"""``transfer_ms``: host milliseconds per evaluation in ``repro.transfer``,
the host→device copy of the padded batch, to its arrival on the device."""

from chipbench.metrics._spans import span_ms


def read(r):
    return span_ms(r, "repro.transfer")
