"""``fetch_ms``: host milliseconds per evaluation in ``repro.fetch``,
the device→host copies of the measure columns, one per column."""

from chipbench.metrics._spans import span_ms


def read(r):
    return span_ms(r, "repro.fetch")
