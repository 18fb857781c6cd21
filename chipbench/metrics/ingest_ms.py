"""``ingest_ms``: host milliseconds per evaluation in ``repro.ingest``, the
host's ingest: fresh scores set on the buffer and the scatter of the flat
run into the padded ``[Q, D]`` slabs (``batch_from_buffer``)."""

from chipbench.metrics._spans import span_ms


def read(r):
    return span_ms(r, "repro.ingest")
