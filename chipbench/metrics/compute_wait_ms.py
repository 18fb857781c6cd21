"""``compute_wait_ms``: host milliseconds per evaluation in ``repro.compute``,
the measure core's dispatch and the wait for its outputs on the device."""

from chipbench.metrics._spans import span_ms


def read(r):
    return span_ms(r, "repro.compute")
