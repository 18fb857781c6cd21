"""``results_ms``: host milliseconds per evaluation in ``repro.results``,
building the per-query result dicts from the fetched columns."""

from chipbench.metrics._spans import span_ms


def read(r):
    return span_ms(r, "repro.results")
