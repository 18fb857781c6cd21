"""``tokenize_ms``: host milliseconds per evaluation in ``repro.tokenize``,
the dict-of-dicts run turned into a token buffer: every docno interned
through the qrels' token map and, where any docno of a chunk is absent
from the qrels, the chunk's exact string pipeline.  Under ``evaluate`` it
is a part of ``repro.ingest``."""

from chipbench.metrics._spans import span_ms


def read(r):
    return span_ms(r, "repro.tokenize")
