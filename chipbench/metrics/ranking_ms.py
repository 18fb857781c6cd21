"""``ranking_ms``: device milliseconds per evaluation in the ranking ops —
the XLA sort on the full-sort route, the top-k Pallas call on the top-k
route (patterns in ``_device.py``)."""

from chipbench.metrics._device import is_ranking, per_call_ms


def read(r):
    if r.trace is None:
        return None
    ops = [op for op in r.trace.all_ops() if is_ranking(op)]
    if not ops:
        return None
    return per_call_ms(sum(op.dur_ns for op in ops), r.calls)
