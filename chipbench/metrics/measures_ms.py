"""``measures_ms``: device busy milliseconds per evaluation outside the
ranking ops — the measure columns (``core/registry`` over the
``SortedBatch``) and the layout work around them.  Ops on one device run
one after another, so busy time less ranking time is the rest."""

from chipbench.metrics._device import is_ranking, per_call_ms


def read(r):
    if r.trace is None or not r.trace.ops:
        return None
    ranking_ns = sum(op.dur_ns for op in r.trace.all_ops() if is_ranking(op))
    busy_ns = sum(r.trace.busy_s.values()) * 1e9
    return per_call_ms(busy_ns - ranking_ns, r.calls)
