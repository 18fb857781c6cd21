"""``topk_roofline``: the top-k Pallas kernel's share of its roofline, in %.

Least time = bytes / peak HBM bytes per second; share = least time summed
over the kernel's calls / the kernel's device time.  Bytes per call come
from the call's padded shapes (``topk_bytes``): the ``[Q, D]`` float32
scores read once, the ``[Q, K]`` values and indices written once.  The
kernel is a compare-and-select network with no MXU work, so the published
FLOP/s peak bounds nothing here and only the bytes set the least time.
"""

from chipbench.metrics._device import is_topk, shapes

BYTES = {"f32": 4, "s32": 4, "bf16": 2, "u32": 4, "pred": 1}


def topk_bytes(hlo: str) -> int:
    """Bytes the call must move: its operands read, its results written."""
    return sum(BYTES[dt] * _prod(dims) for dt, dims in shapes(hlo))


def _prod(dims) -> int:
    n = 1
    for d in dims:
        n *= d
    return n


def read(r):
    if r.trace is None:
        return None
    ops = [op for op in r.trace.all_ops() if is_topk(op)]
    if not ops:
        return None
    bw = r.peaks["hbm_bytes_per_s"]
    least_ns = sum(topk_bytes(op.hlo) / bw * 1e9 for op in ops)
    return 100.0 * least_ns / sum(op.dur_ns for op in ops)
