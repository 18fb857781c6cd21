"""Shared arithmetic of the device metrics: the op patterns of the ranking
layer, read from traces of both routes by hand.

* full-sort route (``core/sorting.rank_sort``): XLA ``sort`` ops, e.g.
  ``%sort.16 = (f32[256,1024], s32[256,1024], f32[256,1024]) sort(...)``;
* top-k route (``kernels/topk.py``): the Pallas call, a ``custom-call``
  with ``custom_call_target="tpu_custom_call"`` whose instruction is named
  ``topk``, e.g. ``%topk.1 = (f32[8192,128], s32[8192,128]) custom-call(
  f32[8192,1024] ...)``.
"""

from __future__ import annotations

import re
from typing import List, Optional

SHAPE = re.compile(r"\b([a-z]+\d*)\[([\d,]*)\]")


def is_topk(op) -> bool:
    return (op.opcode == "custom-call" and "tpu_custom_call" in op.hlo
            and op.name.split(".")[0] == "topk")


def is_ranking(op) -> bool:
    return op.opcode == "sort" or is_topk(op)


def per_call_ms(total_ns: float, calls: int) -> Optional[float]:
    return total_ns / 1e6 / calls if calls else None


def shapes(hlo: str) -> List[tuple]:
    """``(dtype, dims)`` of every array shape in an instruction's text, in
    order: results first, then operands."""
    return [(m.group(1), tuple(int(x) for x in m.group(2).split(",") if x))
            for m in SHAPE.finditer(hlo.split(" custom_call_target")[0])]


def idle_share(trace) -> Optional[float]:
    if trace is None or not trace.busy_s or trace.window_s <= 0:
        return None
    return 1.0 - trace.mean_busy_s / trace.window_s
