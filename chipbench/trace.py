"""Profiler traces: record one window, reduce it to device and host events.

A traced run wraps its measured window in :func:`record`.  The JAX profiler
writes an ``.xplane.pb``; :func:`reduce` reads it with
``jax.profiler.ProfileData`` and keeps, inside the window:

* each device's XLA ops (plane ``/device:<kind>:<n>``, line ``XLA Ops``),
  as ``Op(name, opcode, hlo, start_ns, dur_ns)``;
* the union of those intervals per device (device busy time);
* the host's events (plane ``/host:CPU``), to say what the host was doing
  in each idle gap of the device.

The window is the span of the benchmark's own ``chipbench.window``
annotation, so device and host events are read on the same clock.
"""

from __future__ import annotations

import contextlib
import glob
import os
import shutil
import tempfile
from typing import Dict, Iterator, List, NamedTuple, Optional, Sequence, Tuple

import numpy as np

WINDOW_SPAN = "chipbench.window"
#: idle gaps with no host event over their midpoint
NO_HOST_SPAN = "(no host span)"


class Op(NamedTuple):
    name: str  # HLO instruction name, e.g. "sort.16"
    opcode: str  # HLO opcode, e.g. "sort", "fusion", "custom-call"
    hlo: str  # the event's full text: the HLO instruction
    start_ns: float
    dur_ns: float


class Reduced(NamedTuple):
    """One traced window, reduced."""

    window_s: float
    ops: Dict[str, List[Op]]  # device plane name -> its ops in the window
    busy_s: Dict[str, float]  # device plane name -> union of op intervals
    idle_gaps: List[Tuple[str, float]]  # (host activity, idle seconds)

    @property
    def mean_busy_s(self) -> float:
        return sum(self.busy_s.values()) / max(len(self.busy_s), 1)

    def all_ops(self) -> List[Op]:
        return [op for ops in self.ops.values() for op in ops]

    def top_ops(self, n: int = 10) -> List[Tuple[str, float]]:
        """The ``n`` device ops that took most time, summed over calls."""
        total: Dict[str, float] = {}
        for op in self.all_ops():
            total[op.name] = total.get(op.name, 0.0) + op.dur_ns / 1e9
        return sorted(total.items(), key=lambda kv: -kv[1])[:n]


def parse_hlo(text: str) -> Tuple[str, str]:
    """``"%sort.16 = (f32[..], s32[..]) sort(...), ..."`` → ``("sort.16",
    "sort")``; text that is no HLO instruction gives ``(text, "")``."""
    lhs, sep, rhs = text.partition(" = ")
    if not sep:
        return text, ""
    name = lhs.strip().lstrip("%")
    rhs = rhs.lstrip()
    if rhs.startswith("("):  # a tuple shape: skip to its closing paren
        depth = 0
        for i, ch in enumerate(rhs):
            depth += ch == "("
            depth -= ch == ")"
            if depth == 0:
                rhs = rhs[i + 1:]
                break
    else:
        rhs = rhs.split(" ", 1)[1] if " " in rhs else ""
    return name, rhs.lstrip().split("(", 1)[0].strip()


def union_ns(intervals: Sequence[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Merge ``(start, end)`` intervals into disjoint sorted ones."""
    merged: List[List[float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


@contextlib.contextmanager
def record(enabled: bool) -> Iterator[dict]:
    """Trace the body when ``enabled``; yields a dict that holds the
    :class:`Reduced` window under ``"reduced"`` once the body has ended."""
    import jax

    out: dict = {}
    if not enabled:
        yield out
        return
    tmp = tempfile.mkdtemp(prefix="chipbench_trace_")
    try:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(tmp, profiler_options=opts)
        try:
            with jax.profiler.TraceAnnotation(WINDOW_SPAN):
                yield out
        finally:
            jax.profiler.stop_trace()
        out["reduced"] = reduce(find_xplane(tmp))
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def find_xplane(directory: str) -> str:
    paths = glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {directory}, "
                           f"found {len(paths)}")
    return paths[0]


def reduce(path: str) -> Reduced:
    """Reduce one ``.xplane.pb`` to the window of :data:`WINDOW_SPAN`."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    host: Dict[str, List[Tuple[str, float, float]]] = {}
    device_ops: Dict[str, List[Op]] = {}
    for plane in data.planes:
        if plane.name == "/host:CPU":
            for i, line in enumerate(plane.lines):
                host[f"{i}:{line.name}"] = [(e.name, e.start_ns,
                                             e.duration_ns)
                                            for e in line.events]
        elif plane.name.startswith("/device:") and "CUSTOM" not in plane.name:
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops = device_ops.setdefault(plane.name, [])
                    for e in line.events:
                        name, opcode = parse_hlo(e.name)
                        ops.append(Op(name, opcode, e.name, e.start_ns,
                                      e.duration_ns))
    spans = [(s, d) for events in host.values() for n, s, d in events
             if n == WINDOW_SPAN]
    if len(spans) != 1:
        raise RuntimeError(f"expected one {WINDOW_SPAN!r} span in the trace, "
                           f"found {len(spans)}")
    w0, wd = spans[0]
    w1 = w0 + wd
    ops_in: Dict[str, List[Op]] = {}
    busy: Dict[str, float] = {}
    gaps: List[Tuple[float, float]] = []
    for plane, ops in device_ops.items():
        kept = [op for op in ops
                if op.start_ns < w1 and op.start_ns + op.dur_ns > w0]
        ops_in[plane] = kept
        merged = union_ns([(max(op.start_ns, w0),
                            min(op.start_ns + op.dur_ns, w1)) for op in kept])
        busy[plane] = sum(e - s for s, e in merged) / 1e9
        edges = [w0] + [x for se in merged for x in se] + [w1]
        gaps.extend((edges[i], edges[i + 1])
                    for i in range(0, len(edges), 2)
                    if edges[i + 1] > edges[i])
    inner = {line: [(n, s, d) for n, s, d in events
                    if n != WINDOW_SPAN and d > 0 and s < w1 and s + d > w0]
             for line, events in host.items()}
    return Reduced(wd / 1e9, ops_in, busy,
                   _attribute(gaps, {k: v for k, v in inner.items() if v}))


def _attribute(gaps: List[Tuple[float, float]],
               host: Dict[str, List[Tuple[str, float, float]]],
               depth: int = 64) -> List[Tuple[str, float]]:
    """Idle seconds summed by the innermost host event over each gap's
    midpoint (of the events of every host thread that cover it, the one
    that started last), longest first."""
    if not gaps:
        return []
    mids = np.array([(s + e) / 2 for s, e in gaps])
    lengths = np.array([e - s for s, e in gaps]) / 1e9
    best_start = np.full(len(gaps), -np.inf)
    best_name = np.full(len(gaps), NO_HOST_SPAN, dtype=object)
    for events in host.values():
        events = sorted(events, key=lambda h: h[1])
        starts = np.array([h[1] for h in events])
        ends = starts + np.array([h[2] for h in events])
        names = np.array([h[0] for h in events], dtype=object)
        # one thread's events nest, so its innermost cover is among the
        # last few of its events that start before the midpoint
        last = np.searchsorted(starts, mids, side="right") - 1
        found = np.zeros(len(gaps), dtype=bool)
        for back in range(depth):
            idx = last - back
            ok = (idx >= 0) & ~found
            if not ok.any():
                break
            ok[ok] &= ends[idx[ok]] >= mids[ok]
            found |= ok
            take = ok & (starts[np.maximum(idx, 0)] > best_start)
            best_start[take] = starts[idx[take]]
            best_name[take] = names[idx[take]]
    total: Dict[str, float] = {}
    for name, length in zip(best_name.tolist(), lengths.tolist()):
        total[name] = total.get(name, 0.0) + length
    return sorted(total.items(), key=lambda kv: -kv[1])


def breakdown(red: Optional[Reduced], n: int = 10) -> Optional[dict]:
    if red is None:
        return None
    return {"device_ops": [[k, v] for k, v in red.top_ops(n)],
            "idle_gaps": [[k, v] for k, v in red.idle_gaps[:n]]}
