"""Power-of-two shape bucketing + trace-time recompile accounting.

Every jit'd engine in this codebase (the packed measure cores, on one
device or on a mesh, and the Pallas kernels) compiles once per *shape
signature*.  Left unbucketed, the serve layer's variable wave sizes — a
coalesced batch of k requests has a query axis proportional to k — would
trigger one XLA compile per distinct wave, re-introducing exactly the fixed
per-call overhead the paper set out to kill.  This module centralizes the
fix:

* **padding classes** — batch extents are padded UP to the next power of
  two (``bucket_queries`` / ``bucket_docs``), so every possible extent in
  ``[1, max]`` maps onto one of ``log2(max) + O(1)`` classes.  A
  concurrency sweep over any number of distinct wave sizes therefore
  compiles at most ``log2(max_batch) + O(1)`` signatures, not one per
  wave.  Padded rows/columns carry ``mask == False`` and are inert for
  every measure, so bucketing never changes a value;
* **recompile accounting** — :func:`record_trace` is called from INSIDE
  the jit'd function bodies.  Python side effects in a traced function run
  exactly once per trace (i.e. once per compiled signature), so the
  counters are a true retrace count: tests assert the closed-set property
  directly (``tests/test_bucketing.py``) and ``benchmarks.run --only
  kernels`` reports it next to achieved bandwidth.

The module is dependency-free (no jax, no numpy) so any layer — the
evaluator's host-side padding, the kernels, the benchmarks — can import it
without cycles.
"""

from __future__ import annotations

import threading
from typing import Dict, Iterable, Optional, Tuple

__all__ = [
    "next_pow2", "bucket_queries", "bucket_docs", "padding_classes",
    "max_signatures", "record_trace", "compile_count", "trace_counts",
    "reset_trace_counts",
]

#: default minimum document-axis bucket (matches the evaluator's historical
#: padding floor; one VPU lane group is never worth splitting below)
MIN_DOC_BUCKET = 8


def next_pow2(n: int, minimum: int = 1) -> int:
    """Smallest power of two (times ``minimum``) that is >= ``n``.

    ``minimum`` must itself be the smallest admissible bucket; the result
    is ``minimum * 2**j`` for the smallest ``j`` with that product >= n.

    >>> [next_pow2(n) for n in (1, 2, 3, 9, 1000)]
    [1, 2, 4, 16, 1024]
    """
    b = minimum
    while b < n:
        b *= 2
    return b


def bucket_queries(nq: int, minimum: int = 1, multiple: int = 1) -> int:
    """Padding class for a query-axis extent.

    Power-of-two bucketing, then rounded up to ``multiple`` so the batch
    divides evenly over a device mesh (``ShardedEvaluator`` passes its
    shard count).  For a fixed ``multiple`` the image of ``[1, max]`` is
    still a closed set of ``log2(max) + O(1)`` classes.

    >>> bucket_queries(37)
    64
    >>> bucket_queries(5, multiple=3)
    9
    """
    b = next_pow2(max(nq, 1), minimum)
    if multiple > 1:
        b = ((b + multiple - 1) // multiple) * multiple
    return b


def bucket_docs(nd: int, minimum: int = MIN_DOC_BUCKET) -> int:
    """Padding class for a document- (or judged-) axis extent.

    >>> bucket_docs(100), bucket_docs(3), bucket_docs(1000)
    (128, 8, 1024)
    """
    return next_pow2(max(nd, 1), minimum)


def padding_classes(max_n: int, minimum: int = 1,
                    multiple: int = 1) -> Tuple[int, ...]:
    """The closed set of classes extents in ``[1, max_n]`` can map to.

    This is what "recompile-proof" means operationally: however many
    distinct raw extents a workload produces, the compiled-signature count
    is bounded by ``len(padding_classes(max_n))``.

    >>> padding_classes(16)
    (1, 2, 4, 8, 16)
    """
    out = []
    b = minimum
    while True:
        c = bucket_queries(b, minimum, multiple)
        if not out or c != out[-1]:
            out.append(c)
        if c >= max_n and b >= max_n:
            break
        b *= 2
    return tuple(out)


def max_signatures(max_n: int, minimum: int = 1, multiple: int = 1) -> int:
    """Upper bound on compiled signatures for extents in ``[1, max_n]``."""
    return len(padding_classes(max_n, minimum, multiple))


# ---------------------------------------------------------------------------
# Trace-time compile counters.
# ---------------------------------------------------------------------------

_lock = threading.Lock()
_counts: Dict[str, int] = {}


def record_trace(name: str) -> None:
    """Count one retrace of the named engine.

    Call from INSIDE a jit'd function body: the call executes at trace
    time only, so each increment corresponds to one new compiled
    signature entering that engine's jit cache.  Thread-safe (traces can
    run on executor threads).  While a profiler session records, it also
    leaves a zero-length ``repro.compile.<name>`` record (:mod:`repro.obs`)
    under the span whose call caused the retrace.
    """
    with _lock:
        _counts[name] = _counts.get(name, 0) + 1
    from repro import obs  # lazy: this module stays free of jax imports

    obs.mark(f"repro.compile.{name}")


def compile_count(name: Optional[str] = None) -> int:
    """Retraces recorded for ``name`` (or the total across all engines)."""
    with _lock:
        if name is not None:
            return _counts.get(name, 0)
        return sum(_counts.values())


def trace_counts() -> Dict[str, int]:
    """Snapshot of every engine's retrace count (for ``--only kernels``)."""
    with _lock:
        return dict(_counts)


def reset_trace_counts(names: Optional[Iterable[str]] = None) -> None:
    """Zero the counters (all of them, or just ``names``).

    Note this resets the *accounting*, not the process-global jit caches:
    a shape compiled before the reset will not retrace afterwards.  Tests
    should assert on deltas with fresh static signatures instead.
    """
    with _lock:
        if names is None:
            _counts.clear()
        else:
            for n in names:
                _counts.pop(n, None)
