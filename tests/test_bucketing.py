"""Shape bucketing and the recompile-bound contract.

The tentpole claim of the bucketing layer is operational: however many
distinct raw batch extents a workload produces, the number of compiled jit
signatures stays within the closed set ``padding_classes`` describes.  The
sweep test at the bottom drives the REAL serve path (coalesced waves of 8+
distinct sizes through :class:`EvaluationService`) and asserts the bound on
the trace-time compile counters — the honest count, recorded from inside
the jit'd bodies themselves.
"""

import asyncio
import math
import threading

import numpy as np
import pytest

from repro.kernels import bucketing


# -- padding classes ---------------------------------------------------------

def test_next_pow2_basics():
    assert [bucketing.next_pow2(n) for n in (1, 2, 3, 4, 5, 9, 1000)] == \
        [1, 2, 4, 4, 8, 16, 1024]
    assert bucketing.next_pow2(3, minimum=8) == 8
    assert bucketing.next_pow2(17, minimum=8) == 32


def test_bucket_queries_pow2_then_multiple():
    assert bucketing.bucket_queries(37) == 64
    assert bucketing.bucket_queries(1) == 1
    assert bucketing.bucket_queries(0) == 1  # degenerate extent still padded
    # shard-aware rounding happens AFTER the pow2 bucket
    assert bucketing.bucket_queries(5, multiple=3) == 9
    assert bucketing.bucket_queries(8, multiple=4) == 8


def test_bucket_docs_floor():
    assert bucketing.bucket_docs(3) == bucketing.MIN_DOC_BUCKET
    assert bucketing.bucket_docs(100) == 128
    assert bucketing.bucket_docs(1000) == 1024


def test_padding_classes_are_closed_and_complete():
    classes = bucketing.padding_classes(64)
    assert classes == (1, 2, 4, 8, 16, 32, 64)
    # completeness: every admissible extent maps INTO the closed set
    for n in range(1, 65):
        assert bucketing.bucket_queries(n) in classes
    assert bucketing.max_signatures(64) == len(classes)


def test_padding_classes_respect_multiple():
    classes = bucketing.padding_classes(16, multiple=4)
    for n in range(1, 17):
        b = bucketing.bucket_queries(n, multiple=4)
        assert b % 4 == 0
        assert b in classes


def test_signature_bound_is_logarithmic():
    # the whole point: 10_000 possible extents, ~log2 signatures
    assert bucketing.max_signatures(10_000) <= math.log2(10_000) + 2


# -- trace counters ----------------------------------------------------------

def test_trace_counters_roundtrip():
    name = "test_counter_roundtrip"
    bucketing.reset_trace_counts([name])
    assert bucketing.compile_count(name) == 0
    bucketing.record_trace(name)
    bucketing.record_trace(name)
    assert bucketing.compile_count(name) == 2
    assert bucketing.trace_counts()[name] == 2
    bucketing.reset_trace_counts([name])
    assert bucketing.compile_count(name) == 0


def test_trace_counters_thread_safe():
    name = "test_counter_threads"
    bucketing.reset_trace_counts([name])
    threads = [threading.Thread(
        target=lambda: [bucketing.record_trace(name) for _ in range(200)])
        for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert bucketing.compile_count(name) == 8 * 200


# -- the recompile-bound contract on the real serve path --------------------

def test_serve_wave_sweep_compiles_bounded_signatures():
    """≥8 distinct coalesced wave sizes → at most log2(max_batch)+2 compiles.

    Drives the full request path: concurrent ``evaluate`` calls coalesce
    into waves, each wave concatenates into one RunBuffer whose query axis
    is the wave size, ``batch_from_buffer`` pads it through the bucketing
    module, and the measure core jit-compiles per *padded* signature.  A
    one-off measure tuple keys fresh jit entries, so the counter delta is
    exactly this test's compiles.
    """
    from repro.serve import EvaluationService

    max_batch = 64
    wave_sizes = [1, 2, 3, 5, 9, 17, 33, 64]  # 8 distinct raw sizes
    assert len(set(wave_sizes)) >= 8
    qrel = {"q1": {"d1": 1, "d2": 0, "d3": 1}}
    run = {"q1": {"d1": 0.9, "d2": 0.5, "d3": 0.1}}
    # fresh static jit key: this measure pair is used nowhere else
    measures = ("map_cut_30", "success_5")

    async def sweep():
        svc = EvaluationService(window=0.01, max_batch=max_batch,
                                backend="single")
        svc.register_qrel("sweep", qrel, measures)
        for k in wave_sizes:
            res = await asyncio.gather(
                *(svc.evaluate("sweep", run=run) for _ in range(k)))
            assert len(res) == k
            for r in res:
                assert r.per_query["q1"]["success_5"] == 1.0

    before = bucketing.compile_count("measure_core")
    asyncio.run(sweep())
    compiled = bucketing.compile_count("measure_core") - before
    bound = math.log2(max_batch) + 2
    assert 0 < compiled <= bound, (
        f"{len(wave_sizes)} distinct wave sizes compiled {compiled} "
        f"measure-core signatures; bucketing promises <= {bound}")
    # and the closed set predicted by padding_classes really covers it
    assert compiled <= bucketing.max_signatures(max_batch)


def test_evaluator_padding_uses_shared_buckets():
    """batch_from_buffer's padded axes land exactly on the bucket classes."""
    from repro.core import RelevanceEvaluator

    qrel = {f"q{i}": {f"d{j}": int(j < 2) for j in range(5)}
            for i in range(3)}
    run = {f"q{i}": {f"d{j}": float(10 - j) for j in range(5)}
           for i in range(3)}
    ev = RelevanceEvaluator(qrel, ("map",))
    batch = ev.batch_from_buffer(ev.tokenize_run(run))
    q_pad, d_pad = batch.scores.shape
    assert q_pad == bucketing.bucket_queries(3)
    assert d_pad == bucketing.bucket_docs(5)
    # shard-aware rounding still applies on top of the pow2 class
    batch6 = ev.batch_from_buffer(ev.tokenize_run(run), q_multiple=6)
    assert batch6.scores.shape[0] == bucketing.bucket_queries(3, multiple=6)
