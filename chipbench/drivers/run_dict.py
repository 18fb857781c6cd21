"""Closed loop over pytrec_eval's own API: one caller evaluates a fresh
``{qid: {docno: score}}`` run, call after call.

This is the caller pytrec_eval was written for: a reranker or a learning
loop that holds its candidate lists, scores them anew and hands the run to
``RelevanceEvaluator.evaluate`` as a dict of dicts.  Set-up builds one
``RelevanceEvaluator`` on the collection's qrels, as such a caller keeps
one for its whole loop; each timed call is ``ev.evaluate(run_i)``, through
tokenization of every docno, the layout of a buffer seen for the first
time, transfer, ranking, measure columns and the per-query dicts.

``run_i`` is built before its call, outside the call's wall time and
inside the window: per query one new dict of the run's documents to call
``i``'s scores (the library driver's ``ScoreStream``: the run's own scores
plus a slice of a seeded noise pool at an offset no earlier call used).
The docno strings are the same objects on every call, as a caller's held
candidate lists would give them; the dicts and floats are new.

Traffic parameters (``chipbench/traffic/<mix>.json``): ``fresh_offsets``,
``noise_steps`` and ``warm_calls``, as for the library driver.
"""

from __future__ import annotations

import contextlib
import time
import traceback

import numpy as np

from chipbench import check, harness, reference
from chipbench import trace as tr
from chipbench.drivers.library import ScoreStream


class RunDicts:
    """Run dicts of call ``i``: the collection's documents, grouped by
    query, to the scores of ``stream.scores(i)``."""

    def __init__(self, qids: np.ndarray, docnos: np.ndarray,
                 stream: ScoreStream):
        self.stream = stream
        starts = np.flatnonzero(np.r_[True, qids[1:] != qids[:-1]])
        if len(set(qids[starts].tolist())) != len(starts):
            raise harness.BenchError("the run's rows are not grouped by "
                                     "query")
        bounds = np.r_[starts, len(qids)].tolist()
        names = docnos.tolist()
        self.groups = [(q, names[lo:hi], lo, hi) for q, lo, hi in
                       zip(qids[starts].tolist(), bounds, bounds[1:])]

    def run(self, i: int) -> dict:
        scores = self.stream.scores(i).tolist()
        return {q: dict(zip(docs, scores[lo:hi]))
                for q, docs, lo, hi in self.groups}

    def oov_share(self, qrel: dict) -> float:
        """Share of the run's rows whose docno no topic's qrels hold."""
        judged = {d for docs in qrel.values() for d in docs}
        docs = [d for _, names, _, _ in self.groups for d in names]
        return sum(d not in judged for d in docs) / max(len(docs), 1)


def run(ctx: harness.Context) -> harness.Outcome:
    import jax

    from repro.core import RelevanceEvaluator
    from repro.kernels import bucketing

    cfg, mix = ctx.cell.config, ctx.cell.traffic
    coll = ctx.collection()
    ev = RelevanceEvaluator(coll.qrel, cfg["measures"])
    stream = ScoreStream(coll.scores, cfg["score_grid"], ctx.seed,
                         mix["fresh_offsets"], mix["noise_steps"])
    dicts = RunDicts(coll.qids, coll.docnos, stream)
    for i in range(mix["warm_calls"]):  # the cell's one shape, compiled
        ev.evaluate(dicts.run(-1 - i))

    span = jax.profiler.TraceAnnotation if ctx.trace else (
        lambda name: contextlib.nullcontext())
    keep = check.Reservoir(cfg["check"]["answers"], ctx.seed)
    walls, making, failed = [], 0.0, 0
    compiles = sum(bucketing.trace_counts().values())
    with tr.record(ctx.trace) as rec:
        t_start = t_end = time.monotonic()
        i = 0
        while t_end - t_start < ctx.seconds:
            with span("chipbench.scores"):
                a = time.monotonic()
                run_i = dicts.run(i)
                b = time.monotonic()
            try:
                with span("chipbench.call"):
                    res = ev.evaluate(run_i)
            except Exception:  # noqa: BLE001 — a call that fails is counted
                if not failed:
                    traceback.print_exc()
                failed += 1
                res = None
            t_end = time.monotonic()
            making += b - a
            walls.append(t_end - b)
            if res is not None:
                keep.offer(i, res)
            i += 1
    compiles = sum(bucketing.trace_counts().values()) - compiles
    window = t_end - t_start
    peak = harness.device_peak_bytes(ctx.devices)
    del ev  # the program's state goes before the reference runs

    reading = check.Reading()
    for k, got in keep.items:
        run_k = check.run_dict(coll.qids, coll.docnos, stream.scores(k))
        want = reference.evaluate(run_k, coll.qrel, cfg["reference_measures"])
        reading.add(got, want, cfg["keys"])
    ctx.note(setup_s=t_start - ctx.t0, calls=i, window_s=window,
             score_making_share=making / window,
             call_ms_median=float(np.median(walls)) * 1e3,
             oov_row_share=dicts.oov_share(coll.qrel),
             compiles_in_window=compiles,
             compared_answers=len(keep.items),
             compared_queries=reading.compared_queries,
             compared_values=reading.compared_values,
             widest_gap_at=repr(reading.where))
    done = i - failed
    return harness.Outcome(
        attempted=i, failed=failed,
        end_to_end={"runs_per_s": done / window,
                    "setup_s": t_start - ctx.t0},
        checks=reading.checks(cfg["check"]["max_abs_diff"], failed),
        memory_peak_bytes=peak,
        reduced=rec.get("reduced"), calls_traced=i if ctx.trace else 0)
