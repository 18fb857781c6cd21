"""Closed loop over the library: one caller re-scores one run, call after call.

This is a reranker or fusion sweep, or a ranker's validation loop: the
collection and the run's documents stay, the scores change every call.
Set-up builds a ``RelevanceEvaluator`` and tokenizes the run once with
``buffer_from_arrays``; each timed call is
``RelevanceEvaluator.evaluate_buffer(buf, scores=s_i)``, the library's
string-free entry, through host ingest, ranking, measure columns and the
per-query dicts.

Call ``i`` scores the run with its own scores plus a slice of a seeded noise
pool, at an offset no earlier call used, so no call repeats an earlier
call's scores and making them is one vector add.

Traffic parameters (``chipbench/traffic/<mix>.json``):
``fresh_offsets`` (distinct noise offsets, an upper bound on the calls of a
window that carry unseen scores), ``noise_steps`` (noise in whole score-grid
steps, ``-n..n``), ``warm_calls`` (calls made in set-up).
"""

from __future__ import annotations

import contextlib
import time
import traceback

import numpy as np

from chipbench import check, harness, reference
from chipbench import trace as tr



class ScoreStream:
    """Scores of call ``i``: the run's own plus ``noise[o_i : o_i + n]``."""

    def __init__(self, base: np.ndarray, grid: float, seed: int,
                 offsets: int, steps: int):
        rng = np.random.default_rng([seed, 0x5C0E])
        self.base = np.asarray(base, dtype=np.float32)
        n = self.base.shape[0]
        self.noise = (rng.integers(-steps, steps + 1, n + offsets)
                      * grid).astype(np.float32)
        self.offsets = rng.permutation(offsets)

    def scores(self, i: int) -> np.ndarray:
        o = self.offsets[i % len(self.offsets)]
        return self.base + self.noise[o:o + self.base.shape[0]]


def run(ctx: harness.Context) -> harness.Outcome:
    import jax

    from repro.core import RelevanceEvaluator
    from repro.kernels import bucketing

    cfg, mix = ctx.cell.config, ctx.cell.traffic
    coll = ctx.collection()
    ev = RelevanceEvaluator(coll.qrel, cfg["measures"])
    buf = ev.buffer_from_arrays(coll.qids, coll.docnos, coll.scores)
    firsts = np.cumsum(np.concatenate([[0], buf.counts[:-1]]))
    if list(buf.qids) != coll.qids[firsts].tolist():
        raise harness.BenchError("the run's rows are not in the buffer's "
                                 "order; fresh scores would be misplaced")
    stream = ScoreStream(coll.scores, cfg["score_grid"], ctx.seed,
                         mix["fresh_offsets"], mix["noise_steps"])
    for i in range(mix["warm_calls"]):  # the cell's one shape, compiled
        ev.evaluate_buffer(buf, scores=stream.scores(-1 - i))

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
                s = stream.scores(i)
                b = time.monotonic()
            try:
                with span("chipbench.call"):
                    res = ev.evaluate_buffer(buf, scores=s)
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
    del ev, buf  # the program's state goes before the reference runs

    reading = check.Reading()
    for k, got in keep.items:
        run_k = check.run_dict(coll.qids, coll.docnos, stream.scores(k))
        want = reference.evaluate(run_k, coll.qrel, cfg["reference_measures"])
        reading.add(got, want, cfg["keys"])
    ctx.note(setup_s=t_start - ctx.t0, calls=i, window_s=window,
             score_making_share=making / window,
             compiles_in_window=compiles,
             compared_answers=len(keep.items),
             compared_queries=reading.compared_queries,
             compared_values=reading.compared_values,
             widest_gap_at=repr(reading.where))
    done = i - failed
    walls_ms = np.asarray(walls) * 1e3
    return harness.Outcome(
        attempted=i, failed=failed,
        end_to_end={"runs_per_s": done / window,
                    "eval_ms_p95": float(np.percentile(walls_ms, 95)),
                    "setup_s": t_start - ctx.t0},
        checks=reading.checks(cfg["check"]["max_abs_diff"], failed),
        memory_peak_bytes=peak,
        reduced=rec.get("reduced"), calls_traced=i if ctx.trace else 0)
