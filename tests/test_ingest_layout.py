"""A buffer's padded layout is built once and reused for fresh scores.

``RelevanceEvaluator.batch_from_buffer`` keeps the score-independent part
of the ``EvalBatch`` (and where each score lands) on the buffer, shared by
every buffer ``with_scores`` derives from it.  Each batch it hands back
must still equal, field by field and bit for bit, the batch of a buffer
seen for the first time, and the plain 2-D scatter of the flat arrays.
"""

import contextlib
import itertools

import jax
import numpy as np
import pytest

from repro import obs
from repro.core import RelevanceEvaluator, RunBuffer
from repro.kernels import bucketing

NQ = 7


def make_case(uniform, oov, seed=0):
    """A qrel over ``d000..d079`` and a run of ``NQ`` queries on a score
    grid (so ties need the docno tie-break), fixed or varying depth, with
    or without documents the qrels never mention."""
    rng = np.random.default_rng(seed)
    qrel, run = {}, {}
    for q in range(NQ):
        depth = 12 if uniform else int(rng.integers(1, 30))
        pool = [f"d{j:03d}" for j in range(80)]
        if oov:
            pool += [f"zz{j}" for j in range(6)]
        docs = rng.choice(pool, depth, replace=False).tolist()
        run[f"q{q}"] = {d: float(rng.integers(0, 4)) for d in docs}
        judged = rng.choice(80, 15, replace=False)
        qrel[f"q{q}"] = {f"d{j:03d}": int(rng.integers(0, 3)) for j in judged}
    return qrel, run


def fresh(buf, scores):
    """The same collection as a buffer never evaluated before."""
    return RunBuffer(buf.qids, buf.gidx, buf.qidx, buf.col, buf.counts,
                     buf.rel, buf.judged, buf.tiebreak, scores)


def plain(ev, buf, scores, q_multiple, topk):
    """The batch by a plain 2-D scatter of every flat field."""
    nq = len(buf)
    q_pad = bucketing.bucket_queries(nq, multiple=q_multiple)
    d_pad = bucketing.bucket_docs(int(buf.counts.max()))
    jrows = ev._ideal[buf.gidx]
    j_pad = bucketing.bucket_docs(int(ev._judged_counts[buf.gidx].max()))
    col = buf.tiebreak if topk else buf.col
    out = {}
    for name, flat, dtype in (("scores", scores, np.float32),
                              ("tiebreak", buf.tiebreak, np.int32),
                              ("rel", buf.rel, np.float32),
                              ("judged", buf.judged, bool),
                              ("mask", True, bool)):
        out[name] = np.zeros((q_pad, d_pad), dtype=dtype)
        out[name][buf.qidx, col] = flat
    out["ideal_rel"] = np.zeros((q_pad, j_pad), dtype=np.float32)
    w = min(j_pad, jrows.shape[1])
    out["ideal_rel"][:nq, :w] = jrows[:, :w]
    for name, per_query, dtype in (
            ("n_rel", ev._n_rel[buf.gidx], np.float32),
            ("n_judged_nonrel", ev._n_nonrel[buf.gidx], np.float32),
            ("query_mask", True, bool)):
        out[name] = np.zeros(q_pad, dtype=dtype)
        out[name][:nq] = per_query
    return out


def assert_same(got, want):
    for field in got._fields:
        a = np.asarray(getattr(got, field))
        b = np.asarray(want[field] if isinstance(want, dict)
                       else getattr(want, field))
        assert a.dtype == b.dtype and a.shape == b.shape, field
        np.testing.assert_array_equal(a, b, err_msg=field)


def rescored(buf, k, seed=1):
    rng = np.random.default_rng(seed)
    return [buf.scores + rng.integers(-2, 3, buf.scores.shape[0])
            .astype(np.float32) for _ in range(k)]


@contextlib.contextmanager
def profiled(log_dir):
    """A profiler session, so ``obs`` keeps the program's marks."""
    obs.clear()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with jax.profiler.trace(str(log_dir), profiler_options=opts):
        yield


@pytest.mark.parametrize(
    "topk,uniform,q_multiple,oov",
    list(itertools.product([True, False], [True, False], [1, 3],
                           [True, False])),
    ids=lambda v: {True: "T", False: "F"}.get(v, str(v)))
def test_cached_batch_equals_a_fresh_buffers(topk, uniform, q_multiple, oov):
    qrel, run = make_case(uniform, oov)
    ev = RelevanceEvaluator(qrel, {"map", "ndcg_cut"})
    buf = ev.tokenize_run(run)
    assert (int(buf.counts.min()) == int(buf.counts.max())) == uniform
    for scores in rescored(buf, 4):
        got = ev.batch_from_buffer(buf, scores, q_multiple=q_multiple,
                                   topk_layout=topk)
        want = ev.batch_from_buffer(fresh(buf, scores),
                                    q_multiple=q_multiple, topk_layout=topk)
        assert_same(got, want)
        assert_same(got, plain(ev, buf, scores, q_multiple, topk))
    assert buf.layout[0] is not None


@pytest.mark.parametrize("topk", [True, False], ids=["topk", "natural"])
def test_with_scores_carries_the_layout_and_later_calls_build_nothing(
        topk, tmp_path):
    qrel, run = make_case(uniform=not topk, oov=topk)
    ev = RelevanceEvaluator(qrel, {"map"})
    buf = ev.tokenize_run(run)
    scores = rescored(buf, 3)
    with profiled(tmp_path):
        for s in scores:
            ev.batch_from_buffer(buf, s, topk_layout=topk)
        held = buf.layout[0]
        again = buf.with_scores(scores[0])
        assert again.layout is buf.layout
        ev.batch_from_buffer(again, topk_layout=topk)
        assert buf.layout[0] is held
    names = [r.name for r in obs.records()]
    assert names.count("repro.layout.build") == 1
    assert names.count("repro.layout.hit") == 3


@pytest.mark.parametrize("uniform", [True, False], ids=["uniform", "ragged"])
def test_other_padding_layout_or_evaluator_rebuilds(uniform):
    qrel, run = make_case(uniform, oov=False)
    ev = RelevanceEvaluator(qrel, {"map"})
    ev2 = RelevanceEvaluator(qrel, {"map"}, relevance_level=2)
    buf = ev.tokenize_run(run)
    s = rescored(buf, 1)[0]
    seen = []
    for e, q_multiple, topk in ((ev, 1, False), (ev, 3, False),
                                (ev, 3, True), (ev2, 3, True),
                                (ev, 1, False)):
        got = e.batch_from_buffer(buf, s, q_multiple=q_multiple,
                                  topk_layout=topk)
        assert_same(got, plain(e, buf, s, q_multiple, topk))
        layout = buf.layout[0][1]
        assert all(layout is not old for old in seen)
        seen.append(layout)
    assert not np.array_equal(*(e.batch_from_buffer(buf, s).n_rel
                                for e in (ev, ev2)))


@pytest.mark.parametrize("uniform", [True, False], ids=["uniform", "ragged"])
def test_static_slabs_read_only_and_scores_slab_new_each_call(uniform):
    qrel, run = make_case(uniform, oov=True)
    ev = RelevanceEvaluator(qrel, {"map"})
    buf = ev.tokenize_run(run)
    s1, s2 = rescored(buf, 2)
    b1 = ev.batch_from_buffer(buf, s1)
    b2 = ev.batch_from_buffer(buf, s2)
    for field in b1._fields[1:]:
        a = getattr(b1, field)
        assert a is getattr(b2, field)
        assert not a.flags.writeable
        with pytest.raises(ValueError):
            a[...] = 0
    assert b1.scores.flags.writeable and b2.scores.flags.writeable
    assert not np.shares_memory(b1.scores, b2.scores)
    assert_same(b1, plain(ev, buf, s1, 1, False))


@pytest.mark.parametrize("route", ["topk", "sort"])
def test_evaluate_buffer_equals_evaluate_after_ten_rescorings(route):
    rng = np.random.default_rng(3)
    depth = 600 if route == "topk" else 40
    qrel = {f"q{q}": {f"d{j:04d}": int(rng.integers(0, 3))
                      for j in rng.choice(depth + 50, 20, replace=False)}
            for q in range(3)}
    run = {q: {f"d{j:04d}": float(rng.integers(0, 50))
               for j in rng.choice(depth + 50, depth, replace=False)}
           for q in qrel}
    measures = ({"ndcg_cut_10", "P_10"} if route == "topk"
                else {"map", "ndcg", "recip_rank"})
    ev = RelevanceEvaluator(qrel, measures)
    buf = ev.tokenize_run(run)
    d_pad = bucketing.bucket_docs(int(buf.counts.max()))
    assert ev._route_topk(d_pad) == (route == "topk")
    for scores in rescored(buf, 10):
        got = ev.evaluate_buffer(buf, scores=scores)
        flat = iter(scores.tolist())
        want = ev.evaluate({q: {d: next(flat) for d in docs}
                            for q, docs in run.items()})
        assert got == want
    assert buf.layout[0] is not None
