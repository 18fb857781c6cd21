"""Ragged runs are evaluated in depth classes, not one padded rectangle.

A buffer's queries are grouped by the padding class of their own list
length (``bucketing.bucket_docs``); each class is padded, routed (full sort
or top-k) and measured on its own, and the answers come back in the
buffer's query order.  Every entry point must agree with the plain
reference (``baselines/pure_eval.py``) within 1e-5, a uniform buffer must
stay the one rectangle it always was, and the split must be built once per
buffer and compile a closed set of signatures.
"""

import contextlib

import jax
import numpy as np
import pytest

from repro import obs
from repro.baselines import pure_eval
from repro.core import RelevanceEvaluator
from repro.kernels import bucketing

TOL = 1e-5
#: list lengths every case holds: eight depth classes, 8 through 1024
FIXED_LENGTHS = [1, 3, 8, 9, 16, 17, 40, 100, 200, 300, 513, 600]
UNBOUNDED = ("map", "ndcg", "recip_rank", "bpref", "Rprec")
BOUNDED = ("nDCG@5", "nDCG@10", "P@10")
REFERENCE = {UNBOUNDED: UNBOUNDED, BOUNDED: ("ndcg_cut", "P")}


def ragged_case(seed, nq=24, max_len=600, no_rel=3):
    """Graded 0-4 judgments of every listed document, a few judged ones
    outside the list, scores on a 0.5 grid (ties), and ``no_rel``
    queries whose judgments are all 0."""
    rng = np.random.default_rng(seed)
    lengths = FIXED_LENGTHS + rng.integers(
        1, max_len + 1, nq - len(FIXED_LENGTHS)).tolist()
    qrel, run = {}, {}
    for q, n in enumerate(lengths):
        qid = f"q{q:03d}"
        docs = [f"{qid}-d{j:04d}" for j in rng.permutation(n + 4)]
        grades = rng.choice(5, n + 4, p=[0.52, 0.32, 0.13, 0.02, 0.01])
        if q < no_rel:
            grades[:] = 0
        qrel[qid] = dict(zip(docs, grades.tolist()))
        scores = np.round(2 * (grades[:n] + rng.normal(size=n))) / 2
        run[qid] = dict(zip(docs[:n], scores.tolist()))
    return qrel, run


def rescore(run, seed):
    rng = np.random.default_rng(seed)
    return {q: {d: s + float(rng.integers(-2, 3)) / 2 for d, s in docs.items()}
            for q, docs in run.items()}


def flat_scores(run):
    return np.array([s for docs in run.values() for s in docs.values()],
                    dtype=np.float32)


def assert_agrees(got, want, keys):
    assert list(got) == list(want)
    for qid, vals in want.items():
        assert set(got[qid]) == set(keys)
        for k in keys:
            assert got[qid][k] == pytest.approx(vals[k], abs=TOL), (qid, k)


def classes_of(ev, buf):
    classes, _ = ev._class_batches(buf)
    return classes


@contextlib.contextmanager
def profiled(log_dir):
    """A profiler session, so ``obs`` keeps the program's records."""
    obs.clear()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with jax.profiler.trace(str(log_dir), profiler_options=opts):
        yield


@pytest.mark.parametrize("seed", [0, 1])
@pytest.mark.parametrize("measures", [UNBOUNDED, BOUNDED],
                         ids=["unbounded", "bounded"])
@pytest.mark.parametrize("entry", ["evaluate_buffer", "evaluate_buffers",
                                   "evaluate"])
def test_entry_points_agree_with_the_reference(entry, measures, seed):
    qrel, run = ragged_case(seed)
    ev = RelevanceEvaluator(qrel, measures)
    buf = ev.tokenize_run(run)
    classes = classes_of(ev, buf)
    assert len(classes) >= 4
    assert sum(len(c.queries) for c in classes) == len(buf)
    # the top-k kernel takes the wide classes of depth-bounded measures
    assert [c.topk for c in classes] == [
        measures is BOUNDED and c.d_pad > 512 for c in classes]
    assert any(c.topk for c in classes) == (measures is BOUNDED)
    other = rescore(run, seed + 10)
    want = [pure_eval.evaluate(r, qrel, REFERENCE[measures])
            for r in (run, other)]
    if entry == "evaluate_buffer":
        got = [ev.evaluate_buffer(buf),
               ev.evaluate_buffer(buf, scores=flat_scores(other))]
    elif entry == "evaluate_buffers":
        got = ev.evaluate_buffers([buf, buf],
                                  [None, flat_scores(other)])
    else:
        got = [ev.evaluate(run), ev.evaluate(other)]
    for g, w in zip(got, want):
        assert_agrees(g, w, ev.measure_keys)


@pytest.mark.parametrize("measures", [UNBOUNDED, BOUNDED],
                         ids=["unbounded", "bounded"])
@pytest.mark.parametrize("depth", [5, 100, 1000])
def test_uniform_buffer_is_one_class_of_todays_padding(measures, depth):
    rng = np.random.default_rng(depth)
    qrel = {f"q{q}": {f"d{j:04d}": int(rng.integers(0, 3))
                      for j in rng.choice(depth + 40, 30, replace=False)}
            for q in range(13)}
    run = {q: {f"d{j:04d}": float(rng.integers(0, 9))
               for j in rng.choice(depth + 40, depth, replace=False)}
           for q in qrel}
    ev = RelevanceEvaluator(qrel, measures)
    buf = ev.tokenize_run(run)
    (only,) = classes_of(ev, buf)
    rect = ev.batch_from_buffer(buf, topk_layout=only.topk)
    assert (only.q_pad, only.d_pad, only.j_pad) == (
        rect.scores.shape + rect.ideal_rel.shape[1:])
    assert (only.q_pad, only.d_pad) == (bucketing.bucket_queries(13),
                                        bucketing.bucket_docs(depth))
    assert only.topk == (measures is BOUNDED and depth > 512)
    assert only.docs == 13 * depth
    np.testing.assert_array_equal(only.queries, np.arange(13))
    (batch,) = ev._class_batches(buf)[1]
    for field in rect._fields:
        np.testing.assert_array_equal(getattr(batch, field),
                                      getattr(rect, field), err_msg=field)


@pytest.mark.parametrize("measures", [UNBOUNDED, BOUNDED],
                         ids=["unbounded", "bounded"])
def test_the_split_is_built_once_per_buffer(measures, tmp_path):
    qrel, run = ragged_case(2)
    ev = RelevanceEvaluator(qrel, measures)
    buf = ev.tokenize_run(run)
    ev.evaluate_buffer(buf)  # builds the split, outside the session
    held = buf.layout[0]
    with profiled(tmp_path):
        for k in range(3):
            ev.evaluate_buffer(buf, scores=flat_scores(rescore(run, k)))
    assert buf.layout[0] is held
    recs = obs.records()
    names = [r.name for r in recs]
    assert names.count("repro.layout.build") == 0
    assert names.count("repro.layout.hit") == 3  # layout_hit_share 1.0
    classes = held[1].classes
    rows = [r.value for r in recs if r.name == "repro.batch.rows"]
    cells = [r.value for r in recs if r.name == "repro.batch.cells"]
    assert rows == [c.docs for c in classes] * 3
    assert cells == [c.q_pad * c.d_pad for c in classes] * 3
    assert sum(rows) == 3 * sum(len(d) for d in run.values())


def test_output_keys_come_in_the_buffers_query_order():
    qrel, run = ragged_case(3)
    order = list(np.random.default_rng(3).permutation(list(run)))
    run = {q: run[q] for q in order}
    ev = RelevanceEvaluator(qrel, BOUNDED)
    buf = ev.tokenize_run(run)
    assert buf.qids == order
    assert list(ev.evaluate_buffer(buf)) == order
    assert list(ev.evaluate(run)) == order
    a, b = ev.evaluate_buffers([buf, ev.tokenize_run(
        {q: run[q] for q in order[:5]})])
    assert list(a) == order and list(b) == order[:5]


def test_compiled_signatures_stay_in_the_closed_set():
    # a measure tuple no other test uses: fresh jit entries to count
    measures = ("recall_15", "P_200")
    judged, max_len, max_nq = 12, 300, 40
    before = bucketing.compile_count("measure_core")
    batches = 0
    for seed in range(8):
        rng = np.random.default_rng(100 + seed)
        nq = int(rng.integers(5, max_nq + 1))
        lengths = rng.integers(1, max_len + 1, nq)
        qrel = {f"q{q}": {f"d{j:03d}": int(rng.integers(0, 2))
                          for j in range(judged)} for q in range(nq)}
        run = {f"q{q}": {f"d{j:03d}": float(rng.integers(0, 5))
                         for j in range(n)} for q, n in enumerate(lengths)}
        ev = RelevanceEvaluator(qrel, measures)
        buf = ev.tokenize_run(run)
        batches += len(classes_of(ev, buf))
        ev.evaluate_buffer(buf)
    compiled = bucketing.compile_count("measure_core") - before
    bound = (bucketing.max_signatures(max_nq)
             * bucketing.max_signatures(max_len,
                                        minimum=bucketing.MIN_DOC_BUCKET))
    assert 0 < compiled <= bound
    assert compiled < batches  # classes of one padding share one program
