"""Ragged runs are evaluated in depth classes, not one padded rectangle.

A buffer's queries are grouped by the padding class of their own list
length (``bucketing.bucket_docs``); each class is padded, routed (full sort
or top-k) and measured on its own, and the answers come back in the
buffer's query order.  Every entry point must agree with the plain
reference (``baselines/pure_eval.py``) within 1e-5, a uniform buffer must
stay the one rectangle it always was, and the split must be built once per
buffer and compile a closed set of signatures.  Each class's columns come
back packed in one ``[K, q_pad]`` array, fetched in one copy, with the
bits of the per-key columns.  A buffer's static slabs go to the device
on its first call and stay there; later calls send the scores alone.
"""

import contextlib
import gc
import weakref

import jax
import numpy as np
import pytest

from repro import obs
from repro.baselines import pure_eval
from repro.core import RelevanceEvaluator
from repro.core import measures as M
from repro.core.evaluator import concat_run_buffers
from repro.kernels import bucketing

TOL = 1e-5
#: list lengths every case holds: eight depth classes, 8 through 1024
FIXED_LENGTHS = [1, 3, 8, 9, 16, 17, 40, 100, 200, 300, 513, 600]
UNBOUNDED = ("map", "ndcg", "recip_rank", "bpref", "Rprec")
BOUNDED = ("nDCG@5", "nDCG@10", "P@10")
#: the Robust04 configuration's measures: 32 columns
ROBUST04 = ("map", "bpref", "ndcg", "Rprec", "recip_rank", "P", "recall",
            "ndcg_cut")
REFERENCE = {UNBOUNDED: UNBOUNDED, BOUNDED: ("ndcg_cut", "P"),
             ROBUST04: ROBUST04}
MEASURE_SETS = {"unbounded": UNBOUNDED, "bounded": BOUNDED,
                "robust04": ROBUST04}


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


def uniform_case(seed, nq=13, depth=600):
    """``ragged_case``'s grades and scores with every list ``depth`` long:
    one depth class, on the top-k route for depth-bounded measures."""
    rng = np.random.default_rng(seed)
    qrel, run = {}, {}
    for q in range(nq):
        qid = f"q{q:03d}"
        docs = [f"{qid}-d{j:04d}" for j in rng.permutation(depth + 4)]
        grades = rng.choice(5, depth + 4, p=[0.52, 0.32, 0.13, 0.02, 0.01])
        qrel[qid] = dict(zip(docs, grades.tolist()))
        scores = np.round(2 * (grades[:depth] + rng.normal(size=depth))) / 2
        run[qid] = dict(zip(docs[:depth], scores.tolist()))
    return qrel, run


CASES = {"one-class": uniform_case, "ragged": ragged_case}


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
    return ev._class_batches(buf)[0].classes


@contextlib.contextmanager
def profiled(log_dir):
    """A profiler session, so ``obs`` keeps the program's records."""
    obs.clear()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with jax.profiler.trace(str(log_dir), profiler_options=opts):
        yield


@pytest.mark.parametrize("seed", [0, 1])
@pytest.mark.parametrize("measures", [UNBOUNDED, BOUNDED, ROBUST04],
                         ids=["unbounded", "bounded", "robust04"])
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


def by_class_cores(ev, bufs):
    """Each buffer's results assembled key by key, apart from the
    evaluator's row fetch: the buffers end to end, each class's packed
    core, each key's row put back in query order and split by buffer."""
    both = concat_run_buffers(bufs)
    layout, batches = ev._class_batches(both)
    classes = layout.classes
    cols = {k: np.empty(len(both), np.float32) for k in ev.measure_keys}
    for c, batch in zip(classes, batches):
        got = np.asarray(M.packed_core(c.topk)(
            batch, ev.measures, ev.relevance_level, ev.judged_docs_only))
        for row, (k, col) in zip(got, cols.items()):
            col[c.queries] = row[:len(c.queries)]
    out, lo = [], 0
    for buf in bufs:
        out.append({q: {k: cols[k][i] for k in ev.measure_keys}
                    for i, q in enumerate(buf.qids, lo)})
        lo += len(buf)
    return out


def assert_same_bits(got, want, keys):
    assert list(got) == list(want)
    for qid in want:
        assert list(got[qid]) == list(keys), qid
    bits = lambda res: np.array(  # noqa: E731
        [[res[q][k] for k in keys] for q in want], np.float32).view(np.int32)
    np.testing.assert_array_equal(bits(got), bits(want))


@pytest.mark.parametrize("shape", CASES)
@pytest.mark.parametrize("measures", MEASURE_SETS)
@pytest.mark.parametrize("entry", ["evaluate_buffer", "evaluate_buffers",
                                   "evaluate"])
def test_packed_columns_are_the_dict_cores_bits(entry, measures, shape):
    qrel, run = CASES[shape](4)
    ev = RelevanceEvaluator(qrel, MEASURE_SETS[measures])
    buf = ev.tokenize_run(run)
    classes = classes_of(ev, buf)
    assert (len(classes) == 1) == (shape == "one-class")
    assert any(c.topk for c in classes) == (measures == "bounded")
    other = rescore(run, 5)
    other_buf = ev.tokenize_run(other)
    want = [by_class_cores(ev, [b])[0] for b in (buf, other_buf)]
    if entry == "evaluate_buffer":
        got = [ev.evaluate_buffer(buf),
               ev.evaluate_buffer(buf, scores=flat_scores(other))]
    elif entry == "evaluate_buffers":
        # an empty group between the two keeps its place and gets nothing
        got = ev.evaluate_buffers([buf, ev.tokenize_run({}), buf],
                                  [None, None, flat_scores(other)])
        assert got.pop(1) == {}
        # the buffers share one padded axis, so they share its programs
        want = by_class_cores(ev, [buf, other_buf])
    else:
        got = [ev.evaluate(run), ev.evaluate(other)]
    for g, w in zip(got, want):
        assert_same_bits(g, w, ev.measure_keys)


@pytest.mark.parametrize("route,measures", [
    ("full-sort", ROBUST04), ("top-k", BOUNDED), ("full-sort", ())],
    ids=["full-sort", "top-k", "no-measures"])
def test_packed_cores_stack_the_dict_columns_in_key_order(route, measures):
    qrel, run = uniform_case(6)
    ev = RelevanceEvaluator(qrel, measures)
    buf = ev.tokenize_run(run)
    (only,) = classes_of(ev, buf)
    assert only.topk == (route == "top-k")
    batch = ev.batch_from_buffer(buf, topk_layout=only.topk)
    dict_fn, packed_core = {
        "full-sort": (M.compute_measures, M.compute_measures_packed_jit),
        "top-k": (M.compute_measures_topk,
                  M.compute_measures_topk_packed_jit)}[route]
    # the dict of columns in a program of its own (run op by op, XLA would
    # fuse differently and move some values by an ulp or two)
    dict_core = jax.jit(dict_fn, static_argnums=(1, 2, 3))
    args = (batch, ev.measures, ev.relevance_level, ev.judged_docs_only)
    columns, packed = dict_core(*args), np.asarray(packed_core(*args))
    assert packed.shape == (len(ev.measure_keys), only.q_pad)
    assert packed.dtype == np.float32
    want = np.array([np.asarray(columns[k]) for k in ev.measure_keys],
                    np.float32).reshape(packed.shape)
    np.testing.assert_array_equal(packed.view(np.int32), want.view(np.int32))
    if not measures:
        assert ev.evaluate(run) == {q: {} for q in run}


@pytest.mark.parametrize("shape", CASES)
@pytest.mark.parametrize("entry", ["evaluate_buffer", "evaluate_buffers",
                                   "evaluate"])
def test_one_fetch_copy_per_class_per_call(entry, shape, tmp_path):
    qrel, run = CASES[shape](7)
    ev = RelevanceEvaluator(qrel, BOUNDED)
    buf = ev.tokenize_run(run)
    classes = classes_of(ev, buf)
    call = {"evaluate_buffer": lambda: ev.evaluate_buffer(buf),
            "evaluate_buffers": lambda: ev.evaluate_buffers(
                [buf, ev.tokenize_run({})]),
            "evaluate": lambda: ev.evaluate(run)}[entry]
    call()  # compiles outside the session
    with profiled(tmp_path):
        for _ in range(3):
            call()
    recs = obs.records()
    copies = [r for r in recs if r.name == "repro.fetch.copy"]
    assert [r.value for r in copies] == [
        4 * len(ev.measure_keys) * c.q_pad for c in classes] * 3
    assert {recs[r.parent].name for r in copies} == {"repro.fetch"}
    assert [r.name for r in recs].count("repro.evaluate") == 3


def transfer_records(recs):
    """Per ``repro.transfer`` span: its static marks and the bytes sent."""
    out = []
    for i, r in enumerate(recs):
        if r.name == "repro.transfer":
            kids = [k for k in recs if k.parent == i]
            out.append(([k.name for k in kids
                         if k.name.startswith("repro.transfer.static_")],
                        [k.value for k in kids
                         if k.name == "repro.transfer.bytes"]))
    return out


def counted_puts(monkeypatch):
    """Every ``jax.device_put`` from here on: the leaves each one sent."""
    puts, put = [], jax.device_put

    def counting(x, *a, **k):
        puts.append(jax.tree.leaves(x))
        return put(x, *a, **k)

    monkeypatch.setattr(jax, "device_put", counting)
    return puts


def leaf_bytes(batch):
    return sum(a.nbytes for a in batch)


@pytest.mark.parametrize("measures", [UNBOUNDED, BOUNDED],
                         ids=["unbounded", "bounded"])
@pytest.mark.parametrize("shape", CASES)
def test_static_slabs_go_to_the_device_once_per_buffer(shape, measures,
                                                       tmp_path,
                                                       monkeypatch):
    qrel, run = CASES[shape](8)
    ev = RelevanceEvaluator(qrel, measures)
    ev.evaluate_buffer(ev.tokenize_run(run))  # compiles outside the session
    buf = ev.tokenize_run(run)
    other = rescore(run, 9)
    puts = counted_puts(monkeypatch)
    with profiled(tmp_path):
        got = [ev.evaluate_buffer(buf),
               ev.evaluate_buffer(buf, scores=flat_scores(other))]
    layout = buf.layout[0][1]
    classes = layout.classes
    assert (len(classes) == 1) == (shape == "one-class")
    assert any(c.topk for c in classes) == (measures is BOUNDED)
    host = layout.batches(flat_scores(other))
    scores_bytes = sum(4 * c.q_pad * c.d_pad for c in classes)
    # the first call sends every leaf, the second the scores slabs alone
    assert transfer_records(obs.records()) == [
        (["repro.transfer.static_upload"] * len(classes),
         [sum(leaf_bytes(b) for b in host)]),
        (["repro.transfer.static_hit"] * len(classes), [scores_bytes])]
    assert [len(p) for p in puts] == [9 * len(classes), len(classes)]
    assert [p.shape for p in puts[1]] == [(c.q_pad, c.d_pad)
                                          for c in classes]
    # the device copy is the host's static slabs, kept for the default
    # placement and sent no more
    (static,) = layout.device.values()
    assert list(layout.device) == [None]
    for dev, want in zip(static, layout.static):
        assert dev.scores is None
        for field in M.EvalBatch._fields[1:]:
            np.testing.assert_array_equal(np.asarray(getattr(dev, field)),
                                          getattr(want, field),
                                          err_msg=field)
    # the same bits as buffers seen for the first time
    for g, r in zip(got, (run, other)):
        assert_same_bits(g, ev.evaluate_buffer(ev.tokenize_run(r)),
                         ev.measure_keys)


def test_a_new_layout_drops_the_old_device_copy(tmp_path):
    qrel, run = ragged_case(10)
    ev = RelevanceEvaluator(qrel, BOUNDED)
    buf = ev.tokenize_run(run)
    ev.evaluate_buffer(buf)
    old = buf.layout[0][1]
    gone = [weakref.ref(a) for b in old.device[None] for a in b[1:]]
    # a one-rectangle batch is another layout: it replaces the classes'
    ev.batch_from_buffer(buf)
    assert buf.layout[0][1] is not old and not buf.layout[0][1].device
    del old
    gc.collect()
    assert all(r() is None for r in gone)
    with profiled(tmp_path):
        got = ev.evaluate_buffer(buf)
    classes = buf.layout[0][1].classes
    assert [m for m, _ in transfer_records(obs.records())] == [
        ["repro.transfer.static_upload"] * len(classes)]
    assert list(buf.layout[0][1].device) == [None]
    assert_same_bits(got, ev.evaluate_buffer(ev.tokenize_run(run)),
                     ev.measure_keys)


@pytest.mark.parametrize("entry", ["evaluate", "evaluate_buffers"])
def test_layouts_of_one_call_send_every_leaf_in_one_put(entry, tmp_path,
                                                        monkeypatch):
    qrel, run = ragged_case(11)
    ev = RelevanceEvaluator(qrel, BOUNDED)
    bufs = [ev.tokenize_run(run), ev.tokenize_run(rescore(run, 12))]
    if entry == "evaluate":
        call, stacked = (lambda: [ev.evaluate(run)]), bufs[0]
    else:  # a coalesced flush stacks its buffers into a new one each call
        call = lambda: ev.evaluate_buffers(bufs)  # noqa: E731
        stacked = concat_run_buffers(bufs)
    want = call()  # compiles outside the session
    layout, host = ev._class_batches(stacked)
    puts = counted_puts(monkeypatch)
    with profiled(tmp_path):
        got = [call(), call()]
    assert transfer_records(obs.records()) == [
        (["repro.transfer.static_upload"] * len(layout.classes),
         [sum(leaf_bytes(b) for b in host)])] * 2
    assert [len(p) for p in puts] == [9 * len(layout.classes)] * 2
    for g in got:
        for a, b in zip(g, want):
            assert_same_bits(a, b, ev.measure_keys)
