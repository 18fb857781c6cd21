"""pytrec_eval's own entry, ``evaluate(run_dict)``, at a small Robust04-like
shape: 12 topics x 200 documents graded 0-2, about a quarter of the run's
docnos in no qrels and scores on a 0.1 grid, so ties cross judged and
unjudged documents and the exact string pipeline breaks them.

The values equal the plain reference (``baselines/pure_eval``) on every
Robust04 measure key, with and without ``-J``, and the bits of
``evaluate_buffer`` on the same rows.  While the profiler records,
``repro.tokenize`` sits under ``repro.ingest`` with its three counts."""

import contextlib

import jax
import numpy as np
import pytest

from repro import obs
from repro.baselines import pure_eval
from repro.core import RelevanceEvaluator

TOPICS, DEPTH = 12, 200
#: Robust04's measures: 32 keys
MEASURES = ("map", "bpref", "ndcg", "Rprec", "recip_rank", "P", "recall",
            "ndcg_cut")
KEYS = RelevanceEvaluator({"q": {"d": 1}}, MEASURES).measure_keys


def make_case(seed=2004, oov_share=0.25):
    """Qrels and a dict run in Robust04's shape, scaled down.  Of each
    topic's documents about ``oov_share`` are in no topic's qrels, about a
    tenth judged only for another topic, the rest judged for this one."""
    rng = np.random.default_rng(seed)
    qrel, run = {}, {}
    for t in range(TOPICS):
        qid = str(301 + t)
        judged = [f"FT{t:02d}-{j:04d}" for j in range(260)]
        grades = rng.choice(3, len(judged), p=[0.8, 0.14, 0.06])
        qrel[qid] = dict(zip(judged, grades.tolist()))
        n_oov = int(DEPTH * oov_share)
        n_other = DEPTH // 10
        other = [f"FT{(t + 1) % TOPICS:02d}-{j:04d}"
                 for j in rng.choice(260, n_other, replace=False)]
        oov = [f"LA{t:02d}-{j:04d}" for j in range(n_oov)]
        own = rng.choice(judged, DEPTH - n_oov - n_other,
                         replace=False).tolist()
        docs = own + other + oov
        rng.shuffle(docs)
        gain = np.array([qrel[qid].get(d, 0) for d in docs], dtype=float)
        scores = np.round(gain + rng.normal(size=DEPTH), 1)
        run[qid] = dict(zip(docs, scores.tolist()))
    return qrel, run


def judged_only(run, qrel):
    """trec_eval ``-J``: each ranking without its unjudged documents."""
    return {q: {d: s for d, s in docs.items() if d in qrel[q]}
            for q, docs in run.items()}


@pytest.fixture(scope="module")
def case():
    return make_case()


@pytest.fixture(scope="module", params=[False, True], ids=["all", "J"])
def evaluated(request, case):
    qrel, run = case
    ev = RelevanceEvaluator(qrel, MEASURES, judged_docs_only=request.param)
    ref_run = judged_only(run, qrel) if request.param else run
    return ev, ev.evaluate(run), pure_eval.evaluate(ref_run, qrel, MEASURES)


def test_the_case_has_the_promised_shape(case):
    qrel, run = case
    vocab = {d for docs in qrel.values() for d in docs}
    rows = [(q, d, s) for q, docs in run.items() for d, s in docs.items()]
    assert len(run) == TOPICS and len(rows) == TOPICS * DEPTH
    oov = sum(d not in vocab for _, d, _ in rows)
    assert oov / len(rows) == pytest.approx(0.25)
    # ties on the score grid cross judged and unjudged documents
    crossing = 0
    for q, docs in run.items():
        by_score = {}
        for d, s in docs.items():
            by_score.setdefault(s, set()).add(d in qrel[q])
        crossing += sum(len(kinds) == 2 for kinds in by_score.values())
    assert crossing >= TOPICS


@pytest.mark.parametrize("key", KEYS)
def test_values_match_the_plain_reference(evaluated, key):
    _, got, want = evaluated
    assert got.keys() == want.keys()
    for qid in want:
        assert got[qid][key] == pytest.approx(want[qid][key], abs=1e-6), qid


@pytest.mark.parametrize("oov", [True, False], ids=["oov", "in-vocab"])
def test_values_are_the_bits_of_evaluate_buffer(evaluated, case, oov):
    ev, got, _ = evaluated
    qrel, run = case
    if not oov:  # the integer tokenizer, not the string pipeline
        vocab = {d for docs in qrel.values() for d in docs}
        run = {q: {d: s for d, s in docs.items() if d in vocab}
               for q, docs in run.items()}
        got = ev.evaluate(run)
    rows = [(q, d, s) for q, docs in run.items() for d, s in docs.items()]
    qids, docnos, scores = (np.array(c) for c in zip(*rows))
    buf = ev.buffer_from_arrays(qids, docnos, scores.astype(np.float32))
    assert ev.evaluate_buffer(buf) == got


@contextlib.contextmanager
def profiled(log_dir):
    obs.clear()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with jax.profiler.trace(str(log_dir), profiler_options=opts):
        yield


def traced(run, tmp_path, qrel):
    ev = RelevanceEvaluator(qrel, MEASURES)
    ev.evaluate(run)  # compiled outside the session
    with profiled(tmp_path):
        got = ev.evaluate(run)
    assert got == ev.evaluate(run)
    return obs.records()


def test_tokenize_nests_under_ingest(case, tmp_path):
    qrel, run = case
    recs = traced(run, tmp_path, qrel)
    tok = [i for i, r in enumerate(recs) if r.name == "repro.tokenize"]
    assert len(tok) == 1
    ingest = recs[recs[tok[0]].parent]
    assert ingest.name == "repro.ingest"
    assert recs[ingest.parent].name == "repro.evaluate"
    t = recs[tok[0]]
    assert ingest.start_ns <= t.start_ns <= t.end_ns <= ingest.end_ns
    counts = {r.name: r for r in recs if r.name.startswith("repro.tokenize.")}
    assert set(counts) == {"repro.tokenize.rows", "repro.tokenize.oov",
                           "repro.tokenize.strings"}
    assert all(r.parent == tok[0] for r in counts.values())


def test_counts_the_rows_and_the_docnos_missing_from_the_qrels(case,
                                                               tmp_path):
    qrel, run = case
    vocab = {d for docs in qrel.values() for d in docs}
    recs = traced(run, tmp_path, qrel)
    value = {r.name: r.value for r in recs}
    assert value["repro.tokenize.rows"] == TOPICS * DEPTH
    assert value["repro.tokenize.oov"] == sum(
        d not in vocab for docs in run.values() for d in docs)


@pytest.mark.parametrize("missing", [1, 0])
def test_strings_counts_every_row_once_one_docno_is_missing(tmp_path,
                                                            missing):
    qrel, run = make_case(oov_share=0.0)
    q = next(iter(run))
    if missing:
        run[q]["NOT-JUDGED-ANYWHERE"] = 0.3
    recs = traced(run, tmp_path, qrel)
    value = {r.name: r.value for r in recs}
    rows = TOPICS * DEPTH + missing
    assert value["repro.tokenize.rows"] == rows
    assert value["repro.tokenize.oov"] == missing
    assert value["repro.tokenize.strings"] == (rows if missing else 0)


def test_no_record_without_a_profiler_session(case):
    qrel, run = case
    obs.clear()
    RelevanceEvaluator(qrel, MEASURES).evaluate(run)
    assert obs.records() == [] and not obs.recording()
