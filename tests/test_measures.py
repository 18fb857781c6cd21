"""Measure semantics: hand-computed cases + cross-validation vs the
independent pure-Python engine (which mirrors trec_eval's C loop)."""

import math
import random

import numpy as np
import pytest

from repro.baselines import native_ndcg, pure_eval
from repro.core import RelevanceEvaluator, aggregate_results
from repro.kernels import bucketing

MEASURES = ("map", "ndcg", "ndcg_cut", "P", "recall", "recip_rank", "Rprec",
            "bpref", "success", "map_cut", "num_ret", "num_rel",
            "num_rel_ret")


@pytest.fixture
def simple_case():
    qrel = {"q1": {"d1": 1, "d2": 0, "d3": 2, "d4": 1}}
    run = {"q1": {"d1": 1.0, "d2": 0.5, "d3": 2.0}}
    return run, qrel


def test_hand_computed_values(simple_case):
    run, qrel = simple_case
    ev = RelevanceEvaluator(qrel, MEASURES)
    res = ev.evaluate(run)["q1"]
    idcg = 2 + 1 / math.log2(3) + 0.5
    dcg = 2 + 1 / math.log2(3)
    expected = {
        "map": 2 / 3, "P_5": 0.4, "recall_5": 2 / 3, "recip_rank": 1.0,
        "Rprec": 2 / 3, "bpref": 2 / 3, "num_rel_ret": 2.0, "num_ret": 3.0,
        "num_rel": 3.0, "ndcg": dcg / idcg, "ndcg_cut_10": dcg / idcg,
        "success_1": 1.0, "map_cut_5": 2 / 3,
    }
    for k, v in expected.items():
        assert res[k] == pytest.approx(v, abs=1e-5), k


def test_tie_break_larger_docno_wins():
    # equal scores: trec_eval ranks the lexicographically larger docno first
    ev = RelevanceEvaluator({"q": {"dB": 1}}, {"recip_rank"})
    res = ev.evaluate({"q": {"dA": 1.0, "dB": 1.0}})
    assert res["q"]["recip_rank"] == 1.0
    ev2 = RelevanceEvaluator({"q": {"dA": 1}}, {"recip_rank"})
    res2 = ev2.evaluate({"q": {"dA": 1.0, "dB": 1.0}})
    assert res2["q"]["recip_rank"] == 0.5


def test_run_qrel_intersection():
    ev = RelevanceEvaluator({"q1": {"d1": 1}}, {"map"})
    res = ev.evaluate({"q1": {"d1": 1.0}, "q_unjudged": {"d1": 1.0}})
    assert set(res) == {"q1"}
    assert ev.evaluate({}) == {}


def test_no_relevant_docs_query():
    # R=0: trec_eval yields 0 for R-normalized measures (no div-by-zero)
    ev = RelevanceEvaluator({"q": {"d1": 0}}, MEASURES)
    res = ev.evaluate({"q": {"d1": 1.0, "d2": 2.0}})
    assert res["q"]["map"] == 0.0
    assert res["q"]["ndcg"] == 0.0
    assert res["q"]["num_ret"] == 2.0


def test_unjudged_documents_are_nonrelevant():
    ev = RelevanceEvaluator({"q": {"d1": 1}}, {"P", "map"})
    res = ev.evaluate({"q": {"d_unjudged": 5.0, "d1": 1.0}})
    assert res["q"]["P_5"] == pytest.approx(1 / 5)
    assert res["q"]["map"] == pytest.approx(1 / 2)


def test_graded_relevance_levels():
    # relevance_level=2: only rel>=2 counts as relevant for binary measures
    qrel = {"q": {"d1": 1, "d2": 2}}
    run = {"q": {"d1": 2.0, "d2": 1.0}}
    res = RelevanceEvaluator(qrel, {"map"}, relevance_level=2).evaluate(run)
    assert res["q"]["map"] == pytest.approx(1 / 2)


def test_matches_pure_python_engine_randomized():
    random.seed(42)
    for _ in range(8):
        nq = random.randint(1, 6)
        run, qrel = {}, {}
        for qi in range(nq):
            qid = f"q{qi}"
            docs = [f"d{j}" for j in range(random.randint(1, 60))]
            run[qid] = {d: random.choice([0.0, 0.5, 1.0, 2.0,
                                          random.random()]) for d in docs}
            judged = random.sample(docs, k=random.randint(0, len(docs)))
            qrel[qid] = {d: random.randint(0, 3) for d in judged}
            for j in range(random.randint(0, 4)):
                qrel[qid][f"extra{j}"] = random.randint(0, 2)
            if not qrel[qid]:
                qrel[qid]["extra0"] = 1
        ours = RelevanceEvaluator(qrel, MEASURES).evaluate(run)
        ref = pure_eval.evaluate(run, qrel, MEASURES)
        for qid in ref:
            for key, val in ref[qid].items():
                assert ours[qid][key] == pytest.approx(val, abs=2e-4), \
                    (qid, key)


def test_native_ndcg_matches_engines():
    run = {"q": {f"d{i}": float(i % 7) for i in range(30)}}
    qrel = {"q": {f"d{i}": i % 3 for i in range(25)}}
    ref = pure_eval.evaluate(run, qrel, ("ndcg",))["q"]["ndcg"]
    assert native_ndcg.ndcg(run["q"], qrel["q"]) == pytest.approx(ref)


def test_aggregate_results():
    ev = RelevanceEvaluator(
        {"q1": {"d1": 1}, "q2": {"d1": 1}}, {"recip_rank"})
    res = ev.evaluate({"q1": {"d1": 1.0}, "q2": {"d1": 1.0, "d2": 2.0}})
    agg = aggregate_results(res)
    assert agg["recip_rank"] == pytest.approx((1.0 + 0.5) / 2)


def test_supported_measures_property():
    from repro.core import supported_measures

    assert "ndcg" in supported_measures
    assert "map" in supported_measures
    ev = RelevanceEvaluator({"q": {"d": 1}}, supported_measures)
    res = ev.evaluate({"q": {"d": 1.0}})
    assert res["q"]["ndcg"] == 1.0


# -- top-k kernel routing ----------------------------------------------------

# Depth-bounded request (mixed dialects on purpose): max depth 20, so a
# batch padded past max(2*next_pow2(20, 128), 512) = 512 docs routes to
# the top-k kernel instead of the full multi-key sort.
BOUNDED = ("P@5", "P_10", "recall_10", "nDCG@10", "map_cut_10",
           "success_10", "Judged@10", "ERR@20", "num_ret", "num_rel")


def _wide_case(nd=600, nq=3, seed=7):
    rng = random.Random(seed)
    run, qrel = {}, {}
    for qi in range(nq):
        qid = f"q{qi}"
        run[qid] = {f"d{j:04d}": rng.random() for j in range(nd)}
        qrel[qid] = {f"d{j:04d}": rng.randint(0, 2)
                     for j in rng.sample(range(nd), 40)}
    return run, qrel


@pytest.mark.parametrize("judged_only", [False, True])
def test_topk_route_taken_and_bit_identical(monkeypatch, judged_only):
    from repro.core import measures as M

    run, qrel = _wide_case()
    ev = RelevanceEvaluator(qrel, BOUNDED, judged_docs_only=judged_only)
    calls = []
    real = M.compute_measures_topk_packed_jit

    def spy(*args, **kwargs):
        calls.append(1)
        return real(*args, **kwargs)

    monkeypatch.setattr(M, "compute_measures_topk_packed_jit", spy)
    routed = ev.evaluate(run)
    assert calls, "wide depth-bounded batch must take the top-k path"

    ev_full = RelevanceEvaluator(qrel, BOUNDED, judged_docs_only=judged_only)
    monkeypatch.setattr(type(ev_full), "_route_topk",
                        lambda self, buf: False)
    full = ev_full.evaluate(run)
    assert routed.keys() == full.keys()
    for qid in routed:
        assert routed[qid].keys() == full[qid].keys()
        for key in routed[qid]:
            assert routed[qid][key] == full[qid][key], (qid, key)


def test_full_depth_measure_disables_topk_route(monkeypatch):
    from repro.core import measures as M

    run, qrel = _wide_case(nq=1)
    ev = RelevanceEvaluator(qrel, ("map", "P_10"))  # map needs the full sort
    monkeypatch.setattr(
        M, "compute_measures_topk_packed_jit",
        lambda *a, **k: pytest.fail("top-k path taken for full-depth map"))
    ev.evaluate(run)

    # narrow batches stay on the full sort too (top-k gains nothing there)
    ev2 = RelevanceEvaluator({"q": {"d1": 1}}, ("P_10",))
    assert not ev2._route_topk(bucketing.bucket_docs(1))


def test_topk_path_preserves_trec_tie_rule(monkeypatch):
    # equal scores: the tiebreak-column layout makes the kernel's
    # smaller-index-wins rule equal trec_eval's larger-docno-wins rule
    ev = RelevanceEvaluator({"q": {"dB": 1}}, ("P_5", "success_1"))
    monkeypatch.setattr(type(ev), "_route_topk", lambda self, buf: True)
    res = ev.evaluate({"q": {"dA": 1.0, "dB": 1.0}})
    assert res["q"]["success_1"] == 1.0
    assert res["q"]["P_5"] == pytest.approx(1 / 5)
