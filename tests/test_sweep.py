"""``evaluate_sweep`` must be bit-identical to K independent evaluations.

The sweep's whole value proposition is "same numbers, one dispatch", so
these tests assert EXACT float equality between the ``[K, Q, M]`` sweep
table and per-run :meth:`RelevanceEvaluator.evaluate_buffer` calls —
including ragged per-query document counts, chunked dispatch groups, and
randomized shapes (hypothesis when installed, a seeded sweep otherwise).
The sharded backend is held to the same standard against its own
``evaluate_buffer`` and against the single-device sweep.
"""

import numpy as np
import pytest

from repro.core import RelevanceEvaluator, evaluate_sweep, trec
from repro.core.evaluator import RunBuffer
from repro.core.sweep import common_qids

MEASURES = ("map", "ndcg", "P_5", "recip_rank", "gm_map")


def _make_runs(k, n_queries, n_docs, seed=0, ragged=False):
    """K random runs + a qrel over the same corpus; ragged varies depth."""
    rng = np.random.default_rng(seed)
    qrel = {}
    base_docs = {}
    for qi in range(n_queries):
        qid = f"q{qi}"
        nd = int(rng.integers(1, n_docs + 1)) if ragged else n_docs
        docs = [f"d{j}" for j in range(nd)]
        base_docs[qid] = docs
        qrel[qid] = {d: int(rng.integers(0, 3)) for d in docs}
        if not any(qrel[qid].values()):
            qrel[qid][docs[0]] = 1  # every query judges something relevant
    runs = []
    for _ in range(k):
        runs.append({qid: {d: float(s) for d, s in
                           zip(docs, rng.random(len(docs)))}
                     for qid, docs in base_docs.items()})
    return qrel, runs


def _assert_table_matches_per_run(result, ev, runs):
    for ki, run in enumerate(runs):
        want = ev.evaluate_buffer(
            run if isinstance(run, RunBuffer)
            else ev.tokenize_run({q: run[q] for q in result.qids}))
        for qi, qid in enumerate(result.qids):
            for mi, key in enumerate(result.measure_keys):
                assert result.table[ki, qi, mi] == \
                    want[qid][key], (ki, qid, key)


def test_k8_bit_identical_to_independent_evaluations():
    qrel, runs = _make_runs(8, 12, 9, seed=1)
    ev = RelevanceEvaluator(qrel, MEASURES)
    result = evaluate_sweep(ev, runs)
    assert result.table.shape == (8, 12, len(ev.measure_keys))
    assert result.run_names == tuple(f"run_{i}" for i in range(8))
    _assert_table_matches_per_run(result, ev, runs)


def test_ragged_document_counts_stay_bit_identical():
    qrel, runs = _make_runs(5, 10, 17, seed=2, ragged=True)
    ev = RelevanceEvaluator(qrel, MEASURES)
    result = evaluate_sweep(ev, runs)
    _assert_table_matches_per_run(result, ev, runs)


def test_chunked_dispatch_is_identical_to_one_shot():
    qrel, runs = _make_runs(7, 6, 5, seed=3)
    one = evaluate_sweep(RelevanceEvaluator(qrel, MEASURES), runs)
    ev = RelevanceEvaluator(qrel, MEASURES)
    ev.chunk_queries = 13  # groups of 2 runs (13 // 6), then a remainder
    chunked = evaluate_sweep(ev, runs)
    assert np.array_equal(one.table, chunked.table)


def test_buffer_input_path_identical_to_dict_path():
    qrel, runs = _make_runs(4, 8, 6, seed=4)
    ev = RelevanceEvaluator(qrel, MEASURES)
    via_dicts = evaluate_sweep(ev, runs)
    bufs = [ev.tokenize_run({q: r[q] for q in via_dicts.qids}) for r in runs]
    via_bufs = evaluate_sweep(ev, bufs)
    assert via_dicts.qids == via_bufs.qids
    assert np.array_equal(via_dicts.table, via_bufs.table)
    _assert_table_matches_per_run(via_bufs, ev, bufs)


def test_sharded_backend_matches_sharded_evaluate_buffer():
    from repro.distributed.sharded_evaluator import ShardedEvaluator

    qrel, runs = _make_runs(4, 9, 7, seed=5)
    ev = RelevanceEvaluator(qrel, MEASURES)
    result = evaluate_sweep(ev, runs, backend="sharded")
    sev = ShardedEvaluator(ev)
    # exact vs the SAME backend's per-run path...
    for ki, run in enumerate(runs):
        res = sev.evaluate(
            {q: run[q] for q in result.qids})
        for qi, qid in enumerate(result.qids):
            for mi, key in enumerate(result.measure_keys):
                assert result.table[ki, qi, mi] == \
                    res.per_query[qid][key], (ki, qid, key)
    # ...and the single-device sweep's bits: the same classes and cores
    single = evaluate_sweep(ev, runs)
    assert np.array_equal(result.table, single.table)


@pytest.mark.parametrize("backend,measures", [
    ("single", ("nDCG@7", "P@9")), ("sharded", ("nDCG@9", "P@7"))],
    ids=["single", "sharded"])
def test_ragged_bounded_sweep_is_evaluate_buffers_bits(backend, measures):
    """Lists 1-1,100 deep and depth-bounded measures: the sweep measures
    its runs end to end in depth classes, the wide ones on the top-k
    kernel, exactly as ``evaluate_buffers`` does over the same runs.  A
    measure pair no other test uses keys fresh compiles to count."""
    from repro.kernels import bucketing

    rng = np.random.default_rng(11)
    lengths = [1, 5, 60, 300, 700, 1100]
    qrel = {f"q{q}": {f"d{j}": int(g) for j, g in enumerate(
        rng.choice(3, n, p=(0.6, 0.3, 0.1)))} for q, n in enumerate(lengths)}
    runs = [{q: {d: float(s) for d, s in zip(docs, np.round(
        2 * rng.normal(size=len(docs))) / 2)} for q, docs in qrel.items()}
        for _ in range(3)]
    ev = RelevanceEvaluator(qrel, measures)
    before = bucketing.compile_count("measure_core_topk")
    result = evaluate_sweep(ev, runs, backend=backend)
    assert bucketing.compile_count("measure_core_topk") > before
    want = ev.evaluate_buffers([ev.tokenize_run(r) for r in runs])
    for ki, per_query in enumerate(want):
        for qi, qid in enumerate(result.qids):
            for mi, key in enumerate(result.measure_keys):
                assert result.table[ki, qi, mi] == per_query[qid][key], \
                    (ki, qid, key)


# -- randomized shapes: hypothesis when available, seeded sweep always -------


def _roundtrip(k, n_queries, n_docs, seed, ragged):
    qrel, runs = _make_runs(k, n_queries, n_docs, seed=seed, ragged=ragged)
    ev = RelevanceEvaluator(qrel, ("map", "ndcg", "P_5"))
    _assert_table_matches_per_run(evaluate_sweep(ev, runs), ev, runs)


def test_random_shapes_bit_identical_seeded():
    rng = np.random.default_rng(123)
    for trial in range(6):
        _roundtrip(int(rng.integers(1, 7)), int(rng.integers(1, 11)),
                   int(rng.integers(1, 14)), seed=100 + trial,
                   ragged=bool(trial % 2))


def test_random_shapes_bit_identical_hypothesis():
    hyp = pytest.importorskip("hypothesis")
    st = pytest.importorskip("hypothesis.strategies")

    @hyp.settings(max_examples=15, deadline=None)
    @hyp.given(k=st.integers(1, 6), n_queries=st.integers(1, 10),
               n_docs=st.integers(1, 12), seed=st.integers(0, 2**16),
               ragged=st.booleans())
    def inner(k, n_queries, n_docs, seed, ragged):
        _roundtrip(k, n_queries, n_docs, seed, ragged)

    inner()


# -- SweepResult helpers ------------------------------------------------------


def test_sweep_result_views_agree_with_table():
    qrel, runs = _make_runs(3, 5, 4, seed=6)
    result = evaluate_sweep(qrel, dict(zip("abc", runs)),
                            measures=("map", "ndcg"))
    assert result.run_names == ("a", "b", "c")
    pq = result.per_query()
    for ki, name in enumerate(result.run_names):
        for qi, qid in enumerate(result.qids):
            assert pq[name][qid]["map"] == result.table[ki, qi, 0]
    sl = result.measure("ndcg")
    assert sl.shape == (3, 5)
    assert np.array_equal(sl, result.table[:, :, 1])
    with pytest.raises(KeyError):
        result.measure("P_5")
    aggs = result.aggregates()
    assert aggs["a"]["map"] == pytest.approx(
        float(result.table[0, :, 0].mean(dtype=np.float64)))


def test_gm_map_aggregate_is_geometric():
    qrel, runs = _make_runs(2, 4, 5, seed=7)
    result = evaluate_sweep(qrel, runs, measures=("map", "gm_map"))
    want = RelevanceEvaluator(qrel, ("map", "gm_map")).evaluate(
        {q: runs[0][q] for q in result.qids})
    got = result.aggregates()["run_0"]["gm_map"]
    ref = np.exp(np.mean([want[q]["gm_map"] for q in result.qids]))
    assert got == pytest.approx(float(ref), rel=1e-6)


def test_compare_returns_significance_bundle():
    qrel, runs = _make_runs(3, 8, 6, seed=8)
    result = evaluate_sweep(qrel, runs, measures=("map",))
    rep = result.compare("map")
    assert rep["run_names"] == result.run_names
    assert rep["measure"] == "map"
    assert rep["t"].shape == (3, 3)
    assert np.array_equal(rep["p"], rep["p"].T)
    # an identical pair of runs must come out utterly non-significant
    twin = evaluate_sweep(qrel, [runs[0], dict(runs[0])], measures=("map",))
    rep2 = twin.compare("map")
    assert float(rep2["t"][0, 1]) == 0.0 and float(rep2["p"][0, 1]) == 1.0


# -- alignment and error paths -----------------------------------------------


def test_common_qids_intersection_in_first_run_order():
    qrel_qids = {"q1": 0, "q2": 1, "q3": 2}
    runs = [{"q3": {}, "q1": {}, "q2": {}, "qX": {}},
            {"q1": {}, "q3": {}}]
    assert common_qids(qrel_qids, runs) == ["q3", "q1"]


def test_dict_runs_align_on_common_judged_queries():
    qrel = {"q1": {"d1": 1}, "q2": {"d1": 1}, "q3": {"d1": 1}}
    runs = [{"q1": {"d1": 1.0}, "q2": {"d1": 1.0}, "q3": {"d1": 1.0}},
            {"q2": {"d1": 2.0}, "q3": {"d1": 2.0}}]
    result = evaluate_sweep(qrel, runs, measures=("map",))
    assert result.qids == ("q2", "q3")


def test_error_paths():
    qrel, runs = _make_runs(2, 3, 3, seed=9)
    ev = RelevanceEvaluator(qrel, ("map",))
    with pytest.raises(ValueError, match="evaluator already owns"):
        evaluate_sweep(ev, runs, measures=("map",))
    with pytest.raises(ValueError, match="no runs"):
        evaluate_sweep(ev, [])
    with pytest.raises(ValueError, match="names for"):
        evaluate_sweep(ev, runs, run_names=["only_one"])
    with pytest.raises(ValueError, match="run_names conflicts"):
        evaluate_sweep(ev, {"a": runs[0], "b": runs[1]},
                       run_names=["a", "b"])
    with pytest.raises(TypeError, match="mix"):
        evaluate_sweep(ev, [runs[0], ev.tokenize_run(runs[1])])
    with pytest.raises(TypeError, match="mix"):
        evaluate_sweep(ev, [ev.tokenize_run(runs[0]), runs[1]])
    with pytest.raises(ValueError, match="no common judged"):
        evaluate_sweep(ev, [runs[0], {"zzz": {"d1": 1.0}}])
    b0 = ev.tokenize_run(runs[0])
    b1 = ev.tokenize_run({"q0": runs[1]["q0"]})
    with pytest.raises(ValueError, match="different queries"):
        evaluate_sweep(ev, [b0, b1])
    scoreless = RunBuffer(b0.qids, b0.gidx, b0.qidx, b0.col, b0.counts,
                          b0.rel, b0.judged, b0.tiebreak, None)
    with pytest.raises(ValueError, match="no scores"):
        evaluate_sweep(ev, [scoreless, b0])


def test_conformance_fixture_sweep_matches_single_run_cli_values():
    """The golden fixtures run through the sweep give the known map values."""
    qrel = trec.load_qrel("tests/fixtures/conformance.qrel")
    runs = {name: trec.load_run(f"tests/fixtures/{name}.run")
            for name in ("conformance", "sweep_b", "sweep_c")}
    result = evaluate_sweep(qrel, runs, measures=("map",))
    aggs = result.aggregates()
    assert aggs["conformance"]["map"] == pytest.approx(0.5, abs=1e-6)
    assert aggs["sweep_c"]["map"] == pytest.approx(1.0, abs=1e-6)
    rep = result.compare("map")
    # sweep_c beats conformance on every query -> constant-sign diff,
    # infinite t, p = 0 (the CLI golden renders this pair with a '*')
    i, j = 0, 2
    assert float(rep["t"][i, j]) == -np.inf
    assert float(rep["p"][i, j]) == 0.0
