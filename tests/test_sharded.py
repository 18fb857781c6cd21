"""Sharded evaluation pipeline: the single-device evaluator's values.

``ShardedEvaluator`` runs the evaluator's own depth classes and packed
measure cores, each class's query axis split over the mesh.  Acceptance:
per-query results **bit-identical** to ``RelevanceEvaluator.evaluate`` on
the conformance fixtures for mesh sizes 1, 2, and 4, and equal to
``evaluate_buffer`` on the full-sort, top-k and mixed routes.  Mesh size 1
runs in-process; 2 and 4 need ``--xla_force_host_platform_device_count``
set before jax initializes, hence subprocesses (one per mesh size for all
the route cases).  These are tier-1 tests (not marked slow): they guard the
acceptance criterion of the sharded pipeline.
"""

import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

FIXTURES = os.path.join(os.path.dirname(__file__), "fixtures")
SRC = os.path.join(os.path.dirname(__file__), "..", "src")
TESTS = os.path.dirname(os.path.abspath(__file__))

_BIT_IDENTITY_CODE = """
    import numpy as np
    from repro.core import RelevanceEvaluator, aggregate_results, \\
        supported_measures, trec
    from repro.distributed import ShardedEvaluator

    qrel = trec.load_qrel({qrel!r})
    run = trec.load_run({run!r})
    ev = RelevanceEvaluator(qrel, supported_measures)
    want = ev.evaluate(run)
    sev = ShardedEvaluator(ev)
    assert sev.n_shards == {devices}, sev.n_shards
    res = sev.evaluate(run)
    assert set(res.per_query) == set(want)
    for qid in want:
        for key, val in want[qid].items():
            got = res.per_query[qid][key]
            assert got == val, (qid, key, got, val)  # bit-identical
    agg = aggregate_results(want)
    for key, val in agg.items():
        np.testing.assert_allclose(res.aggregates[key], val, atol=1e-6,
                                   err_msg=key)
    print("BIT_IDENTICAL")
"""


def _fixture_paths():
    return (os.path.join(FIXTURES, "conformance.qrel"),
            os.path.join(FIXTURES, "conformance.run"))


def _device_env(devices: int) -> dict:
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    return env


def _run_subprocess(code: str, devices: int) -> str:
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True,
                         env=_device_env(devices))
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


def test_sharded_bit_identical_mesh1():
    qrel_path, run_path = _fixture_paths()
    code = _BIT_IDENTITY_CODE.format(qrel=qrel_path, run=run_path, devices=1)
    env_devices = 1
    # in-process: the tier-1 session runs on exactly one device (conftest)
    ns = {}
    exec(textwrap.dedent(code), ns)  # raises on mismatch


@pytest.mark.parametrize("devices", [2, 4])
def test_sharded_bit_identical_multi_device(devices):
    qrel_path, run_path = _fixture_paths()
    out = _run_subprocess(
        _BIT_IDENTITY_CODE.format(qrel=qrel_path, run=run_path,
                                  devices=devices), devices)
    assert "BIT_IDENTICAL" in out


_MESH_INVARIANCE_CODE = """
    import json
    from repro.core import RelevanceEvaluator, supported_measures
    from repro.data.synthetic_ir import synthesize_run
    from repro.distributed import ShardedEvaluator

    run, qrel = synthesize_run(12, 30)
    ev = RelevanceEvaluator(qrel, supported_measures)
    res = ShardedEvaluator(ev).evaluate(run)
    print(json.dumps(res.per_query, sort_keys=True))
"""


def test_sharded_results_invariant_across_mesh_sizes():
    """Measures are row-independent: sharding must not change ANY bit, on
    synthetic float data too."""
    out2 = _run_subprocess(_MESH_INVARIANCE_CODE, devices=2)
    out4 = _run_subprocess(_MESH_INVARIANCE_CODE, devices=4)
    assert out2 == out4
    import json

    per_query = json.loads(out2)
    assert len(per_query) == 12

    # and vs the single-device evaluator: the same per-row program
    from repro.core import RelevanceEvaluator, supported_measures
    from repro.data.synthetic_ir import synthesize_run

    run, qrel = synthesize_run(12, 30)
    want = RelevanceEvaluator(qrel, supported_measures).evaluate(run)
    for qid in want:
        for key, val in want[qid].items():
            assert per_query[qid][key] == val, (qid, key)


def test_sharded_buffer_rescore_matches_evaluate_buffer():
    """Session fast path under sharding: fresh scores, zero string work."""
    from repro.core import RelevanceEvaluator, supported_measures, trec
    from repro.distributed import ShardedEvaluator

    qrel_path, run_path = _fixture_paths()
    ev = RelevanceEvaluator(trec.load_qrel(qrel_path), supported_measures)
    buf = ev.buffer_from_arrays(*trec.load_run_arrays(run_path))
    sev = ShardedEvaluator(ev)
    fresh = np.linspace(1.0, 0.1, buf.qidx.shape[0]).astype(np.float32)
    want = ev.evaluate_buffer(buf, scores=fresh)
    got = sev.evaluate_buffer(buf, scores=fresh).per_query
    for qid in want:
        for key, val in want[qid].items():
            assert got[qid][key] == val, (qid, key)


def test_sharded_from_files_and_uneven_padding():
    """from_files ingest + a query count that does not divide the mesh."""
    from repro.distributed import ShardedEvaluator

    qrel_path, run_path = _fixture_paths()
    sev, buf = ShardedEvaluator.from_files(qrel_path, run_path,
                                           measures=("map", "ndcg"))
    res = sev.evaluate_buffer(buf)
    want = sev.evaluator.evaluate_buffer(buf)
    assert set(res.per_query) == set(want)
    for qid in want:
        for key, val in want[qid].items():
            assert res.per_query[qid][key] == val
    # aggregates equal the mean over real queries only (padding masked out)
    for key in ("map", "ndcg"):
        vals = [want[q][key] for q in want]
        np.testing.assert_allclose(res.aggregates[key], np.mean(vals),
                                   atol=1e-6)


def test_sharded_empty_run():
    from repro.core import RelevanceEvaluator
    from repro.distributed import ShardedEvaluator

    ev = RelevanceEvaluator({"q1": {"d1": 1}}, ("map",))
    res = ShardedEvaluator(ev).evaluate({})
    assert res.per_query == {} and res.aggregates == {}


def test_evaluator_convenience_method():
    from repro.core import RelevanceEvaluator

    ev = RelevanceEvaluator({"q1": {"d1": 1, "d2": 0}}, ("map",))
    res = ev.evaluate_sharded({"q1": {"d1": 0.2, "d2": 0.9}})
    assert res.per_query["q1"]["map"] == 0.5


# -- sharded rows vs evaluate_buffer, per route and mesh size -----------------

ROBUST04 = ("map", "bpref", "ndcg", "Rprec", "recip_rank", "P", "recall",
            "ndcg_cut")
#: route -> (measures, list lengths of its collection)
ROUTES = {
    "full-sort": (ROBUST04, [1000] * 12),
    "top-k": (("nDCG@10", "P@10"), [1000] * 12),
    "mixed": (("nDCG@5", "nDCG@10"),
              [1, 3, 9, 300, 513, 700, 1000, 1100, 1251]),
}
def route_collection(lengths, seed=0):
    """Graded 0-2 judgments of a pool twice each list's length, about half
    of each list judged, scores on a 0.5 grid (ties)."""
    rng = np.random.default_rng(seed)
    qrel, run = {}, {}
    for q, n in enumerate(lengths):
        qid = f"q{q:02d}"
        docs = [f"{qid}-d{j:04d}" for j in rng.permutation(2 * n + 2)]
        judged = docs[n // 2:n // 2 + n + 2]
        grades = rng.choice(3, len(judged), p=(0.7, 0.2, 0.1))
        qrel[qid] = dict(zip(judged, grades.tolist()))
        run[qid] = dict(zip(docs[:n], (np.round(
            2 * rng.normal(size=n)) / 2).tolist()))
    return qrel, run


def mesh_results(devices):
    """Every route case and the coalesced flush on this process's mesh:
    ``ShardedEvaluator``'s per-query values beside the evaluator's own."""
    from repro.core import RelevanceEvaluator
    from repro.distributed import ShardedEvaluator

    out = {}
    for route, (measures, lengths) in ROUTES.items():
        qrel, run = route_collection(lengths)
        for judged_only in (False, True):
            ev = RelevanceEvaluator(qrel, measures,
                                    judged_docs_only=judged_only)
            buf = ev.tokenize_run(run)
            sev = ShardedEvaluator(ev)
            classes = ev._class_batches(
                buf, q_multiple=sev.n_shards)[0].classes
            out[f"{route}/{judged_only}"] = {
                "n_shards": sev.n_shards,
                "topk": [c.topk for c in classes],
                "got": sev.evaluate_buffer(buf).per_query,
                "want": ev.evaluate_buffer(buf)}
    measures, lengths = ROUTES["mixed"]
    qrel, run = route_collection(lengths, seed=1)
    ev = RelevanceEvaluator(qrel, measures)
    bufs = [ev.tokenize_run(run), ev.tokenize_run({}),
            ev.tokenize_run({q: run[q] for q in list(run)[3:]})]
    fresh = [None, None, np.linspace(1.0, 0.0, bufs[2].qidx.shape[0])]
    got = ShardedEvaluator(ev).evaluate_buffers(
        [b if s is None else b.with_scores(s) for b, s in zip(bufs, fresh)])
    out["flush"] = {"got": [r.per_query for r in got],
                    "want": ev.evaluate_buffers(bufs, fresh)}
    return out


@pytest.fixture(scope="module")
def by_mesh():
    """``mesh_results`` per mesh size, each computed once: in-process for
    one device, and in one subprocess for each larger mesh, both started
    at once so that they compile side by side."""
    procs = {devices: subprocess.Popen(
        [sys.executable, "-c", textwrap.dedent(f"""
            import json, sys
            sys.path.insert(0, {TESTS!r})
            from test_sharded import mesh_results
            print(json.dumps(mesh_results({devices})))
        """)], stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        env=_device_env(devices)) for devices in (2, 4)}
    cache = {}

    def get(devices):
        if devices not in cache:
            if devices == 1:
                got = mesh_results(1)
            else:
                out, err = procs[devices].communicate()
                assert procs[devices].returncode == 0, err[-3000:]
                got = json.loads(out.splitlines()[-1])
            cache[devices] = json.loads(json.dumps(got))  # one key type
        return cache[devices]

    yield get
    for proc in procs.values():
        proc.kill()
        proc.communicate()


def assert_same_values(got, want):
    assert list(got) == list(want)
    for qid, vals in want.items():
        assert list(got[qid]) == list(vals), qid
        for key, val in vals.items():
            assert got[qid][key] == val, (qid, key, got[qid][key], val)


@pytest.mark.parametrize("judged_only", [False, True],
                         ids=["all-docs", "judged-only"])
@pytest.mark.parametrize("route", list(ROUTES))
@pytest.mark.parametrize("devices", [1, 2, 4])
def test_sharded_rows_equal_evaluate_buffer(by_mesh, devices, route,
                                            judged_only):
    case = by_mesh(devices)[f"{route}/{judged_only}"]
    assert case["n_shards"] == devices
    # the routing is the evaluator's: one class per padded list length,
    # top-k for the wide classes of depth-bounded measures
    assert case["topk"] == {"full-sort": [False], "top-k": [True],
                            "mixed": [False] * 3 + [True] * 2}[route]
    assert_same_values(case["got"], case["want"])


@pytest.mark.parametrize("devices", [1, 2, 4])
def test_sharded_flush_equals_evaluate_buffers(by_mesh, devices):
    """The coalesced flush: one sharded call over three buffers (one
    empty), split back per buffer, as the evaluator's own flush."""
    flush = by_mesh(devices)["flush"]
    assert flush["got"][1] == flush["want"][1] == {}
    for got, want in zip(flush["got"], flush["want"]):
        assert_same_values(got, want)


def host_aggregates(per_query):
    """The float32 mean of each measure over the queries, geometric where
    the measure is."""
    from repro.core import measures as M

    keys = list(next(iter(per_query.values())))
    rows = np.array([[v[k] for v in per_query.values()] for k in keys],
                    np.float32)
    n = np.float32(rows.shape[1])
    return M.finalize_aggregates(
        {k: float(r.sum(dtype=np.float32) / n) for k, r in zip(keys, rows)})


@pytest.mark.parametrize("entry", ["evaluate_buffer", "evaluate_buffers"])
def test_sharded_aggregates_are_the_host_mean_of_the_rows(entry):
    from repro.core import RelevanceEvaluator, supported_measures, trec
    from repro.distributed import ShardedEvaluator

    qrel_path, run_path = _fixture_paths()
    qrel, run = trec.load_qrel(qrel_path), trec.load_run(run_path)
    ev = RelevanceEvaluator(qrel, supported_measures)
    sev = ShardedEvaluator(ev)
    bufs = [ev.tokenize_run(run), ev.tokenize_run(dict(list(run.items())[1:]))]
    if entry == "evaluate_buffer":
        got = [sev.evaluate_buffer(b) for b in bufs]
    else:
        got = sev.evaluate_buffers(bufs)
    for res, buf in zip(got, bufs):
        want = ev.evaluate_buffer(buf)
        assert res.per_query == want
        assert res.aggregates == host_aggregates(want)


def test_mesh_and_default_placement_each_keep_their_static_slabs():
    """One buffer measured on the 1-device mesh and on the default device:
    one layout (both pad the query axis alike), one device copy of its
    static slabs per placement, and ``evaluate_buffer``'s values on both,
    on the first call at each placement and on the calls that reuse it."""
    from jax.sharding import NamedSharding, PartitionSpec

    from repro.core import RelevanceEvaluator
    from repro.distributed import ShardedEvaluator

    measures, lengths = ROUTES["mixed"]
    qrel, run = route_collection(lengths, seed=2)
    ev = RelevanceEvaluator(qrel, measures)
    sev = ShardedEvaluator(ev)
    assert sev.n_shards == 1
    buf = ev.tokenize_run(run)
    rng = np.random.default_rng(2)
    for k in range(3):
        fresh = None if k == 0 else np.round(
            2 * rng.normal(size=buf.qidx.shape[0])).astype(np.float32) / 2
        want = ev.evaluate_buffer(ev.tokenize_run(run), scores=fresh)
        assert_same_values(sev.evaluate_buffer(buf, scores=fresh).per_query,
                           want)
        assert_same_values(ev.evaluate_buffer(buf, scores=fresh), want)
    layout = buf.layout[0][1]
    mesh = NamedSharding(sev.mesh, PartitionSpec(sev.mesh.axis_names[0]))
    assert set(layout.device) == {None, mesh}
    assert len(layout.device[None]) == len(layout.classes) == 5
    for on_mesh, default in zip(layout.device[mesh], layout.device[None]):
        assert on_mesh.rel.sharding == mesh
        assert on_mesh.rel is not default.rel
        np.testing.assert_array_equal(np.asarray(on_mesh.rel),
                                      np.asarray(default.rel))
