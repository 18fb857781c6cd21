"""Spans of the program's own layers (``repro.obs``): off without a profiler
session, on under one, nested per thread, and written into the profiler's
``.xplane.pb`` under their bare names."""

import asyncio
import contextlib
import glob
import json
import os
import threading

import jax
import pytest

from repro import obs
from repro.core import RelevanceEvaluator
from repro.kernels import bucketing

QREL = {"q1": {"d1": 1, "d2": 0}, "q2": {"d3": 2, "d4": 1}}
RUN = {"q1": {"d1": 1.0, "d2": 0.5}, "q2": {"d3": 0.1, "d4": 0.3, "d5": 0.2}}
LAYERS = ["repro.ingest", "repro.transfer", "repro.compute", "repro.fetch",
          "repro.results"]


@contextlib.contextmanager
def profiled(log_dir):
    """A profiler session as the benchmark records one: no Python tracer."""
    obs.clear()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with jax.profiler.trace(str(log_dir), profiler_options=opts):
        yield


@pytest.fixture(scope="module")
def ev():
    ev = RelevanceEvaluator(QREL, {"map", "ndcg"})
    ev.evaluate(RUN)  # compiled outside any session
    return ev


def children(recs, parent):
    return [r for r in recs if r.parent == parent]


def test_off_without_a_profiler_session(ev):
    obs.clear()
    assert not jax.profiler.TraceAnnotation.is_enabled()
    assert obs.span("repro.a") is obs.span("repro.b")
    with obs.span("repro.a"):
        obs.mark("repro.compile.x")
    ev.evaluate_buffer(ev.tokenize_run(RUN))
    assert obs.records() == [] and obs.dropped() == 0


@pytest.mark.parametrize("entry", ["evaluate", "evaluate_buffer",
                                   "evaluate_buffers"])
def test_one_root_with_the_five_layers_in_order(ev, tmp_path, entry):
    buf = ev.tokenize_run(RUN)
    call = {"evaluate": lambda: ev.evaluate(RUN),
            "evaluate_buffer": lambda: ev.evaluate_buffer(buf),
            "evaluate_buffers": lambda: ev.evaluate_buffers([buf, buf])[0]}
    with profiled(tmp_path):
        got = call[entry]()
    assert got == ev.evaluate(RUN)
    recs = obs.records()
    roots = [i for i, r in enumerate(recs) if r.name == "repro.evaluate"]
    assert len(roots) == 1 and recs[roots[0]].parent is None
    root = recs[roots[0]]
    kids = children(recs, roots[0])
    assert [r.name for r in kids] == LAYERS
    for r in kids:
        assert root.start_ns <= r.start_ns <= r.end_ns <= root.end_ns
        assert r.thread_id == root.thread_id
    for a, b in zip(kids, kids[1:]):
        assert a.end_ns <= b.start_ns


@pytest.mark.parametrize("session", [False, True], ids=["off", "profiled"])
def test_a_count_carries_its_value_only_under_a_session(ev, tmp_path,
                                                        session):
    with (profiled(tmp_path) if session else contextlib.nullcontext()):
        obs.clear()
        with obs.span("repro.a"):
            obs.count("repro.n", 7)
        obs.count("repro.m", 0)
        ev.evaluate_buffer(ev.tokenize_run(RUN))
    recs = obs.records()
    if not session:
        assert recs == []
        return
    named = {r.name: (i, r) for i, r in enumerate(recs)}
    i_a, _ = named["repro.a"]
    _, n = named["repro.n"]
    assert (n.parent, n.value, n.start_ns) == (i_a, 7, n.end_ns)
    assert named["repro.m"][1].parent is None
    assert named["repro.m"][1].value == 0
    assert named["repro.a"][1].value is None  # spans carry no number
    # the evaluator counts the one batch of this two-query buffer
    i_t, _ = named["repro.transfer"]
    rows = [r for r in recs if r.name == "repro.batch.rows"]
    cells = [r for r in recs if r.name == "repro.batch.cells"]
    assert [(r.parent, r.value) for r in rows] == [(i_t, 5)]
    assert [(r.parent, r.value) for r in cells] == [(i_t, 2 * 8)]


def test_a_worker_thread_takes_its_own_parent(tmp_path):
    index = {}

    def worker():
        with obs.span("repro.worker"):
            with obs.span("repro.worker.inner"):
                pass

    with profiled(tmp_path):
        with obs.span("repro.main"):
            t = threading.Thread(target=worker)
            t.start()
            t.join(timeout=30)
            with obs.span("repro.main.inner"):
                pass
    assert not t.is_alive()
    recs = obs.records()
    for i, r in enumerate(recs):
        index[r.name] = i
    assert recs[index["repro.main"]].parent is None
    assert recs[index["repro.main.inner"]].parent == index["repro.main"]
    assert recs[index["repro.worker"]].parent is None
    assert recs[index["repro.worker.inner"]].parent == index["repro.worker"]
    assert (recs[index["repro.worker"]].thread_id
            != recs[index["repro.main"]].thread_id)


def test_a_retrace_is_marked_under_the_compute_span(tmp_path):
    # a measure set no other test compiles, so the first call retraces
    ev = RelevanceEvaluator(QREL, {"P.13", "recall.17", "map"})
    buf = ev.tokenize_run(RUN)
    before = bucketing.compile_count("measure_core")
    with profiled(tmp_path / "first"):
        ev.evaluate_buffer(buf)
    assert bucketing.compile_count("measure_core") == before + 1
    recs = obs.records()
    marks = [r for r in recs if r.name == "repro.compile.measure_core"]
    assert len(marks) == 1 and marks[0].start_ns == marks[0].end_ns
    parent = recs[marks[0].parent]
    assert parent.name == "repro.compute"
    assert parent.start_ns <= marks[0].start_ns <= parent.end_ns
    with profiled(tmp_path / "second"):
        ev.evaluate_buffer(buf)
    assert not [r for r in obs.records()
                if r.name.startswith("repro.compile.")]
    assert bucketing.compile_count("measure_core") == before + 1


def test_the_xplane_holds_the_layers_by_name(ev, tmp_path):
    from jax.profiler import ProfileData

    with profiled(tmp_path):
        ev.evaluate_buffer(ev.tokenize_run(RUN))
    paths = glob.glob(os.path.join(tmp_path, "**", "*.xplane.pb"),
                      recursive=True)
    assert len(paths) == 1
    names = [e.name for plane in ProfileData.from_file(paths[0]).planes
             if plane.name == "/host:CPU" for line in plane.lines
             for e in line.events if e.name.startswith("repro.")]
    # the run's tokenization, then the evaluation and its layers
    assert names == ["repro.tokenize", "repro.evaluate"] + LAYERS


def test_records_past_the_capacity_are_counted(tmp_path, monkeypatch):
    monkeypatch.setattr(obs, "CAPACITY", 3)
    with profiled(tmp_path):
        for _ in range(5):
            with obs.span("repro.x"):
                pass
    assert len(obs.records()) == 3 and obs.dropped() == 2
    obs.clear()
    assert obs.records() == [] and obs.dropped() == 0


def test_the_service_path_spans(tmp_path):
    from repro.serve import EvaluationService
    from repro.serve.frontend import handle_line

    async def go():
        svc = EvaluationService(window=0.0)
        svc.register_qrel("c", QREL, measures=["map"])
        line = json.dumps({"op": "evaluate", "id": 1, "qrel_id": "c",
                           "run": RUN})
        await handle_line(svc, line)  # compiled outside the session
        with profiled(tmp_path):
            resp = json.loads(await handle_line(svc, line))
        await svc.drain()
        return resp

    resp = asyncio.run(go())
    assert resp["ok"] is True
    recs = obs.records()
    names = [r.name for r in recs]
    assert names.count("repro.serve.decode") == 1
    assert names.count("repro.serve.encode") == 1
    flush = names.index("repro.serve.flush")
    root = names.index("repro.evaluate")
    assert recs[root].parent == flush
    assert [r.name for r in children(recs, root)] == LAYERS
