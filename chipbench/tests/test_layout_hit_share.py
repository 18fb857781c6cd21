"""The ``layout_hit_share`` reader: exact on hand-made marks, silent
without marks, a trace or a program that records them, and 1.0 in a traced
run of a re-scoring cell (the layout is built by the warm calls of set-up,
outside the window)."""

import sys

import pytest

from conftest import run_cell

from chipbench import harness

READ = harness.reader("layout_hit_share")


def marks(obs, hits, builds):
    """One call's root span and ingest span per mark, builds first."""
    recs = []
    for k, name in enumerate(["repro.layout.build"] * builds
                             + ["repro.layout.hit"] * hits):
        t = 10 * k
        recs.append(obs.Record("repro.evaluate", None, t, t + 9, 7))
        recs.append(obs.Record("repro.ingest", len(recs) - 1, t, t + 4, 7))
        recs.append(obs.Record(name, len(recs) - 1, t + 1, t + 1, 7))
    return recs


def readings(trace=object(), calls=1):
    return harness.Readings(trace, calls, {}, {})


@pytest.mark.parametrize("hits,builds,want", [
    (10, 0, 1.0), (9, 1, 0.9), (0, 0, None), (0, 3, 0.0)],
    ids=["all-hits", "one-build-nine-hits", "no-marks", "all-builds"])
def test_share_of_hand_made_marks(monkeypatch, hits, builds, want):
    from repro import obs

    recs = marks(obs, hits, builds)
    monkeypatch.setattr(obs, "records", lambda: list(recs))
    monkeypatch.setattr(obs, "dropped", lambda: 0)
    assert READ(readings(calls=max(hits + builds, 1))) == want
    assert READ(readings(trace=None)) is None
    monkeypatch.setattr(obs, "dropped", lambda: 1)
    assert READ(readings()) is None


def test_silent_without_the_program(monkeypatch):
    from repro import obs

    monkeypatch.setattr(obs, "dropped", lambda: 0)
    monkeypatch.setattr(obs, "records", lambda: [
        obs.Record("repro.compile.measure_core", None, 0, 0, 1)])
    assert READ(readings()) is None
    monkeypatch.setitem(sys.modules, "repro.obs", None)
    assert READ(readings()) is None


def test_traced_rescore_run_reuses_the_layout(cpu_harness, capsys):
    from repro import obs

    obs.clear()
    rc, line = run_cell(cpu_harness, capsys, "robust04.rescore", trace=1)
    assert rc == 0 and line["correct"] is True
    got = line["metrics"]["layout_hit_share"]
    assert got == {"value": 1.0, "unit": "share"}
