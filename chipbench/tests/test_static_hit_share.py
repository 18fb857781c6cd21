"""The ``static_hit_share`` reader: exact on hand-made marks, silent
without marks, a trace or a program that records them, and 1.0 in a traced
run of each re-scoring cell (the warm calls of set-up send the static
slabs, outside the window)."""

import sys

import pytest

from conftest import TINY, run_cell
from test_ltr import SMALL

from chipbench import harness

READ = harness.reader("static_hit_share")


def marks(obs, calls):
    """One evaluation per entry of ``calls``, each a list of the marks of
    its classes under its transfer span."""
    recs = []
    for k, names in enumerate(calls):
        t = 10 * k
        recs.append(obs.Record("repro.evaluate", None, t, t + 9, 7))
        recs.append(obs.Record("repro.transfer", len(recs) - 1, t, t + 4,
                               7))
        transfer = len(recs) - 1
        for name in names:
            recs.append(obs.Record(f"repro.transfer.static_{name}",
                                   transfer, t + 1, t + 1, 7))
    return recs


def readings(trace=object(), calls=1):
    return harness.Readings(trace, calls, {}, {})


CASES = {
    "all-hits": ([["hit"]] * 10, 1.0),
    "one-upload-nine-hits": ([["upload"]] + [["hit"]] * 9, 0.9),
    "nine-classes": ([["upload"] * 9] + [["hit"] * 9] * 3, 0.75),
    "all-uploads": ([["upload"]] * 3, 0.0),
    "no-marks": ([[], []], None),
}


@pytest.mark.parametrize("case", CASES)
def test_share_of_hand_made_marks(monkeypatch, case):
    from repro import obs

    calls, want = CASES[case]
    recs = marks(obs, calls)
    monkeypatch.setattr(obs, "records", lambda: list(recs))
    monkeypatch.setattr(obs, "dropped", lambda: 0)
    assert READ(readings(calls=len(calls))) == want
    assert READ(readings(trace=None)) is None
    monkeypatch.setattr(obs, "dropped", lambda: 1)
    assert READ(readings()) is None


def test_silent_without_the_program(monkeypatch):
    from repro import obs

    monkeypatch.setattr(obs, "dropped", lambda: 0)
    # a program that spans its transfers but marks no static slabs, as
    # the parent
    monkeypatch.setattr(obs, "records", lambda: [
        obs.Record("repro.evaluate", None, 0, 9, 1),
        obs.Record("repro.transfer", 0, 1, 4, 1),
        obs.Record("repro.batch.rows", 1, 2, 2, 1, 100)])
    assert READ(readings()) is None
    monkeypatch.setattr(obs, "records", lambda: [])
    assert READ(readings()) is None
    monkeypatch.setitem(sys.modules, "repro.obs", None)
    assert READ(readings()) is None


@pytest.mark.parametrize("workload", ["robust04.rescore",
                                      "msmarco-dev.rescore",
                                      "mslr-web30k.rescore"])
def test_traced_rescore_run_keeps_the_static_slabs(cpu_harness, capsys,
                                                   monkeypatch, workload):
    from repro import obs

    monkeypatch.setitem(TINY, "ltr", SMALL)
    obs.clear()
    rc, line = run_cell(cpu_harness, capsys, workload, trace=1)
    assert rc == 0 and line["correct"] is True
    got = line["metrics"]["static_hit_share"]
    assert got == {"value": 1.0, "unit": "share"}
