"""The ``fetch_copies_per_call`` reader: exact on hand-made counts, silent
without counts, a trace or a program that records them, and reported by a
traced run of each cell, one copy per depth class a call."""

import sys

import pytest

from conftest import TINY, run_cell
from test_ltr import SMALL

from chipbench import harness

READ = harness.reader("fetch_copies_per_call")


def counted(obs, calls):
    """One evaluation per entry of ``calls``, each a list of the bytes of
    the copies counted under its fetch span."""
    recs = []
    for k, copies in enumerate(calls):
        t = 100 * k
        recs.append(obs.Record("repro.evaluate", None, t, t + 99, 7))
        recs.append(obs.Record("repro.fetch", len(recs) - 1, t + 50, t + 59,
                               7))
        fetch = len(recs) - 1
        for nbytes in copies:
            recs.append(obs.Record("repro.fetch.copy", fetch, t + 51, t + 51,
                                   7, nbytes))
    return recs


def readings(trace=object(), calls=1):
    return harness.Readings(trace, calls, {}, {})


CASES = {
    "one-class": ([[32 * 256 * 4]] * 3, 1.0),
    "nine-classes": ([[2 * 64 * 4] * 9] * 2, 9.0),
    "mixed-calls": ([[4], [4, 8]], 1.5),
    "no-counts": ([[], []], None),
}


@pytest.mark.parametrize("case", CASES)
def test_copies_of_hand_made_counts(monkeypatch, case):
    from repro import obs

    calls, per_call = CASES[case]
    recs = counted(obs, calls)
    monkeypatch.setattr(obs, "records", lambda: list(recs))
    monkeypatch.setattr(obs, "dropped", lambda: 0)
    assert READ(readings(calls=len(calls))) == per_call
    assert READ(readings(trace=None)) is None
    monkeypatch.setattr(obs, "dropped", lambda: 1)
    assert READ(readings()) is None


def test_silent_without_the_program(monkeypatch):
    from repro import obs

    monkeypatch.setattr(obs, "dropped", lambda: 0)
    # a program that spans its fetches but counts no copy, as the parent
    monkeypatch.setattr(obs, "records", lambda: [
        obs.Record("repro.evaluate", None, 0, 9, 1),
        obs.Record("repro.fetch", 0, 5, 8, 1)])
    assert READ(readings()) is None
    monkeypatch.setattr(obs, "records", lambda: [])
    assert READ(readings()) is None
    monkeypatch.setitem(sys.modules, "repro.obs", None)
    assert READ(readings()) is None


@pytest.mark.parametrize("workload", ["robust04.rescore",
                                      "msmarco-dev.rescore",
                                      "mslr-web30k.rescore"])
def test_traced_cell_fetches_once_per_class(cpu_harness, capsys,
                                            monkeypatch, workload):
    from repro import obs

    monkeypatch.setitem(TINY, "ltr", SMALL)
    obs.clear()
    rc, line = run_cell(cpu_harness, capsys, workload, trace=1)
    assert rc == 0 and line["correct"] is True
    got = line["metrics"]
    assert got["fetch_copies_per_call"]["unit"] == "count"
    assert (got["fetch_copies_per_call"]["value"]
            == got["batches_per_call"]["value"])
    if workload != "mslr-web30k.rescore":
        assert got["fetch_copies_per_call"]["value"] == 1.0
