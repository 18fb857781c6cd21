"""The ``tokenize_ms`` reader: milliseconds per evaluation in
``repro.tokenize`` on hand-made records, silent without such spans, a trace
or a program that records them, and a part of ``ingest_ms`` in a traced run
of the dict cell."""

import sys

import pytest

from conftest import run_cell

from chipbench import harness

READ = harness.reader("tokenize_ms")


def made(obs, tokenize_ms):
    """One evaluation per entry, its ``repro.tokenize`` under its
    ``repro.ingest`` lasting the entry's milliseconds."""
    recs, t = [], 0
    for ms in tokenize_ms:
        ns = int(ms * 10**6)
        recs.append(obs.Record("repro.evaluate", None, t, t + ns + 20, 7))
        recs.append(obs.Record("repro.ingest", len(recs) - 1, t,
                               t + ns + 10, 7))
        recs.append(obs.Record("repro.tokenize", len(recs) - 1, t, t + ns,
                               7))
        t += ns + 20
    return recs


def readings(trace=object(), calls=1):
    return harness.Readings(trace, calls, {}, {})


CASES = {"one-call": ([400.0], 400.0), "three-calls": ([1.0, 2.0, 6.0], 3.0)}


@pytest.mark.parametrize("case", CASES)
def test_ms_of_hand_made_spans(monkeypatch, case):
    from repro import obs

    spans, want = CASES[case]
    recs = made(obs, spans)
    monkeypatch.setattr(obs, "records", lambda: list(recs))
    monkeypatch.setattr(obs, "dropped", lambda: 0)
    assert READ(readings(calls=len(spans))) == pytest.approx(want)
    assert READ(readings(trace=None)) is None
    monkeypatch.setattr(obs, "dropped", lambda: 1)
    assert READ(readings()) is None


def test_silent_without_the_program(monkeypatch):
    from repro import obs

    monkeypatch.setattr(obs, "dropped", lambda: 0)
    # a program that spans its ingest but not its tokenizer, as the parent
    monkeypatch.setattr(obs, "records", lambda: [
        obs.Record("repro.evaluate", None, 0, 9, 1),
        obs.Record("repro.ingest", 0, 1, 4, 1)])
    assert READ(readings()) is None
    monkeypatch.setattr(obs, "records", lambda: [])
    assert READ(readings()) is None
    monkeypatch.setitem(sys.modules, "repro.obs", None)
    assert READ(readings()) is None


def test_traced_dict_run_reads_a_part_of_ingest(cpu_harness, capsys):
    from repro import obs

    obs.clear()
    rc, line = run_cell(cpu_harness, capsys, "robust04.dict", trace=1)
    assert rc == 0 and line["correct"] is True
    got, ingest = (line["metrics"][m] for m in ("tokenize_ms", "ingest_ms"))
    assert got["unit"] == "ms"
    assert 0 < got["value"] < ingest["value"]


def test_silent_in_a_traced_rescore_run(cpu_harness, capsys):
    """The library driver tokenizes in set-up, outside the window."""
    from repro import obs

    obs.clear()
    rc, line = run_cell(cpu_harness, capsys, "robust04.rescore", trace=1)
    assert rc == 0 and line["correct"] is True
    assert READ(harness.Readings(object(), 1, {}, {})) is None
