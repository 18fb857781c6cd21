"""The ``string_path_share`` reader: Σ ``repro.tokenize.strings`` ÷ Σ
``repro.tokenize.rows`` on hand-made counts, silent without such counts, a
trace or a program that records them, and 1.0 in a traced run of the dict
cell, whose run holds docnos that no qrels mention."""

import sys

import pytest

from conftest import run_cell

from chipbench import harness

READ = harness.reader("string_path_share")


def counted(obs, chunks):
    """One tokenize span per ``(rows, strings)`` entry, its two counts
    under it."""
    recs = []
    for k, (rows, strings) in enumerate(chunks):
        t = 10 * k
        recs.append(obs.Record("repro.tokenize", None, t, t + 9, 7))
        span = len(recs) - 1
        recs.append(obs.Record("repro.tokenize.rows", span, t + 1, t + 1, 7,
                               rows))
        recs.append(obs.Record("repro.tokenize.oov", span, t + 1, t + 1, 7,
                               rows // 4))
        recs.append(obs.Record("repro.tokenize.strings", span, t + 1,
                               t + 1, 7, strings))
    return recs


def readings(trace=object(), calls=1):
    return harness.Readings(trace, calls, {}, {})


CASES = {
    "every-chunk-by-strings": ([(249_000, 249_000)] * 3, 1.0),
    "no-chunk-by-strings": ([(1_000, 0)] * 2, 0.0),
    "one-of-four-rows": ([(3_000, 0), (1_000, 1_000)], 0.25),
    "empty-chunks": ([(0, 0)], None),
}


@pytest.mark.parametrize("case", CASES)
def test_share_of_hand_made_counts(monkeypatch, case):
    from repro import obs

    chunks, want = CASES[case]
    recs = counted(obs, chunks)
    monkeypatch.setattr(obs, "records", lambda: list(recs))
    monkeypatch.setattr(obs, "dropped", lambda: 0)
    assert READ(readings()) == want
    assert READ(readings(trace=None)) is None
    monkeypatch.setattr(obs, "dropped", lambda: 1)
    assert READ(readings()) is None


def test_silent_without_the_program(monkeypatch):
    from repro import obs

    monkeypatch.setattr(obs, "dropped", lambda: 0)
    # a program that counts its batches but not its tokenizer, as the parent
    monkeypatch.setattr(obs, "records", lambda: [
        obs.Record("repro.evaluate", None, 0, 9, 1),
        obs.Record("repro.transfer", 0, 1, 4, 1),
        obs.Record("repro.batch.rows", 1, 2, 2, 1, 100)])
    assert READ(readings()) is None
    monkeypatch.setattr(obs, "records", lambda: [])
    assert READ(readings()) is None
    monkeypatch.setitem(sys.modules, "repro.obs", None)
    assert READ(readings()) is None


def test_traced_dict_run_takes_the_string_path(cpu_harness, capsys):
    from repro import obs

    obs.clear()
    rc, line = run_cell(cpu_harness, capsys, "robust04.dict", trace=1)
    assert rc == 0 and line["correct"] is True
    assert line["metrics"]["string_path_share"] == {"value": 1.0,
                                                    "unit": "share"}
    recs = obs.records()
    rows = sum(r.value for r in recs if r.name == "repro.tokenize.rows")
    calls = [r.name for r in recs].count("repro.evaluate")
    assert calls and rows == calls * 12 * 120  # the tiny shape's rows
