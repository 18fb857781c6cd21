"""The ``transfer_mb_per_call`` reader: exact on hand-made counts, silent
without counts, a trace or a program that records them, and, in a traced
run of each re-scoring cell, the scores slabs alone (the static slabs went
to the device in set-up)."""

import sys

import pytest

from conftest import TINY, run_cell
from test_ltr import SMALL

from chipbench import harness

READ = harness.reader("transfer_mb_per_call")


def counted(obs, calls):
    """One evaluation per entry of ``calls``, each a list of the bytes of
    the puts counted under its transfer span."""
    recs = []
    for k, puts in enumerate(calls):
        t = 100 * k
        recs.append(obs.Record("repro.evaluate", None, t, t + 99, 7))
        recs.append(obs.Record("repro.transfer", len(recs) - 1, t, t + 9,
                               7))
        transfer = len(recs) - 1
        for nbytes in puts:
            recs.append(obs.Record("repro.transfer.bytes", transfer, t + 1,
                                   t + 1, 7, nbytes))
    return recs


def readings(trace=object(), calls=1):
    return harness.Readings(trace, calls, {}, {})


CASES = {
    "scores-alone": ([[1_048_576]] * 3, 1.048576),
    "upload-then-scores": ([[5_800_000], [1_000_000], [1_000_000],
                            [1_000_000]], 2.2),
    "two-puts-a-call": ([[500_000, 250_000]] * 2, 0.75),
    "no-counts": ([[], []], None),
}


@pytest.mark.parametrize("case", CASES)
def test_megabytes_of_hand_made_counts(monkeypatch, case):
    from repro import obs

    calls, want = CASES[case]
    recs = counted(obs, calls)
    monkeypatch.setattr(obs, "records", lambda: list(recs))
    monkeypatch.setattr(obs, "dropped", lambda: 0)
    got = READ(readings(calls=len(calls)))
    assert got == (None if want is None else pytest.approx(want))
    assert READ(readings(trace=None)) is None
    monkeypatch.setattr(obs, "dropped", lambda: 1)
    assert READ(readings()) is None


def test_silent_without_the_program(monkeypatch):
    from repro import obs

    monkeypatch.setattr(obs, "dropped", lambda: 0)
    # a program that spans its transfers but counts no bytes, as the parent
    monkeypatch.setattr(obs, "records", lambda: [
        obs.Record("repro.evaluate", None, 0, 9, 1),
        obs.Record("repro.transfer", 0, 1, 4, 1),
        obs.Record("repro.batch.cells", 1, 2, 2, 1, 4096)])
    assert READ(readings()) is None
    monkeypatch.setattr(obs, "records", lambda: [])
    assert READ(readings()) is None
    monkeypatch.setitem(sys.modules, "repro.obs", None)
    assert READ(readings()) is None


@pytest.mark.parametrize("workload", ["robust04.rescore",
                                      "msmarco-dev.rescore",
                                      "mslr-web30k.rescore"])
def test_traced_rescore_run_sends_the_scores_alone(cpu_harness, capsys,
                                                   monkeypatch, workload):
    from repro import obs

    monkeypatch.setitem(TINY, "ltr", SMALL)
    obs.clear()
    rc, line = run_cell(cpu_harness, capsys, workload, trace=1)
    assert rc == 0 and line["correct"] is True
    cells = [r.value for r in obs.records() if r.name == "repro.batch.cells"]
    calls = [r.name for r in obs.records()].count("repro.evaluate")
    got = line["metrics"]["transfer_mb_per_call"]
    assert got["unit"] == "MB"
    # four bytes a padded cell: the float32 scores slabs and nothing else
    assert got["value"] == pytest.approx(4 * sum(cells) / calls / 1e6)
