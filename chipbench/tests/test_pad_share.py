"""The ``pad_share`` and ``batches_per_call`` readers: exact on hand-made
counts, silent without counts, a trace or a program that records them,
and reported by a traced run of a cell, one batch a call for a uniform
collection."""

import sys

import pytest

from conftest import run_cell

from chipbench import harness

READ = {m: harness.reader(m) for m in ("pad_share", "batches_per_call")}


def counted(obs, calls):
    """One evaluation per entry of ``calls``, each a list of ``(rows,
    cells)`` batches counted under its transfer span."""
    recs = []
    for k, batches in enumerate(calls):
        t = 100 * k
        recs.append(obs.Record("repro.evaluate", None, t, t + 99, 7))
        recs.append(obs.Record("repro.transfer", len(recs) - 1, t, t + 9, 7))
        for rows, cells in batches:
            recs.append(obs.Record("repro.batch.rows", len(recs) - 1,
                                   t + 1, t + 1, 7, rows))
            recs.append(obs.Record("repro.batch.cells", len(recs) - 2,
                                   t + 1, t + 1, 7, cells))
    return recs


def readings(trace=object(), calls=1):
    return harness.Readings(trace, calls, {}, {})


CASES = {
    "one-rectangle": ([[(249000, 262144)]] * 3, 1 - 249000 / 262144, 1.0),
    "three-classes": ([[(6, 8), (30, 64), (100, 128)]] * 2,
                      1 - 136 / 200, 3.0),
    "mixed-calls": ([[(1, 4)], [(1, 4), (2, 4)]], 1 - 4 / 12, 1.5),
    "no-counts": ([[], []], None, None),
}


@pytest.mark.parametrize("case", CASES)
def test_share_and_count_of_hand_made_counts(monkeypatch, case):
    from repro import obs

    calls, share, per_call = CASES[case]
    recs = counted(obs, calls)
    monkeypatch.setattr(obs, "records", lambda: list(recs))
    monkeypatch.setattr(obs, "dropped", lambda: 0)
    r = readings(calls=len(calls))
    assert READ["pad_share"](r) == (None if share is None
                                    else pytest.approx(share))
    assert READ["batches_per_call"](r) == per_call
    for read in READ.values():
        assert read(readings(trace=None)) is None
    monkeypatch.setattr(obs, "dropped", lambda: 1)
    for read in READ.values():
        assert read(readings()) is None


@pytest.mark.parametrize("metric", READ)
def test_silent_without_the_program(monkeypatch, metric):
    from repro import obs

    monkeypatch.setattr(obs, "dropped", lambda: 0)
    # a program that spans its calls but counts no batch, as the parent
    monkeypatch.setattr(obs, "records", lambda: [
        obs.Record("repro.evaluate", None, 0, 9, 1),
        obs.Record("repro.layout.hit", 0, 1, 1, 1)])
    assert READ[metric](readings()) is None
    monkeypatch.setattr(obs, "records", lambda: [])
    assert READ[metric](readings()) is None
    monkeypatch.setitem(sys.modules, "repro.obs", None)
    assert READ[metric](readings()) is None


@pytest.mark.parametrize("workload", ["robust04.rescore",
                                      "msmarco-dev.rescore"])
def test_traced_uniform_cell_is_one_rectangle(cpu_harness, capsys, workload):
    from repro import obs
    from repro.kernels import bucketing

    obs.clear()
    rc, line = run_cell(cpu_harness, capsys, workload, trace=1)
    assert rc == 0 and line["correct"] is True
    cfg = cpu_harness.load_cell(workload).config
    nq = cfg.get("topics") or cfg["queries"]
    cells = bucketing.bucket_queries(nq) * bucketing.bucket_docs(cfg["depth"])
    got = line["metrics"]
    assert got["batches_per_call"] == {"value": 1.0, "unit": "count"}
    assert got["pad_share"]["unit"] == "share"
    assert got["pad_share"]["value"] == pytest.approx(
        1 - nq * cfg["depth"] / cells)
