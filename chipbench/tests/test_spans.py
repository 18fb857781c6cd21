"""The host-layer readers (``chipbench/metrics/_spans.py`` and the six
metrics on it): exact on hand-made records, silent without a trace or a
program that records spans, and reported by a traced run of a cell."""

import sys

import pytest

from conftest import run_cell

from chipbench import harness

TIMES = {"ingest_ms": "repro.ingest", "transfer_ms": "repro.transfer",
         "compute_wait_ms": "repro.compute", "fetch_ms": "repro.fetch",
         "results_ms": "repro.results"}
METRICS = list(TIMES) + ["compiles_in_window"]


def made(obs):
    """Two calls' records: ``repro.<layer>`` lasts 1, 2, ... ms on call 1 and
    twice that on call 2; call 2 retraces once."""
    recs, t = [], 0
    for call in (1, 2):
        root = len(recs)
        recs.append(obs.Record("repro.evaluate", None, t, None, 7))
        for k, name in enumerate(TIMES.values(), 1):
            recs.append(obs.Record(name, root, t, t + call * k * 10**6, 7))
            if call == 2 and name == "repro.compute":
                recs.append(obs.Record("repro.compile.measure_core",
                                       len(recs) - 1, t, t, 7))
            t += call * k * 10**6
        recs[root] = recs[root]._replace(end_ns=t)
    return recs


@pytest.fixture
def fake(monkeypatch):
    from repro import obs

    recs = made(obs)
    monkeypatch.setattr(obs, "records", lambda: list(recs))
    monkeypatch.setattr(obs, "dropped", lambda: 0)
    return obs


def readings(trace=object(), calls=2):
    return harness.Readings(trace, calls, {}, {})


@pytest.mark.parametrize("metric", METRICS)
def test_reader_on_hand_made_records(fake, monkeypatch, metric):
    read = harness.reader(metric)
    if metric == "compiles_in_window":
        assert read(readings()) == 1
    else:
        k = list(TIMES).index(metric) + 1
        assert read(readings()) == pytest.approx((1 + 2) * k / 2)
    assert read(readings(trace=None)) is None
    monkeypatch.setattr(fake, "dropped", lambda: 1)
    assert read(readings()) is None


@pytest.mark.parametrize("metric", METRICS)
def test_reader_without_records_or_program(monkeypatch, metric):
    from repro import obs

    read = harness.reader(metric)
    monkeypatch.setattr(obs, "dropped", lambda: 0)
    monkeypatch.setattr(obs, "records", lambda: [
        obs.Record("repro.other", None, 0, 5, 1)])
    want = 0 if metric == "compiles_in_window" else None
    assert read(readings()) == want
    monkeypatch.setattr(obs, "records", lambda: [])
    assert read(readings()) is None
    # a program without repro.obs: nothing to read, and no error
    monkeypatch.setitem(sys.modules, "repro.obs", None)
    assert read(readings()) is None


def test_traced_run_reports_every_host_layer(cpu_harness, capsys):
    from repro import obs

    obs.clear()
    rc, line = run_cell(cpu_harness, capsys, "robust04.rescore", trace=1)
    assert rc == 0 and line["correct"] is True
    got = {m: line["metrics"][m]["value"] for m in METRICS}
    assert got["compiles_in_window"] == 0
    calls = line["attempted"]
    wall_ms = line["device"]["window_s"] * 1e3 / calls
    layers_ms = sum(got[m] for m in TIMES)
    assert all(got[m] > 0 for m in TIMES)
    assert layers_ms <= wall_ms
    units = {m["name"]: m["unit"] for m in harness.load_cell(
        "robust04.rescore").per_layer}
    assert {line["metrics"][m]["unit"] for m in TIMES} == {"ms"}
    assert units["compiles_in_window"] == "count"
