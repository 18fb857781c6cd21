"""The ``robust04.dict`` cell: pytrec_eval's ``evaluate(run_dict)`` with a
fresh dict of dicts per call.  It prints the contract's line with its
end-to-end metrics, a sound run is correct, a fault in the answers is not,
and every call gets new dicts and unseen scores over the same docno
strings."""

import json

import numpy as np
import pytest

from conftest import cpu_devices, run_cell
from test_correct import altered, half, stale

from chipbench.drivers.library import ScoreStream
from chipbench.drivers.run_dict import RunDicts

CELL = "robust04.dict"


def test_cell_prints_the_contract_line(cpu_harness, capsys):
    rc = cpu_harness.main(["--workload", CELL, "--seed", str(2**31 + 11),
                           "--seconds", "1.0", "--trace", "0"],
                          device_check=cpu_devices)
    out = capsys.readouterr().out.strip().splitlines()
    assert rc == 0
    line = json.loads(out[-1])
    assert list(line) == ["correct", "attempted", "failed", "metrics",
                          "device", "checks"]
    assert set(line["metrics"]) == {"runs_per_s", "setup_s"}
    assert line["metrics"]["runs_per_s"]["unit"] == "runs/s"
    assert line["metrics"]["runs_per_s"]["value"] > 0
    assert line["attempted"] > 0 and line["failed"] == 0
    note = next(s for s in out if s.startswith(f"[{CELL}]"))
    fields = dict(f.split("=", 1) for f in note.split()[1:]
                  if "=" in f and not f.startswith("widest"))
    assert 0 < float(fields["score_making_share"]) < 1
    assert 0 < float(fields["oov_row_share"]) < 1


def test_sound_run_is_correct(cpu_harness, capsys):
    rc, line = run_cell(cpu_harness, capsys, CELL, seconds=1.5)
    assert rc == 0 and line["correct"] is True, line["checks"]
    assert line["checks"]["max_abs_diff"]["value"] < 1e-6
    assert line["checks"]["wrong_answers"]["value"] == 0


@pytest.mark.parametrize("fault", [stale, half, altered])
def test_fault_is_not_correct(cpu_harness, capsys, monkeypatch, fault):
    from repro.core.evaluator import RelevanceEvaluator

    monkeypatch.setattr(RelevanceEvaluator, "evaluate",
                        fault(RelevanceEvaluator.evaluate))
    rc, line = run_cell(cpu_harness, capsys, CELL)
    assert rc == 0 and line["correct"] is False, line["checks"]


def test_each_call_gets_new_dicts_and_unseen_scores(cpu_harness, capsys,
                                                   monkeypatch):
    from repro.core.evaluator import RelevanceEvaluator

    orig, runs = RelevanceEvaluator.evaluate, []

    def spy(self, run):
        runs.append(run)  # kept alive, so no id is reused
        return orig(self, run)

    monkeypatch.setattr(RelevanceEvaluator, "evaluate", spy)
    rc, line = run_cell(cpu_harness, capsys, CELL)
    assert rc == 0 and line["correct"] is True
    assert len(runs) >= 4  # two warm calls and the window's
    assert len({id(r) for r in runs}) == len(runs)
    qids = list(runs[0])
    for q in qids:
        assert len({id(r[q]) for r in runs}) == len(runs)
        docs = list(runs[0][q])
        for r in runs[1:]:
            assert all(a is b for a, b in zip(r[q], docs))
    seen = {tuple(s for q in qids for s in r[q].values()) for r in runs}
    assert len(seen) == len(runs)


def test_run_dicts_follow_the_score_stream():
    qids = np.repeat(np.array(["301", "302", "303"]), [3, 1, 2])
    docnos = np.array([f"D{j}" for j in range(6)])
    base = np.arange(6, dtype=np.float32)
    stream = ScoreStream(base, 0.1, 2**31 + 3, 64, 2)
    dicts = RunDicts(qids, docnos, stream)
    for i in (0, 1, 63, -1):
        run = dicts.run(i)
        want = stream.scores(i).tolist()
        assert list(run) == ["301", "302", "303"]
        assert [s for q in run for s in run[q].values()] == want
        assert [d for q in run for d in run[q]] == docnos.tolist()
    assert dicts.oov_share({"301": {"D0": 1, "D9": 0}, "302": {"D3": 2}}) \
        == pytest.approx(4 / 6)


def test_rows_not_grouped_by_query_are_refused():
    from chipbench import harness

    qids = np.array(["301", "302", "301"])
    stream = ScoreStream(np.zeros(3, dtype=np.float32), 0.1, 1, 8, 1)
    with pytest.raises(harness.BenchError):
        RunDicts(qids, np.array(["a", "b", "c"]), stream)


def test_config_is_robust04_handed_over_as_dicts():
    """The cell's configuration is the ``robust04`` collection, number for
    number, under a source of its own: pytrec_eval's dict-of-dicts API."""
    from chipbench import harness
    from conftest import ROOT

    spec = json.loads(open(f"{ROOT}/BENCHMARK.json").read())
    entries = {c["name"]: c for c in spec["configs"]}
    cell = next(w for w in spec["workloads"] if w["name"] == CELL)
    mine, base = entries[cell["config"]], entries["robust04"]
    assert mine["file"] != base["file"] and mine["source"] != base["source"]
    assert mine["reduced"] == []
    got = harness.load_cell(CELL).config
    want = json.loads(open(f"{ROOT}/{base['file']}").read())
    assert {k for k in want if got[k] != want[k]} == {"name", "source"}
    assert set(got) - set(want) == {"input"}
