"""The ``ltr`` generator (MSLR-WEB30K shape) and its cell at a tiny size:
the published counts hold exactly, the list is the judged set, the
reference is pure_eval and the control fails it, and a traced run is
correct, split into depth classes."""

import json
import os

import numpy as np
import pytest

from conftest import ROOT, TINY, run_cell

from chipbench import check, control, reference
from chipbench.generators import ltr

CELL = "mslr-web30k.rescore"
#: a tiny MSLR: lists of 1-600 documents, so the classes reach 1,024 wide
#: and depth-bounded nDCG takes the top-k kernel there, and short lists
#: enough that some queries have nothing relevant
SMALL = dict(queries=600, rows=12000, longest_list=600)


def config(**shape):
    cfg = json.load(open(os.path.join(ROOT, "chipbench", "configs",
                                      "mslr-web30k.json")))
    cfg.update(shape)
    return cfg


@pytest.fixture
def tiny_ltr(monkeypatch):
    monkeypatch.setitem(TINY, "ltr", SMALL)


def test_published_counts_at_full_size():
    cfg = config()
    for seed in (1, 2**31 + 7):
        lengths = ltr.list_lengths(cfg, np.random.default_rng(seed))
        assert lengths.shape == (31531,)
        assert int(lengths.sum()) == 3771125
        assert int(lengths.min()) >= 1 and int(lengths.max()) == 1251
        assert lengths.mean() == pytest.approx(119.6, abs=0.05)


def test_every_seed_lists_the_same_lengths():
    cfg = config()
    a = ltr.list_lengths(cfg, np.random.default_rng(5))
    b = ltr.list_lengths(cfg, np.random.default_rng(2**31 + 5))
    assert not np.array_equal(a, b)  # dealt to other queries
    np.testing.assert_array_equal(np.sort(a), np.sort(b))


@pytest.mark.parametrize("seed", [3, 2**31 + 11])
def test_lists_are_the_judged_sets(seed):
    cfg = config(**SMALL)
    coll = ltr.generate(cfg, seed)
    assert coll.qids.shape == coll.docnos.shape == coll.scores.shape
    assert coll.scores.shape[0] == SMALL["rows"]
    assert coll.scores.dtype == np.float32
    run = check.run_dict(coll.qids, coll.docnos, coll.scores)
    assert list(run) == sorted(run) == list(coll.qrel)  # the buffer's order
    assert len(run) == SMALL["queries"]
    assert sum(map(len, run.values())) == SMALL["rows"]
    assert max(map(len, run.values())) == SMALL["longest_list"]
    grades = [g for q in coll.qrel.values() for g in q.values()]
    assert set(grades) <= set(range(5)) and len(set(grades)) >= 4
    for q, docs in run.items():
        assert set(docs) == set(coll.qrel[q])
    # ties on the score grid, and queries with nothing relevant
    assert len(np.unique(coll.scores)) < coll.scores.shape[0]
    assert any(max(q.values()) == 0 for q in coll.qrel.values())
    again = ltr.generate(cfg, seed)
    np.testing.assert_array_equal(again.scores, coll.scores)


def test_reference_is_pure_eval_and_the_control_fails():
    from repro.baselines import pure_eval

    cfg = config(**SMALL)
    coll = ltr.generate(cfg, 12)
    run = check.run_dict(coll.qids, coll.docnos, coll.scores)
    measures = cfg["reference_measures"]
    assert reference.evaluate(run, coll.qrel, measures) == \
        pure_eval.evaluate(run, coll.qrel, measures)
    r = control.reading(cfg, coll, [coll.scores])
    assert not check.passed(r.checks(cfg["check"]["max_abs_diff"], 0))
    assert r.max_abs_diff > 10 * cfg["check"]["max_abs_diff"]


def test_traced_run_is_correct_in_depth_classes(cpu_harness, capsys,
                                                 tiny_ltr):
    from repro import obs

    obs.clear()
    rc, line = run_cell(cpu_harness, capsys, CELL, trace=1)
    assert rc == 0 and line["correct"] is True
    got = {k: v["value"] for k, v in line["metrics"].items()}
    assert got["batches_per_call"] >= 4
    assert got["layout_hit_share"] == 1.0
    assert got["compiles_in_window"] == 0
    assert 0 < got["pad_share"] < 0.6


def test_untraced_run_reports_the_cells_metrics(cpu_harness, capsys,
                                                 tiny_ltr):
    rc, line = run_cell(cpu_harness, capsys, CELL, trace=0)
    assert rc == 0 and line["correct"] is True
    assert set(line["metrics"]) == {"runs_per_s", "setup_s"}
