"""The comparison that decides ``correct``: the reference is the
repository's pure_eval, the control fails it, and so does every fault a
cell can have in its timed path."""

import json
import os

import pytest

from conftest import ROOT, TINY, run_cell

from chipbench import check, control, reference
from chipbench.generators import adhoc, devset


def config(name):
    cfg = json.load(open(os.path.join(ROOT, "chipbench", "configs",
                                      f"{name}.json")))
    cfg.update(TINY[cfg["generator"]])
    return cfg


@pytest.mark.parametrize("name,gen", [("robust04", adhoc),
                                      ("msmarco-dev", devset)])
def test_reference_is_pure_eval(name, gen):
    from repro.baselines import pure_eval

    cfg = config(name)
    coll = gen.generate(cfg, 11)
    run = check.run_dict(coll.qids, coll.docnos, coll.scores)
    measures = cfg["reference_measures"]
    assert reference.evaluate(run, coll.qrel, measures) == \
        pure_eval.evaluate(run, coll.qrel, measures)


@pytest.mark.parametrize("name,gen", [("robust04", adhoc),
                                      ("msmarco-dev", devset)])
def test_control_is_not_correct(name, gen):
    cfg = config(name)
    coll = gen.generate(cfg, 12)
    r = control.reading(cfg, coll, [coll.scores])
    checks = r.checks(cfg["check"]["max_abs_diff"], 0)
    assert not check.passed(checks)
    assert r.max_abs_diff > 10 * cfg["check"]["max_abs_diff"]


def test_reading_counts_missing_extra_and_bad_values():
    want = {"a": {"m": 0.5}, "b": {"m": 0.25}}
    r = check.Reading()
    r.add({"a": {"m": 0.5}, "c": {"m": 1.0}}, want, ["m"])
    r.add({"a": {"m": float("nan")}, "b": {"m": 0.25, "x": 1.0}}, want, ["m"])
    assert r.wrong_answers == 4 and r.max_abs_diff == 0.0
    r.add({"a": {"m": 0.5 + 1e-3}, "b": {"m": 0.25}}, want, ["m"])
    assert r.max_abs_diff == pytest.approx(1e-3)


def test_reservoir_is_seeded():
    def keep(seed):
        r = check.Reservoir(3, seed)
        for i in range(100):
            r.offer(i, i)
        return sorted(k for k, _ in r.items)
    assert keep(2**31 + 5) == keep(2**31 + 5) != keep(1)


# -- faults in the timed path -------------------------------------------------


def stale(orig):
    """A step that returns its state unchanged: every call answers as the
    first call in the window did."""
    memo = []

    def f(self, *a, **k):
        res = orig(self, *a, **k)
        if len(memo) < 3:  # two warm-up calls, then the window's first
            memo.append(res)
        return memo[-1]
    return f


def half(orig):
    """Half of the batch left out: only every other query is answered."""
    def f(self, *a, **k):
        res = orig(self, *a, **k)
        return {q: v for i, (q, v) in enumerate(res.items()) if i % 2 == 0}
    return f


def altered(orig):
    """An answer altered where it is produced: one value of every call."""
    def f(self, *a, **k):
        res = orig(self, *a, **k)
        q = next(iter(res))
        key = sorted(res[q])[0]
        res[q][key] += 1e-3
        return res
    return f


def as_batched(fault):
    """The same fault applied to each answer of a coalesced call."""
    def wrap(orig):
        def f(self, bufs, *a, **k):
            results = orig(self, bufs, *a, **k)
            one = fault(lambda self, r: r)
            return [one(self, r) for r in results]
        return f
    return wrap


@pytest.mark.parametrize("fault", [stale, half, altered])
@pytest.mark.parametrize("workload", ["robust04.rescore",
                                      "msmarco-dev.rescore"])
def test_library_fault_is_not_correct(cpu_harness, capsys, monkeypatch,
                                      workload, fault):
    from repro.core.evaluator import RelevanceEvaluator

    monkeypatch.setattr(RelevanceEvaluator, "evaluate_buffer",
                        fault(RelevanceEvaluator.evaluate_buffer))
    rc, line = run_cell(cpu_harness, capsys, workload)
    assert rc == 0 and line["correct"] is False, line["checks"]


@pytest.mark.parametrize("fault", [half, altered])
def test_served_fault_is_not_correct(cpu_harness, capsys, monkeypatch,
                                     fault):
    from repro.core.evaluator import RelevanceEvaluator

    monkeypatch.setattr(RelevanceEvaluator, "evaluate_buffers",
                        as_batched(fault)(RelevanceEvaluator.evaluate_buffers))
    rc, line = run_cell(cpu_harness, capsys, "robust04.served", seconds=2.0)
    assert rc == 0 and line["correct"] is False, line["checks"]


def test_served_stale_answer_is_not_correct(cpu_harness, capsys,
                                            monkeypatch):
    """The service answers every request with its first answer."""
    from repro.core.evaluator import RelevanceEvaluator

    orig = RelevanceEvaluator.evaluate_buffers
    memo = []

    def first(self, bufs, *a, **k):
        results = orig(self, bufs, *a, **k)
        memo.extend(results[:1])
        return [memo[0]] * len(results)
    monkeypatch.setattr(RelevanceEvaluator, "evaluate_buffers", first)
    rc, line = run_cell(cpu_harness, capsys, "robust04.served", seconds=2.0)
    assert rc == 0 and line["correct"] is False, line["checks"]


@pytest.mark.parametrize("workload", ["robust04.rescore",
                                      "msmarco-dev.rescore",
                                      "robust04.served"])
def test_sound_run_is_correct(cpu_harness, capsys, workload):
    rc, line = run_cell(cpu_harness, capsys, workload, seconds=1.5)
    assert rc == 0 and line["correct"] is True, line["checks"]
    assert line["checks"]["max_abs_diff"]["value"] < 1e-6
