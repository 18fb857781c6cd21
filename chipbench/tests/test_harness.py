"""The harness finds everything by name, refuses to run without a chip, and
prints the contract's result line."""

import json
import os
import re
import shutil
import subprocess
import sys

import pytest

from conftest import ROOT, run_cell

from chipbench import harness

SPEC = json.loads(open(os.path.join(ROOT, "BENCHMARK.json")).read())
CELLS = [w["name"] for w in SPEC["workloads"]]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


@pytest.mark.parametrize("cell", CELLS)
def test_cell_found_by_name(cell):
    c = harness.load_cell(cell)
    assert c.config["name"] == next(
        w["config"] for w in SPEC["workloads"] if w["name"] == cell)
    assert callable(harness.driver(c).run)
    assert callable(harness.generator(c.config).generate)
    for m in c.per_layer:
        assert callable(harness.reader(m["name"]))
    names = {m["name"] for m in c.end_to_end}
    assert "setup_s" in names and len(names) >= 2 and c.per_layer


def test_spec_keeps_the_contract():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert SPEC["paths"] == ["chipbench"]
    assert 1 <= SPEC["run_seconds"] <= 51
    e2e = {m["name"]: m for m in SPEC["end_to_end"]}
    assert e2e["setup_s"]["bound"] <= 0.25
    for m in SPEC["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25 and m["source"] in (
            "host_clock", "device_trace")
    for m in SPEC["per_layer"]:
        assert m["moves"] in e2e
        for w in m.get("workloads", CELLS):
            assert _applies(e2e[m["moves"]], w), (m["name"], w)
    for entry in SPEC["configs"] + SPEC["workloads"] + SPEC["end_to_end"] \
            + SPEC["per_layer"]:
        assert NAME.match(entry["name"]), entry["name"]
    for c in SPEC["configs"]:
        assert c["file"].startswith("chipbench/") and c["reduced"] == []
        assert os.path.isfile(os.path.join(ROOT, c["file"]))
    assert len({w["name"] for w in SPEC["workloads"]}) == len(CELLS)
    assert len({(w["config"], w["traffic"]) for w in SPEC["workloads"]}) \
        == len(CELLS)


def _applies(metric, cell):
    return "workloads" not in metric or cell in metric["workloads"]


def test_dummy_cell_config_traffic_and_metric_are_found(tmp_path):
    """A later change adds files and entries only; nothing is edited."""
    made = {
        "chipbench/configs/dummy_cfg.json": json.dumps(
            {"name": "dummy_cfg", "generator": "dummy_gen"}),
        "chipbench/generators/dummy_gen.py":
            "def generate(cfg, seed):\n    return seed\n",
        "chipbench/traffic/dummy_mix.json": json.dumps(
            {"driver": "dummy_drv"}),
        "chipbench/drivers/dummy_drv.py": "def run(ctx):\n    return 7\n",
        "chipbench/metrics/dummy_metric.x.py":
            "def read(r):\n    return r.calls * 2\n",
    }
    spec = json.loads(json.dumps(SPEC))
    spec["configs"].append({"name": "dummy_cfg", "source": "x",
                            "file": "chipbench/configs/dummy_cfg.json",
                            "reduced": [], "why": "x"})
    spec["workloads"].append({"name": "dummy_cfg.dummy_mix",
                              "config": "dummy_cfg", "traffic": "dummy_mix",
                              "chips": 1, "why": "x"})
    spec["per_layer"].append({"name": "dummy_metric.x", "unit": "ms",
                              "better": "lower", "source": "program_span",
                              "layer": "x", "moves": "setup_s",
                              "workloads": ["dummy_cfg.dummy_mix"]})
    spec_path = tmp_path / "BENCHMARK.json"
    spec_path.write_text(json.dumps(spec))
    try:
        for rel, text in made.items():
            with open(os.path.join(ROOT, rel), "x") as fh:
                fh.write(text)
        cell = harness.load_cell("dummy_cfg.dummy_mix", spec_path)
        assert harness.generator(cell.config).generate(cell.config, 5) == 5
        assert harness.driver(cell).run(None) == 7
        assert [m["name"] for m in cell.per_layer] == ["dummy_metric.x"]
        readings = harness.Readings(None, 21, {}, {})
        assert harness.reader("dummy_metric.x")(readings) == 42
    finally:
        for rel in made:
            path = os.path.join(ROOT, rel)
            if os.path.exists(path):
                os.remove(path)
        for mod in ("chipbench.generators.dummy_gen",
                    "chipbench.drivers.dummy_drv"):
            sys.modules.pop(mod, None)


def _run(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "-m", "chipbench", "--workload", "robust04.rescore",
         "--seed", "3", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_no_tpu_exits_nonzero_without_result():
    p = _run(ROOT)
    assert p.returncode != 0
    assert '"correct"' not in p.stdout
    assert "no TPU" in p.stderr


def test_without_the_program_exits_nonzero(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "chipbench"), tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path)
    assert p.returncode != 0
    assert '"correct"' not in p.stdout
    assert "program is missing" in p.stderr


def test_interpret_mode_is_refused(monkeypatch):
    from repro.kernels import ops

    class Dev:
        platform, device_kind = "tpu", "TPU v5 lite"

    monkeypatch.setattr("jax.devices", lambda *a: [Dev()])
    monkeypatch.setattr(ops, "interpret_mode", lambda: True)
    with pytest.raises(harness.BenchError, match="interpret"):
        harness.require_tpu(1)


def test_unknown_device_kind_is_an_error():
    with pytest.raises(harness.BenchError, match="no peaks"):
        harness.peaks("TPU v9 imaginary")
    assert harness.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9


@pytest.mark.parametrize("trace", [0, 1])
def test_last_line_has_the_contract_keys(cpu_harness, capsys, trace):
    rc, line = run_cell(cpu_harness, capsys, "robust04.rescore", trace=trace)
    assert rc == 0
    keys = ["correct", "attempted", "failed", "metrics", "device"]
    keys += ["breakdown"] if trace else []
    assert list(line) == keys + ["checks"]
    assert line["correct"] is True and line["attempted"] > 0
    assert set(line["device"]) >= {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    if trace:
        assert {"busy_s", "window_s"} <= set(line["device"])
    else:
        assert set(line["metrics"]) == {"runs_per_s", "eval_ms_p95",
                                        "setup_s"}
    assert set(line["checks"]) == {"max_abs_diff", "wrong_answers",
                                   "unanswered"}
    for c in line["checks"].values():
        assert set(c) == {"value", "limit"}
