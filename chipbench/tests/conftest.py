"""Harness tests at tiny sizes on the CPU.  Run by explicit path:

    python -m pytest -q chipbench/tests
"""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

#: tiny shapes of each generator, for runs on the CPU
TINY = {
    "adhoc": dict(topics=12, topic_ranges=[[301, 312]], skip_topics=[],
                  depth=120, judged_per_topic=60),
    "devset": dict(queries=48, depth=600),
}


#: the served driver's traffic, for a cell that BENCHMARK.json does not
#: hold yet (its rate awaits a sweep against the latency limit)
SERVED = {"driver": "served", "rate_per_s": 10.0,
          "latency_limit_ms_p95": 500, "warm_requests": 2,
          "fresh_offsets": 8192, "score_spread": 1.5}


def tiny(cell):
    cell.config.update(TINY[cell.config["generator"]])
    return cell


@pytest.fixture
def cpu_harness(monkeypatch, tmp_path):
    """The harness with its look for a chip skipped, tiny configurations,
    a stand-in peaks table and the compile cache under ``tmp_path``."""
    from chipbench import harness

    load = harness.load_cell

    def load_tiny(name, *a, **k):
        if name == "robust04.served":
            config = load("robust04.rescore").config
            metrics = [{"name": n, "unit": u} for n, u in (
                ("latency_ms_p50", "ms"), ("latency_ms_p95", "ms"),
                ("setup_s", "s"))]
            return tiny(harness.Cell(name, 1, config, dict(SERVED),
                                     metrics, []))
        return tiny(load(name, *a, **k))

    monkeypatch.setattr(harness, "load_cell", load_tiny)
    monkeypatch.setattr(harness, "peaks", lambda kind: {
        "hbm_bytes_per_s": 1e11, "bf16_flops_per_s": 1e12})
    monkeypatch.setattr(harness, "CACHE_DIR", tmp_path / "jax_cache")
    # configure_jax() sets these; monkeypatch puts the old values back
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jax_cache"))
    monkeypatch.setenv("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
    return harness


def cpu_devices(chips):
    import jax

    return jax.devices()[:chips]


def run_cell(harness, capsys, workload, seed=2**31 + 7, seconds=1.0, trace=0):
    """One run in this process; returns (exit code, result line or None)."""
    rc = harness.main(["--workload", workload, "--seed", str(seed),
                       "--seconds", str(seconds), "--trace", str(trace)],
                      device_check=cpu_devices)
    lines = capsys.readouterr().out.strip().splitlines()
    last = json.loads(lines[-1]) if rc == 0 else None
    return rc, last
