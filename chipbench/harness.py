"""Run one benchmark cell once and print its result line.

    python3 -m chipbench --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything about a cell is found by name, from ``BENCHMARK.json`` at the
checkout's root:

* the cell's ``config`` names an entry of ``configs``, whose ``file`` holds
  the collection's shape, measures and check; its ``generator`` key names
  ``chipbench/generators/<generator>.py``;
* the cell's ``traffic`` names ``chipbench/traffic/<traffic>.json``, whose
  ``driver`` key names ``chipbench/drivers/<driver>.py``;
* each per-layer metric ``<name>`` is read by ``chipbench/metrics/<name>.py``.

A run makes its collection from ``--seed``, warms up, measures for
``--seconds``, compares the window's answers with the plain reference
(``chipbench/reference.py``) and prints, as the last line of standard
output, one JSON object: ``correct``, ``attempted``, ``failed``,
``metrics`` (end-to-end with ``--trace 0``, per-layer with ``--trace 1``),
``device`` and, last, ``checks``: each number compared with its limit.
The same numbers close standard error.  Without a TPU, with fewer chips
than the cell asks for, or with the Pallas kernels in interpret mode, it
exits non-zero and prints no result.
"""

from __future__ import annotations

import argparse
import dataclasses
import importlib
import importlib.util
import json
import os
import sys
import time
import traceback
from pathlib import Path
from typing import Callable, Dict, List, Optional, Tuple

ROOT = Path(__file__).resolve().parents[1]
HERE = ROOT / "chipbench"
SPEC_PATH = ROOT / "BENCHMARK.json"
#: JAX's persistent compilation cache: a fixed path inside the checkout
CACHE_DIR = ROOT / ".jax_cache"


class BenchError(Exception):
    """The cell cannot be run as asked: no such cell, no chip, no program."""


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: List[dict]
    per_layer: List[dict]


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, spec_path: Path = SPEC_PATH) -> Cell:
    """The cell ``name`` of ``spec_path``, with its configuration, traffic
    mix and the metrics it reports."""
    spec = json.loads(Path(spec_path).read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise BenchError(f"no workload {name!r} in {spec_path.name}; "
                         f"there are {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in spec["configs"]}
    config = json.loads((ROOT / configs[w["config"]]["file"]).read_text())
    traffic = json.loads((HERE / "traffic" / f"{w['traffic']}.json")
                         .read_text())
    return Cell(name, int(w["chips"]), config, traffic,
                [m for m in spec["end_to_end"] if _applies(m, name)],
                [m for m in spec["per_layer"] if _applies(m, name)])


def driver(cell: Cell):
    return importlib.import_module(f"chipbench.drivers.{cell.traffic['driver']}")


def generator(config: dict):
    return importlib.import_module(f"chipbench.generators.{config['generator']}")


def reader(metric: str) -> Callable:
    """``read(readings)`` of ``chipbench/metrics/<metric>.py``."""
    return metric_module(metric).read


def metric_module(metric: str):
    path = HERE / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(
        f"chipbench.metrics.{metric.replace('.', '_')}", path)
    if spec is None or not path.is_file():
        raise BenchError(f"no reader for metric {metric!r} at {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def peaks(device_kind: str) -> dict:
    table = json.loads((HERE / "peaks.json").read_text())
    if device_kind not in table:
        raise BenchError(f"no peaks for device kind {device_kind!r} in "
                         f"peaks.json (known: {sorted(table)})")
    return table[device_kind]


def configure_jax() -> None:
    """Compile cache inside the checkout, every program kept.  Set in the
    environment before JAX is imported, so child processes (cluster
    workers) inherit it."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"


def import_program() -> None:
    """The system under test is the checkout's own ``src/repro``."""
    src = ROOT / "src"
    if not (src / "repro" / "__init__.py").is_file():
        raise BenchError(f"the program is missing: no {src}/repro")
    sys.path.insert(0, str(src))
    import repro

    if Path(repro.__file__).resolve().parents[1] != src.resolve():
        raise BenchError(f"repro was imported from {repro.__file__}, "
                         f"not from {src}")
    from repro import runtime

    runtime.enable_compile_cache()


def require_tpu(chips: int):
    """The TPU devices, or an error: no fallback to the CPU or to Pallas's
    interpret mode."""
    import jax

    from repro.kernels import ops

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise BenchError(f"no TPU: JAX runs on {devices[0].platform}")
    if len(devices) < chips:
        raise BenchError(f"the cell needs {chips} TPU chips, JAX sees "
                         f"{len(devices)}")
    if ops.interpret_mode():
        raise BenchError("Pallas kernels resolved to interpret mode on a TPU")
    return devices[:chips]


@dataclasses.dataclass
class Context:
    """What a driver gets: the cell, the run's arguments and its devices."""

    cell: Cell
    seed: int
    seconds: float
    trace: bool
    t0: float  # monotonic time at process start
    devices: list  # the chips this process holds

    def collection(self):
        cfg = self.cell.config
        return generator(cfg).generate(cfg, self.seed)

    def note(self, **fields) -> None:
        """One line of the run's own numbers, before the result line."""
        print(f"[{self.cell.name}] " + " ".join(
            f"{k}={v}" for k, v in fields.items()), flush=True)


@dataclasses.dataclass
class Outcome:
    """What a driver hands back."""

    attempted: int
    failed: int
    end_to_end: Dict[str, float]
    checks: Dict[str, Tuple[float, float]]
    memory_peak_bytes: int
    reduced: object = None  # trace.Reduced of the traced window
    calls_traced: int = 0  # evaluations inside the traced window
    counters: Dict[str, float] = dataclasses.field(default_factory=dict)


@dataclasses.dataclass
class Readings:
    """What a per-layer metric's reader gets."""

    trace: object  # trace.Reduced, or None
    calls: int
    counters: Dict[str, float]
    peaks: dict


def device_peak_bytes(devices) -> int:
    peaks_seen = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                  for d in devices]
    return int(max(peaks_seen, default=0))


def result_line(cell: Cell, out: Outcome, devices_info: dict, trace: bool,
                peak_table: dict) -> dict:
    from chipbench import check
    from chipbench import trace as tr

    metrics = {}
    if trace:
        readings = Readings(out.reduced, out.calls_traced, out.counters,
                            peak_table)
        for m in cell.per_layer:
            value = reader(m["name"])(readings)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        for m in cell.end_to_end:
            if m["name"] not in out.end_to_end:
                raise BenchError(f"the driver reported no {m['name']!r}")
            metrics[m["name"]] = {"value": out.end_to_end[m["name"]],
                                  "unit": m["unit"]}
    device = dict(devices_info, memory_peak_bytes=out.memory_peak_bytes)
    line = {"correct": check.passed(out.checks), "attempted": out.attempted,
            "failed": out.failed, "metrics": metrics, "device": device}
    if trace and out.reduced is not None:
        device["busy_s"] = out.reduced.mean_busy_s
        device["window_s"] = out.reduced.window_s
        line["breakdown"] = tr.breakdown(out.reduced)
    line["checks"] = {k: {"value": v, "limit": lim}
                      for k, (v, lim) in out.checks.items()}
    return line


def parse_args(argv):
    ap = argparse.ArgumentParser(prog="chipbench",
                                 description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True,
                    help="a cell's name in BENCHMARK.json")
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True,
                    help="length of the measured window")
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0,
                    help="1: trace the window and report per-layer metrics")
    return ap.parse_args(argv)


def main(argv=None, t0: Optional[float] = None,
         device_check: Callable = require_tpu) -> int:
    """Run one cell once; 0 when a result line was printed.

    ``device_check(chips)`` returns the devices the run may use; tests pass
    one that accepts the CPU."""
    t0 = time.monotonic() if t0 is None else t0
    args = parse_args(argv)
    try:
        cell = load_cell(args.workload)
        configure_jax()
        import_program()
        drv = driver(cell)
        devices = device_check(cell.chips)
        info = {"platform": devices[0].platform,
                "kind": devices[0].device_kind, "count": len(devices)}
        peak_table = peaks(info["kind"])
        ctx = Context(cell, args.seed, args.seconds, bool(args.trace), t0,
                      devices)
        out = drv.run(ctx)
        line = result_line(cell, out, info, ctx.trace, peak_table)
    except BenchError as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 2
    except Exception:  # noqa: BLE001 — any failure is a run without result
        traceback.print_exc()
        return 1
    print(json.dumps(line), flush=True)
    for name, c in line["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    return 0
