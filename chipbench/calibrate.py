"""Readings that the comparison's limits are set from (run on the chip).

    python3 chipbench/calibrate.py --workload <cell> --seconds <s> \\
        --seeds <n> [<n> ...] --control-seeds <n> [<n> ...]

For each of ``--seeds`` it runs the cell once in this process, with a
window of ``--seconds`` at the cell's own load, and prints the program's
readings (``side: program``).  For each of ``--control-seeds`` it puts the
control (``chipbench/control.py``) in the program's place on the same
collection and the same kind of fresh scores, as many answers as a run
compares, and prints its readings (``side: control``).  The lower reading
of a number is the largest the program gives; the upper, the smallest the
control gives.  The benchmark's own runs never run this.
"""

import argparse
import json
import os
import sys
import time

T0 = time.monotonic()
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from chipbench import control, harness  # noqa: E402
from chipbench.drivers.library import ScoreStream  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="*", default=[])
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    args = ap.parse_args(argv)
    cell = harness.load_cell(args.workload)
    harness.configure_jax()
    harness.import_program()
    devices = harness.require_tpu(cell.chips)
    drv = harness.driver(cell)
    for seed in args.seeds:
        ctx = harness.Context(cell, seed, args.seconds, False,
                              time.monotonic(), devices)
        out = drv.run(ctx)
        print(json.dumps({"seed": seed, "side": "program",
                          "attempted": out.attempted,
                          **{k: v for k, (v, _) in out.checks.items()}}),
              flush=True)
    cfg = cell.config
    for seed in args.control_seeds:
        t = time.monotonic()
        coll = harness.Context(cell, seed, 0, False, 0, []).collection()
        stream = ScoreStream(coll.scores, cfg["score_grid"], seed,
                             cell.traffic["fresh_offsets"], 2)
        r = control.reading(cfg, coll, [stream.scores(i) for i in
                                        range(cfg["check"]["answers"])])
        print(json.dumps({"seed": seed, "side": "control",
                          "max_abs_diff": r.max_abs_diff,
                          "wrong_answers": r.wrong_answers,
                          "compared_values": r.compared_values,
                          "where": r.where,
                          "seconds": time.monotonic() - t}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
