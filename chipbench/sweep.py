"""Rate sweep of a served cell: the highest rate it sustains (run on the chip).

    python3 chipbench/sweep.py --workload <cell> --seed <n> --seconds <s> \\
        --rates <r> [<r> ...]

Sets the cell up once and runs one open-loop window per rate, lowest
first, printing per rate the latency median and 95th percentile, the
generator's lateness, the longest stalls of both event loops and how long
after the window's close the last answer came (a backlog that grows
through the window shows there).  A rate holds when no request failed,
the 95th percentile is within the traffic file's
``latency_limit_ms_p95`` and the last answer came within a quarter second
of the close.  The last line gives the highest rate that holds and four
fifths of it, the rate the traffic file then fixes.  The benchmark's own
runs never run this.
"""

import argparse
import asyncio
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from chipbench import harness  # noqa: E402
from chipbench.drivers import served  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args(argv)
    cell = harness.load_cell(args.workload)
    harness.configure_jax()
    harness.import_program()
    ctx = harness.Context(cell, args.seed, args.seconds, False,
                          time.monotonic(), harness.require_tpu(cell.chips))
    windows = [{"rate": r, "seconds": args.seconds, "seed": args.seed + k,
                "check": False, "trace": False}
               for k, r in enumerate(sorted(args.rates))]
    limit = cell.traffic["latency_limit_ms_p95"]
    held = []
    for rec in asyncio.run(served.session(ctx, windows)):
        done = rec["done"]
        holds = (done["failed"] == 0 and done["latency_ms_p95"] <= limit
                 and done["drained_s_after_close"] < 0.25)
        if holds:
            held.append(rec["rate"])
        print(json.dumps({"rate_per_s": rec["rate"], "holds": holds,
                          "coalesce_factor": rec["requests"]
                          / max(rec["backend_calls"], 1),
                          **{k: v for k, v in rec.items()
                             if k.startswith("server_")},
                          **{k: v for k, v in done.items()
                             if k != "event"}}), flush=True)
    top = max(held, default=None)
    print(json.dumps({"latency_limit_ms_p95": limit, "highest_holding": top,
                      "four_fifths": None if top is None else 0.8 * top}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
