"""Run one cell once, from the checkout's root:

    python3 -m chipbench --workload <cell> --seed <n> --seconds <s> --trace <0|1>

(see ``chipbench/harness.py``)."""

import sys
import time

T0 = time.monotonic()  # set-up is timed from here

from chipbench import harness  # noqa: E402

sys.exit(harness.main(sys.argv[1:], t0=T0))
