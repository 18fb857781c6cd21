"""Sharded segment: evaluation throughput scaling vs. device count.

One process, one mesh per device count: the first 1, 2, 4 and 8 of
``jax.devices()`` (counts above the visible devices are skipped).  Each
mesh evaluates the same synthesized collection (paper §3 protocol),
tokenized once, and times the steady-state sharded step —
``ShardedEvaluator.evaluate_buffer`` on the cached ``RunBuffer``: the
evaluator's depth classes, each class's packed measure core under
``shard_map`` over the mesh, one host copy per class.
``speedup_vs_1dev`` is the wall-clock ratio against the 1-device mesh.

On a TPU host the devices are the chips.  On a CPU host there is one device
unless the process starts with
``XLA_FLAGS=--xla_force_host_platform_device_count=8``; such "devices" are
threads sharing one machine, so the curve is a plumbing/overhead check,
not a hardware claim.
"""

from __future__ import annotations

from typing import Dict, List

DEVICE_COUNTS = (1, 2, 4, 8)


def run(full: bool = False) -> List[Dict]:
    import jax
    from jax.sharding import Mesh

    from benchmarks.common import time_call
    from repro.core import RelevanceEvaluator
    from repro.data.synthetic_ir import synthesize_run
    from repro.distributed import ShardedEvaluator

    n_queries, n_docs = (2048, 1000) if full else (512, 256)
    reps = 10 if full else 3
    run_, qrel = synthesize_run(n_queries, n_docs)
    ev = RelevanceEvaluator(qrel, ("map", "ndcg", "recip_rank", "P"))
    buf = ev.tokenize_run(run_)
    devices = jax.devices()
    rows: List[Dict] = []
    base_us = None
    for n in DEVICE_COUNTS:
        if n > len(devices):
            print(f"sharded devices={n}: skipped ({len(devices)} visible)")
            continue
        sev = ShardedEvaluator(ev, mesh=Mesh(devices[:n], ("data",)))
        us = time_call(lambda: sev.evaluate_buffer(buf), reps=reps) * 1e6
        if n == 1:
            base_us = us
        row = {"devices": n, "sharded_us": us, "n_queries": n_queries,
               "n_docs": n_docs, "platform": devices[0].platform,
               "device_kind": devices[0].device_kind,
               "speedup_vs_1dev": base_us / us}
        rows.append(row)
        print(f"sharded devices={n}: {us:.0f}us "
              f"({row['speedup_vs_1dev']:.2f}x vs 1 device, "
              f"{row['platform']})")
    return rows
