"""Beyond-paper: batched device evaluation vs the per-query pattern.

pytrec_eval still walks queries in a Python loop (one C call per query dict).
The device-resident engine evaluates the whole [Q, D] tensor in one compiled
call.  This benchmark quantifies that headroom on the paper's largest grid,
on whatever backend runs it (the rows carry no device label, so a CPU run
says nothing about the chip).
"""

from __future__ import annotations

from typing import Dict, List

import numpy as np

import jax
import jax.numpy as jnp

from repro.core import RelevanceEvaluator, measures as M
from repro.data.synthetic_ir import synthesize_run

from benchmarks.common import time_call

MEASURES = ("map", "ndcg", "ndcg_cut", "P", "recall", "recip_rank")


def run(full: bool = False) -> List[Dict]:
    reps = 10 if full else 3
    nq, nd = (10_000, 1000) if full else (2000, 500)
    run_dict, qrel = synthesize_run(nq, nd)
    parsed = M.parse_measures(MEASURES)

    # 1. pytrec_eval pattern: dict API, one batch per call but per-query
    #    Python loop for densify + dict assembly.
    ev = RelevanceEvaluator(qrel, MEASURES)
    t_dict = time_call(lambda: ev.evaluate(run_dict), reps=reps)

    # 2. device-resident: dense tensors stay on device, one compiled call.
    rng = np.random.default_rng(0)
    scores = jnp.asarray(rng.standard_normal((nq, nd)).astype(np.float32))
    rel = jnp.asarray((rng.random((nq, nd)) < 0.1).astype(np.float32))
    batch = M.batch_from_dense(scores, rel)
    compute = jax.jit(lambda b: M.compute_measures(b, parsed))
    t_dense = time_call(
        lambda: jax.block_until_ready(compute(batch)), reps=reps)

    rows = [{
        "n_queries": nq, "n_docs": nd,
        "dict_api_us": t_dict * 1e6,
        "dense_batched_us": t_dense * 1e6,
        "dense_speedup_vs_dict": t_dict / t_dense,
        "queries_per_s_dense": nq / t_dense,
    }]
    print(f"batched q={nq} d={nd}: dict={t_dict*1e3:.0f}ms "
          f"dense={t_dense*1e3:.0f}ms (x{t_dict/t_dense:.1f})")
    return rows
