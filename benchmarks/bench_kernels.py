"""Kernel-layer segment: compile accounting of the packed measure core.

**Is the compiled-signature set actually closed?**  A sweep over many
distinct raw batch sizes is pushed through power-of-two bucketing
(``repro.kernels.bucketing``) into the packed measure core the evaluator
launches (``compute_measures_packed_jit``), and the trace-time compile
counters are read back: the retrace count must stay at the number of
*buckets*, not the number of raw sizes.  This is the same accounting the
serve layer's recompile-bound test asserts; here it is reported as data.
The kernels' times and roofline shares come from a device trace
(``chipbench``), not from here.
"""

from __future__ import annotations

import time
from typing import Dict, List

import numpy as np

import jax
import jax.numpy as jnp

from repro.core import measures as M
from repro.kernels import bucketing


def _bucketing_row(max_batch: int = 64) -> Dict:
    """Sweep distinct raw wave sizes; count retraces of the measure core.

    Uses a one-off measure tuple as the static jit key so the deltas are
    not absorbed by signatures other segments already compiled.
    """
    parsed = M.parse_measures(("recall_30", "success_5"))
    rng = np.random.default_rng(1)
    waves = sorted({max(1, (max_batch * k) // 9) for k in range(1, 10)}
                   | {1, max_batch})
    before = bucketing.compile_count("measure_core")
    t0 = time.perf_counter()
    for nq in waves:
        nq_pad = bucketing.bucket_queries(nq)
        scores = rng.standard_normal((nq, 32)).astype(np.float32)
        rel = (rng.random((nq, 32)) < 0.2).astype(np.float32)
        if nq_pad != nq:
            pad = ((0, nq_pad - nq), (0, 0))
            scores, rel = np.pad(scores, pad), np.pad(rel, pad)
        qmask = jnp.asarray(np.arange(nq_pad) < nq)
        batch = M.batch_from_dense(jnp.asarray(scores), jnp.asarray(rel),
                                   query_mask=qmask)
        jax.block_until_ready(M.compute_measures_packed_jit(batch, parsed))
    elapsed = time.perf_counter() - t0
    compiles = bucketing.compile_count("measure_core") - before
    bound = bucketing.max_signatures(max_batch)
    print(f"bucketing: {len(waves)} distinct wave sizes (1..{max_batch}) -> "
          f"{compiles} compiles (closed-set bound {bound}) "
          f"in {elapsed*1e3:.0f}ms")
    return {
        "segment": "bucketing_sweep", "distinct_wave_sizes": len(waves),
        "max_batch": max_batch, "compiles": compiles,
        "signature_bound": bound, "elapsed_s": elapsed,
        "trace_counts": bucketing.trace_counts(),
    }


def run(full: bool = False) -> List[Dict]:
    return [_bucketing_row(128 if full else 64)]
