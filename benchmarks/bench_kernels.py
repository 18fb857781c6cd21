"""Kernel-layer segment: fused-kernel call time + compile accounting.

Two questions, answered per shape:

1. **What does one fused-measures call take on the host's clock?**
   The kernel reads the two ``[Q, D]`` tiles plus a ``[Q, 16]`` scalar
   block once and writes ``[Q, 64]``; each row gives the wall time per call
   and the bytes per second that implies.  The kernel runs in the
   backend-resolved execution mode (``ops.interpret_mode()``: compiled on
   TPU, interpret elsewhere), reported with every row.  A wall time around
   interpret mode says nothing about the chip: the kernel's share of its
   roofline comes from a device trace (``chipbench``), not from here.

2. **Is the compiled-signature set actually closed?**  A sweep over many
   distinct raw batch sizes is pushed through power-of-two bucketing
   (``repro.kernels.bucketing``) and the trace-time compile counters are
   read back: the retrace count must stay at the number of *buckets*, not
   the number of raw sizes.  This is the same accounting the serve layer's
   recompile-bound test asserts; here it is reported as data.
"""

from __future__ import annotations

import time
from typing import Dict, List

import numpy as np

import jax
import jax.numpy as jnp

from repro.analysis import roofline
from repro.core import measures as M
from repro.kernels import autotune, bucketing, ops
from repro.kernels.fused_measures import OUT_WIDTH

from benchmarks.common import time_call

#: (Q, D) shapes for the roofline rows — small enough for interpret mode on
#: CPU hosts, large enough that the [Q, D] streams dominate the footprint.
SHAPES = ((256, 256), (512, 1024))
SHAPES_FULL = ((256, 256), (512, 1024), (1024, 1024), (1024, 4096))


def _fused_bytes(q: int, d: int) -> int:
    """HBM traffic of one fused_measures call (f32 in and out)."""
    return 4 * (2 * q * d + q * 16 + q * OUT_WIDTH)


def _roofline_rows(shapes, reps: int) -> List[Dict]:
    rng = np.random.default_rng(0)
    rows = []
    for q, d in shapes:
        rel = jnp.asarray((rng.random((q, d)) < 0.1).astype(np.float32))
        judged = jnp.ones((q, d), jnp.float32)
        n_rel = jnp.sum(rel, axis=-1)
        scal = ops.make_scalars(n_rel, jnp.sum(judged, -1) - n_rel, rel)
        scal = jax.block_until_ready(scal)
        block_q = autotune.block_q_for(q, d)
        traces0 = bucketing.compile_count("fused_measures")
        t = time_call(
            lambda: jax.block_until_ready(
                ops.fused_measures_cols(rel, judged, scal)),
            reps=reps)
        rl = roofline.kernel_roofline(_fused_bytes(q, d), t)
        rows.append({
            "segment": "fused_roofline", "n_queries": q, "n_docs": d,
            "block_q": block_q, "interpret": ops.interpret_mode(),
            "us_per_call": t * 1e6,
            "achieved_bytes_per_s": rl["achieved_bytes_per_s"],
            "peak_bytes_per_s": rl["peak_bytes_per_s"],
            "new_compiles": bucketing.compile_count("fused_measures")
            - traces0,
        })
        mode = "interp" if ops.interpret_mode() else "compiled"
        print(f"fused[{mode}] q={q} d={d} block_q={block_q}: "
              f"{t*1e3:.1f}ms  {rl['achieved_bytes_per_s']/1e9:.3f} GB/s")
    return rows


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
        jax.block_until_ready(M.compute_measures_jit(batch, parsed))
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
    reps = 10 if full else 3
    shapes = SHAPES_FULL if full else SHAPES
    rows = _roofline_rows(shapes, reps)
    rows.append(_bucketing_row(128 if full else 64))
    return rows
