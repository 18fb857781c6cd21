"""Blocked top-K Pallas TPU kernel (trec_eval's ranking sort, TPU-native).

The paper's measured hot spot is trec_eval's per-query qsort of the ranking.
On TPU, cutoff measures (P@k / ndcg_cut@k / ... with k ≤ 1000) never need the
full sort: this kernel streams the document axis through VMEM in blocks,
keeping a running top-K candidate buffer, so a 1M-candidate ranking
(``retrieval_cand``) costs one HBM read of the scores and O(D·log²B) VPU work
instead of an O(D log D) global sort with multiple HBM round trips.

Per (8-query, doc-block) grid step:
  1. bitonic-sort the ``[8, block_d]`` VMEM block worst-first (carrying
     global doc indices for trec_eval tie-breaking: equal scores → smaller
     index wins);
  2. merge its top-K with the running top-K scratch buffer (one lane-wise
     compare of the best-first buffer with the worst-first block top-K,
     then a bitonic merge);
  3. on the last block, write the scratch buffer out.

Layout notes (TPU target): a block is one sublane tile of 8 query rows (the
query axis is padded with -inf rows) by a multiple of 128 lanes; the
compare-exchange stages are lane rotations + selects only (no gathers, no
lane reshapes).  Correctness is checked against ``jax.lax.top_k`` (same tie
semantics) in interpret mode and compiled on the chip.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import bitonic, bucketing

NEG_INF = float("-inf")
#: index of the running buffer's initial fill; every real column beats it
INDEX_SENTINEL = 2**31 - 1
#: query rows per grid step: one f32 sublane tile (Mosaic's minimum block)
ROWS = 8


def _topk_kernel(scores_ref, out_v_ref, out_i_ref, v_scr, i_scr, *, k, block_d,
                 n_dblocks):
    db = pl.program_id(1)
    v = scores_ref[...]
    idx = jax.lax.broadcasted_iota(jnp.int32, v.shape, 1) + db * block_d
    # Worst-first sort: the block's top k lands in the last k lanes,
    # worst-first — the mirror image of the best-first running buffer.
    sv, si = bitonic.sort(v, idx, reverse=True)
    bv, bi = sv[:, block_d - k:], si[:, block_d - k:]

    @pl.when(db == 0)
    def _init():
        v_scr[...] = jnp.full(v_scr.shape, NEG_INF, v_scr.dtype)
        i_scr[...] = jnp.full(i_scr.shape, INDEX_SENTINEL, jnp.int32)

    # best-first ++ worst-first is bitonic: one lane-wise compare keeps the
    # top k of the union (still bitonic), and a merge sorts it.
    cv, ci = v_scr[...], i_scr[...]
    take = bitonic.precedes(bv, bi, cv, ci)
    mv, mi = bitonic.merge(jnp.where(take, bv, cv), jnp.where(take, bi, ci))
    v_scr[...] = mv
    i_scr[...] = mi

    @pl.when(db == n_dblocks - 1)
    def _emit():
        out_v_ref[...] = v_scr[...]
        out_i_ref[...] = i_scr[...]


def _next_pow2(n: int, minimum: int = 1) -> int:
    b = minimum
    while b < n:
        b *= 2
    return b


@functools.partial(jax.jit, static_argnames=("k", "block_d", "interpret"))
def topk(scores: jax.Array, k: int, block_d: int | None = None,
         interpret: bool = True):
    """Top-k (values, indices) per row of ``scores`` [Q, D], precedes order.

    Ties: smaller index first (trec_eval with index tiebreak).  Rows shorter
    than k are padded with -inf values / out-of-range indices.
    """
    bucketing.record_trace("topk")  # trace-time: one per compiled signature
    q, d = scores.shape
    k2 = _next_pow2(k, 128)  # lane-aligned candidate buffer
    if block_d is None:
        block_d = max(2 * k2, 512)
    block_d = _next_pow2(block_d)
    if block_d < k2:
        raise ValueError("block_d must be >= padded k")
    q_pad = ((q + ROWS - 1) // ROWS) * ROWS
    d_pad = ((d + block_d - 1) // block_d) * block_d
    if (q_pad, d_pad) != (q, d):
        scores = jnp.pad(scores, ((0, q_pad - q), (0, d_pad - d)),
                         constant_values=NEG_INF)
    n_dblocks = d_pad // block_d

    kern = functools.partial(_topk_kernel, k=k2, block_d=block_d,
                             n_dblocks=n_dblocks)
    out_v, out_i = pl.pallas_call(
        kern,
        grid=(q_pad // ROWS, n_dblocks),
        in_specs=[pl.BlockSpec((ROWS, block_d), lambda qi, di: (qi, di))],
        out_specs=[
            pl.BlockSpec((ROWS, k2), lambda qi, di: (qi, 0)),
            pl.BlockSpec((ROWS, k2), lambda qi, di: (qi, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((q_pad, k2), scores.dtype),
            jax.ShapeDtypeStruct((q_pad, k2), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM((ROWS, k2), scores.dtype),
            pltpu.VMEM((ROWS, k2), jnp.int32),
        ],
        interpret=interpret,
    )(scores)
    return out_v[:q, :k], out_i[:q, :k]
