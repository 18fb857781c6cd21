"""Fused one-pass measure kernel (pytrec_eval's C loop, TPU-native).

trec_eval computes every requested measure in a single walk over the sorted
ranking.  A naive JAX translation materializes a separate [Q, D] intermediate
per measure family (cumsum for AP, another for DCG, another for bpref, ...) —
each one an HBM round trip.  This kernel keeps a [block_q, D] tile of the
rank-sorted relevance in VMEM and computes *all* measures in one visit:
cumulative sums are log2(D) shifted adds in VMEM, cutoff reads are static
slices, and only a [block_q, 64] measure block leaves the core.

Inputs (already rank-sorted by score desc / tiebreak asc — see core.sorting
or the top-K kernel):
  rel      [Q, D] f32 — judgment of doc at each rank (0 unjudged/padding)
  judged   [Q, D] f32 — 1.0 where the doc is judged
  scalars  [Q, 16] f32 — col 0: R (n_rel), 1: judged-nonrel count,
           2: full-ranking ideal DCG, 3..11: ideal DCG at the 9 cutoffs.
The wrapper adds two [1, D] rank tables built on the host
(``core.measures.discount_table`` / ``rbp_table``), the same ones the
reference engine uses.

Output: [Q, 64] f32, columns per :data:`COLUMNS`.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import measures as M
from repro.core.registry import DEFAULT_RBP_P
from repro.kernels import autotune, bucketing

CUTOFFS = (5, 10, 15, 20, 30, 100, 200, 500, 1000)
SUCCESS_CUTOFFS = (1, 5, 10)

COLUMNS = (
    ["map", "recip_rank", "ndcg", "bpref", "num_rel_ret", "Rprec"]
    + [f"P_{k}" for k in CUTOFFS]
    + [f"recall_{k}" for k in CUTOFFS]
    + [f"ndcg_cut_{k}" for k in CUTOFFS]
    + [f"map_cut_{k}" for k in CUTOFFS]
    + [f"success_{k}" for k in SUCCESS_CUTOFFS]
    + [f"judged_{k}" for k in CUTOFFS]
    + [f"rbp_{DEFAULT_RBP_P:.2f}"]
)
OUT_WIDTH = 64  # lane-padded; len(COLUMNS) == 55


def _sdiv(num, den):
    """Guarded division, bit-identical to ``core.measures._safe_div``.

    The kernel used to multiply by a precomputed reciprocal (``* inv_r``),
    which is one multiply cheaper but rounds differently from the reference
    engine's division (e.g. ``1.5 / 3 == 0.5`` exactly, while
    ``1.5 * float32(1/3)`` is ``0.50000001``).  The sharded evaluation path
    promises results bit-identical to ``RelevanceEvaluator.evaluate``, so the
    kernel divides exactly as ``core.measures`` does.
    """
    return jnp.where(den > 0, num / jnp.maximum(den, 1e-30), 0.0)


def _cumsum_lanes(x, lane):
    """Inclusive cumsum along the last axis via log2(D) shifted adds.

    Each shift is a lane rotation with the wrapped-around lanes zeroed
    (static shapes, no gather, no unaligned slice) — a full-tile VPU add,
    so the whole scan stays in VMEM.
    """
    n = x.shape[-1]
    sh = 1
    while sh < n:
        x = x + jnp.where(lane >= sh, pltpu.roll(x, sh, 1), 0.0)
        sh *= 2
    return x


def _lane(x, lane, k: int):
    """Column ``k`` of ``x`` as ``[rows, 1]``.

    A masked lane reduction over one nonzero term, hence exact; Mosaic does
    not lower single-lane indexing (``dynamic_slice``).
    """
    return jnp.sum(jnp.where(lane == k, x, 0.0), axis=-1, keepdims=True)


def _kernel(rel_ref, judged_ref, scal_ref, disc_ref, rbpw_ref, out_ref, *,
            relevance_level, d_valid):
    rel = rel_ref[...]
    judged = judged_ref[...]
    bq, d = rel.shape
    scal = scal_ref[...]
    scal_lane = jax.lax.broadcasted_iota(jnp.int32, scal.shape, 1)
    n_rel = _lane(scal, scal_lane, 0)
    n_nonrel = _lane(scal, scal_lane, 1)
    idcg_full = _lane(scal, scal_lane, 2)

    lane = jax.lax.broadcasted_iota(jnp.int32, (bq, d), 1)
    ranks = lane.astype(jnp.float32) + 1.0
    last = d_valid - 1

    def at(cum, k):
        return _lane(cum, lane, min(k, d_valid) - 1)

    binrel = jnp.where(rel >= relevance_level, 1.0, 0.0)
    cum = _cumsum_lanes(binrel, lane)
    prec = cum / ranks

    # -- AP (+ cutoffs) ------------------------------------------------------
    ap_cum = _cumsum_lanes(binrel * prec, lane)
    # -- DCG (+ cutoffs), linear trec_eval gain ------------------------------
    gains = jnp.maximum(rel, 0.0) * disc_ref[...]
    dcg_cum = _cumsum_lanes(gains, lane)
    # -- bpref ---------------------------------------------------------------
    jn = judged * (1.0 - binrel)
    nr_above = _cumsum_lanes(jn, lane) - jn
    bpref_den = jnp.minimum(n_rel, n_nonrel)
    bterm = jnp.where(
        nr_above > 0,
        1.0 - _sdiv(jnp.minimum(nr_above, n_rel), bpref_den),
        1.0,
    )
    bpref_v = _sdiv(jnp.sum(bterm * binrel, axis=-1, keepdims=True), n_rel)
    # -- reciprocal rank -----------------------------------------------------
    num_rel_ret = _lane(cum, lane, last)
    any_rel = num_rel_ret > 0
    first_rank = 1.0 + jnp.sum(jnp.where(cum == 0, 1.0, 0.0), axis=-1,
                               keepdims=True)
    rr = jnp.where(any_rel, 1.0 / first_rank, 0.0)
    # -- R-precision (dynamic per-row rank R) --------------------------------
    within_r = jnp.where(ranks <= n_rel, 1.0, 0.0)
    rel_at_r = jnp.sum(binrel * within_r, axis=-1, keepdims=True)
    rprec = _sdiv(rel_at_r, n_rel)

    cols = [
        _sdiv(_lane(ap_cum, lane, last), n_rel),
        rr,
        _sdiv(_lane(dcg_cum, lane, last), idcg_full),
        bpref_v,
        num_rel_ret,
        rprec,
    ]
    for k in CUTOFFS:
        cols.append(at(cum, k) / float(k))
    for k in CUTOFFS:
        cols.append(_sdiv(at(cum, k), n_rel))
    for j, k in enumerate(CUTOFFS):
        idcg_k = _lane(scal, scal_lane, 3 + j)
        cols.append(_sdiv(at(dcg_cum, k), idcg_k))
    for k in CUTOFFS:
        cols.append(_sdiv(at(ap_cum, k), n_rel))
    for k in SUCCESS_CUTOFFS:
        cols.append(jnp.where(at(cum, k) > 0, 1.0, 0.0))
    # -- judged@k (exact: 0/1 counts, the shifted-add cumsum is integral) ----
    cum_judged = _cumsum_lanes(judged, lane)
    for k in CUTOFFS:
        cols.append(at(cum_judged, k) / float(k))
    # -- RBP, default persistence (same expression as core.measures.rbp) -----
    cols.append(jnp.sum(binrel * rbpw_ref[...], axis=-1, keepdims=True))

    # Place each [bq, 1] column at its lane of the output block (selects,
    # not a lane-axis stack, which Mosaic would have to relayout).
    out_lane = jax.lax.broadcasted_iota(jnp.int32, (bq, OUT_WIDTH), 1)
    out = jnp.zeros((bq, OUT_WIDTH), jnp.float32)
    for c, col in enumerate(cols):
        out = jnp.where(out_lane == c, col, out)
    out_ref[...] = out


@functools.lru_cache(maxsize=None)
def _measure_call(q_pad: int, d: int, block_q: int, relevance_level: float,
                  interpret: bool):
    """Build the ``pallas_call`` for one shard geometry, memoized.

    The sharded evaluation path (``repro.distributed.sharded_evaluator``)
    invokes the kernel once per device shard; every shard has the identical
    local ``[q_pad/n_shards, d]`` geometry, so the grid/block specs (and the
    closure holding them) are constructed exactly once and reused across
    shards, re-traces, and steps.  Keys are the full static signature —
    anything that changes the lowered kernel.
    """
    kern = functools.partial(_kernel, relevance_level=relevance_level,
                             d_valid=d)
    return pl.pallas_call(
        kern,
        grid=(q_pad // block_q,),
        in_specs=[
            pl.BlockSpec((block_q, d), lambda i: (i, 0)),
            pl.BlockSpec((block_q, d), lambda i: (i, 0)),
            pl.BlockSpec((block_q, 16), lambda i: (i, 0)),
            pl.BlockSpec((1, d), lambda i: (0, 0)),
            pl.BlockSpec((1, d), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((block_q, OUT_WIDTH), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((q_pad, OUT_WIDTH), jnp.float32),
        interpret=interpret,
    )


@functools.partial(jax.jit, static_argnames=("block_q", "relevance_level",
                                             "interpret"))
def fused_measures(rel_sorted, judged_sorted, scalars,
                   block_q: int | None = None,
                   relevance_level: float = 1.0, interpret: bool = True):
    """All 55 standard measure columns in one VMEM pass.  Returns [Q, 64] f32.

    ``block_q=None`` (the default) consults the roofline-driven autotuner
    (``kernels.autotune.block_q_for``) — a deterministic function of the
    ``[Q, D]`` shape, resolved at trace time, so it adds no compiled
    signatures beyond the shape classes themselves.
    """
    bucketing.record_trace("fused_measures")  # trace-time: one per signature
    q, d = rel_sorted.shape
    if block_q is None:
        block_q = autotune.block_q_for(q, d)
    q_pad = ((q + block_q - 1) // block_q) * block_q
    if q_pad != q:
        pad = ((0, q_pad - q), (0, 0))
        rel_sorted = jnp.pad(rel_sorted, pad)
        judged_sorted = jnp.pad(judged_sorted, pad)
        scalars = jnp.pad(scalars, pad)
    # rank-only tables, host-built (the TPU's log2/pow are approximations)
    disc = jnp.asarray(M.discount_table(d))[None, :]
    rbpw = jnp.asarray(M.rbp_table(d, DEFAULT_RBP_P))[None, :]
    out = _measure_call(q_pad, d, block_q, relevance_level, interpret)(
        rel_sorted, judged_sorted, scalars, disc, rbpw)
    return out[:q]
