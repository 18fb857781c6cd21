"""Batched trec_eval evaluation measures on dense ``[Q, D]`` tensors.

This is the device-resident core of the framework: the reference measure
definitions of trec_eval, re-expressed as vectorized JAX computations over a
whole batch of queries at once.  Where trec_eval walks each ranking once in C,
we compute cumulative statistics over the sorted relevance tensor with a single
pass of vector ops — the same one-pass structure, MXU/VPU-friendly.

Semantics follow trec_eval (and therefore pytrec_eval):

* documents are ranked by decreasing score, ties broken by docno (descending
  lex — encoded in the ``tiebreak`` field, see ``core.sorting``);
* unjudged documents count as non-relevant;
* a document is *relevant* iff its judgment >= ``relevance_level`` (default 1);
* ``map`` / ``recall`` / ``Rprec`` normalize by R = number of relevant docs in
  the **qrels** (including unretrieved ones);
* ``ndcg`` uses trec_eval's linear gain (rel / log2(rank+1)) with the ideal
  ranking drawn from the full qrels;
* cutoffs match trec_eval: 5,10,15,20,30,100,200,500,1000 (success: 1,5,10).

All measure functions operate on an :class:`EvalBatch` and return per-query
float32 vectors ``[Q]``; padded queries (``query_mask == False``) return 0 and
are excluded by the aggregation helpers.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, List, NamedTuple, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from repro.core import registry, sorting

# Shared measure constants live in the declarative registry; re-exported
# here because every engine historically imports them from this module.
DEFAULT_CUTOFFS: Tuple[int, ...] = registry.DEFAULT_CUTOFFS
SUCCESS_CUTOFFS: Tuple[int, ...] = registry.SUCCESS_CUTOFFS
IPREC_LEVELS: Tuple[float, ...] = registry.IPREC_LEVELS

#: trec_eval's MIN_GEO_MEAN: per-query AP is clipped to this before the log
#: so queries with AP == 0 do not collapse the geometric mean to 0.
GM_MIN: float = registry.GM_MIN

#: Measure families understood by this module (pytrec_eval-compatible ids),
#: derived from the declarative registry (``repro.core.registry``).
SUPPORTED_MEASURES = registry.supported_families()

#: Aggregate-only measures: the per-query column is a *log contribution*
#: (``log(max(AP, GM_MIN))`` for ``gm_map``, exactly what trec_eval
#: accumulates per query); the user-facing value is the geometric mean
#: ``exp(mean(column))`` produced by :func:`finalize_aggregates`.  The CLI
#: suppresses these keys from per-query (-q) output, like trec_eval does.
AGGREGATE_ONLY_MEASURES = registry.aggregate_only_families()


class EvalBatch(NamedTuple):
    """Dense, padded representation of a batch of rankings + ground truth.

    Axes: Q = queries (padded), D = retrieved docs per query (padded),
    J = judged docs per query (padded; used only for the ideal DCG).
    """

    scores: jax.Array  # [Q, D] f32 — retrieval scores (order irrelevant)
    tiebreak: jax.Array  # [Q, D] i32 — smaller wins ties (docno desc-lex rank)
    rel: jax.Array  # [Q, D] f32 — judgment of each retrieved doc (0 unjudged)
    judged: jax.Array  # [Q, D] bool — retrieved doc appears in the qrels
    mask: jax.Array  # [Q, D] bool — retrieved doc is real (not padding)
    ideal_rel: jax.Array  # [Q, J] f32 — qrel judgments, sorted descending
    n_rel: jax.Array  # [Q] f32 — R: relevant docs in qrels (rel >= level)
    n_judged_nonrel: jax.Array  # [Q] f32 — judged non-relevant docs in qrels
    query_mask: jax.Array  # [Q] bool — query is real (not padding)


class SortedBatch(NamedTuple):
    """EvalBatch after ranking: everything ordered by trec_eval rank."""

    rel: jax.Array  # [Q, D] f32, rank order
    binrel: jax.Array  # [Q, D] f32 (0/1), rank order
    judged: jax.Array  # [Q, D] f32 (0/1), rank order
    mask: jax.Array  # [Q, D] f32 (0/1), rank order
    cum_rel: jax.Array  # [Q, D] f32 — inclusive cumulative count of relevant
    ideal_rel: jax.Array  # [Q, J] f32
    n_rel: jax.Array  # [Q] f32
    n_judged_nonrel: jax.Array  # [Q] f32
    n_ret: jax.Array  # [Q] f32
    query_mask: jax.Array  # [Q] bool


_PACK_OFFSET = 4.0  # rel values ≥ -4 supported (trec_eval uses ≥ -2)


def sort_batch(batch: EvalBatch, relevance_level: float = 1.0,
               judged_only: bool = False) -> SortedBatch:
    """Rank every query's documents under trec_eval ordering.

    ``judged_only`` implements trec_eval's ``-J`` (pytrec_eval's
    ``judged_docs_only`` constructor flag): unjudged retrieved documents are
    removed from the ranking before any measure sees it.  Dropped documents
    sort to the tail with rel=0/judged=0 — indistinguishable from padding,
    hence inert for every measure — and ``n_ret`` counts only the kept docs.

    Perf note (§Perf iteration C2): (rel, judged) ride the sort as ONE packed
    f32 payload — ``(rel+4)·2 + judged`` — and the mask is not sorted at all
    (padding sorts last with rel=0/judged=0, which is inert for every
    measure; n_ret is an order-invariant pre-sort sum).  This halves the
    multi-operand sort's traffic vs the naive 5-payload formulation.
    """
    assert relevance_level >= 1.0 or relevance_level > 0, \
        "packed-payload sort assumes relevance_level > 0"
    mask = batch.mask & batch.judged if judged_only else batch.mask
    packed = (batch.rel * jnp.asarray(mask, jnp.float32)
              + _PACK_OFFSET) * 2.0 + jnp.asarray(
        batch.judged & mask, jnp.float32)
    packed = jnp.where(mask, packed, _PACK_OFFSET * 2.0)
    (packed_s,) = sorting.rank_sort(
        batch.scores, batch.tiebreak, mask, packed)[1:]
    judged_s = packed_s - 2.0 * jnp.floor(packed_s / 2.0)
    rel_s = jnp.floor(packed_s / 2.0) - _PACK_OFFSET
    binrel = jnp.where(rel_s >= relevance_level, 1.0, 0.0)
    cum_rel = jnp.cumsum(binrel, axis=-1)
    return SortedBatch(
        rel=rel_s,
        binrel=binrel,
        judged=judged_s,
        mask=jnp.ones_like(rel_s),
        cum_rel=cum_rel,
        ideal_rel=batch.ideal_rel,
        n_rel=batch.n_rel,
        n_judged_nonrel=batch.n_judged_nonrel,
        n_ret=jnp.sum(mask.astype(jnp.float32), axis=-1),
        query_mask=batch.query_mask,
    )


# ---------------------------------------------------------------------------
# Individual measures (each: SortedBatch -> [Q] f32).
# ---------------------------------------------------------------------------


def _ranks(d: int) -> jax.Array:
    return jnp.arange(1, d + 1, dtype=jnp.float32)


def _safe_div(num, den):
    return jnp.where(den > 0, num / jnp.maximum(den, 1e-30), 0.0)


def _at_rank(cum: jax.Array, k: int) -> jax.Array:
    """cum value at 1-based rank k (clipped to the retrieved-depth D)."""
    d = cum.shape[-1]
    return cum[..., min(k, d) - 1]


def average_precision(s: SortedBatch) -> jax.Array:
    d = s.binrel.shape[-1]
    prec = s.cum_rel / _ranks(d)
    ap = jnp.sum(s.binrel * prec, axis=-1)
    return _safe_div(ap, s.n_rel)


def gm_map_contrib(s: SortedBatch) -> jax.Array:
    """Per-query geometric-MAP contribution: ``log(max(AP, GM_MIN))``.

    trec_eval's ``gm_map`` accumulates exactly this per query and prints only
    the summary ``exp(sum / num_q)``; the clip keeps zero-AP queries from
    sending the geometric mean to 0.
    """
    return jnp.log(jnp.maximum(average_precision(s), GM_MIN))


def map_cut(s: SortedBatch, k: int) -> jax.Array:
    d = s.binrel.shape[-1]
    within = (_ranks(d) <= k).astype(jnp.float32)
    prec = s.cum_rel / _ranks(d)
    ap = jnp.sum(s.binrel * prec * within, axis=-1)
    return _safe_div(ap, s.n_rel)


def precision_at(s: SortedBatch, k: int) -> jax.Array:
    # trec_eval always divides by k, even when fewer than k docs were retrieved.
    return _at_rank(s.cum_rel, k) / float(k)


def recall_at(s: SortedBatch, k: int) -> jax.Array:
    return _safe_div(_at_rank(s.cum_rel, k), s.n_rel)


def success_at(s: SortedBatch, k: int) -> jax.Array:
    return (_at_rank(s.cum_rel, k) > 0).astype(jnp.float32)


def reciprocal_rank(s: SortedBatch) -> jax.Array:
    d = s.binrel.shape[-1]
    any_rel = jnp.sum(s.binrel, axis=-1) > 0
    first = jnp.argmax(s.binrel, axis=-1).astype(jnp.float32) + 1.0
    return jnp.where(any_rel, 1.0 / first, 0.0)


def r_precision(s: SortedBatch) -> jax.Array:
    d = s.cum_rel.shape[-1]
    idx = jnp.clip(s.n_rel.astype(jnp.int32), 1, d) - 1
    at_r = jnp.take_along_axis(s.cum_rel, idx[:, None], axis=-1)[:, 0]
    return _safe_div(at_r, s.n_rel)


def bpref(s: SortedBatch) -> jax.Array:
    """trec_eval bpref: judged-only preference measure."""
    judged_nonrel = s.judged * (1.0 - s.binrel)
    # judged non-relevant docs ranked strictly above each position (exclusive).
    nr_above = jnp.cumsum(judged_nonrel, axis=-1) - judged_nonrel
    r = s.n_rel[:, None]
    n = s.n_judged_nonrel[:, None]
    denom = jnp.minimum(r, n)
    bounded = jnp.minimum(nr_above, r)
    term = jnp.where(nr_above > 0, 1.0 - _safe_div(bounded, denom), 1.0)
    total = jnp.sum(term * s.binrel, axis=-1)
    return _safe_div(total, s.n_rel)


def discount_table(d: int) -> np.ndarray:
    """trec_eval's rank discounts ``1/log2(rank+1)`` for ranks ``1..d``.

    Built on the host in float64 and rounded once to float32, so every
    backend sees the same correctly rounded table: the TPU's float32
    ``log2`` is an approximation (relative error up to ~6e-5 on v5e),
    which moved nDCG by ~1e-5.
    """
    return (1.0 / np.log2(np.arange(2, d + 2, dtype=np.float64))).astype(
        np.float32)


def rbp_table(d: int, p: float) -> np.ndarray:
    """RBP rank weights ``(1-p)·p^(rank-1)`` for ranks ``1..d`` (host-built,
    like :func:`discount_table`, because the TPU's float32 ``pow`` is an
    approximation)."""
    return ((1.0 - p) * np.power(p, np.arange(d, dtype=np.float64))).astype(
        np.float32)


def _discounts(d: int) -> jax.Array:
    return jnp.asarray(discount_table(d))


def dcg(s: SortedBatch, k: int | None = None) -> jax.Array:
    """trec_eval DCG: linear gain rel / log2(rank + 1)."""
    d = s.rel.shape[-1]
    gains = jnp.maximum(s.rel, 0.0) * _discounts(d)  # negative rels gain 0
    if k is not None:
        # Static slice to the cutoff BEFORE reducing (as in err_at): the
        # reduction width is min(k, d) whatever the padding, so the top-k
        # path (d == k) and the full-sort path sum in the same order.
        gains = gains[:, :min(int(k), d)]
    return jnp.sum(gains, axis=-1)


def ideal_dcg(s: SortedBatch, k: int | None = None) -> jax.Array:
    j = s.ideal_rel.shape[-1]
    disc = _discounts(j)
    gains = jnp.maximum(s.ideal_rel, 0.0) * disc
    if k is not None:
        gains = gains * (_ranks(j) <= k).astype(jnp.float32)
    return jnp.sum(gains, axis=-1)


def ndcg(s: SortedBatch) -> jax.Array:
    return _safe_div(dcg(s), ideal_dcg(s))


def ndcg_cut(s: SortedBatch, k: int) -> jax.Array:
    return _safe_div(dcg(s, k), ideal_dcg(s, k))


def iprec_at_recall(s: SortedBatch, level: float) -> jax.Array:
    """Interpolated precision at a recall level (11-pt PR curve point)."""
    d = s.cum_rel.shape[-1]
    prec = s.cum_rel / _ranks(d)
    # Reverse running max: best precision achievable at this rank or deeper.
    rev_max = jnp.flip(
        jax.lax.cummax(jnp.flip(prec, axis=-1), axis=prec.ndim - 1), axis=-1)
    target = jnp.ceil(level * s.n_rel)[:, None]
    # First rank whose relevant-count reaches the target.
    reached = s.cum_rel >= jnp.maximum(target, 0.0)
    any_reach = jnp.any(reached, axis=-1)
    first_idx = jnp.argmax(reached, axis=-1)
    val = jnp.take_along_axis(rev_max, first_idx[:, None], axis=-1)[:, 0]
    val = jnp.where(any_reach, val, 0.0)
    return jnp.where(s.n_rel > 0, val, 0.0)


def num_ret(s: SortedBatch) -> jax.Array:
    return s.n_ret


def num_rel(s: SortedBatch) -> jax.Array:
    return s.n_rel


def num_rel_ret(s: SortedBatch) -> jax.Array:
    return s.cum_rel[:, -1]


def judged_at(s: SortedBatch, k: int) -> jax.Array:
    """Judged@k: fraction of the top k that appears in the qrels.

    Like trec_eval's P@k, the denominator is always k — queries retrieving
    fewer than k documents are penalized, not renormalized.
    """
    cum_judged = jnp.cumsum(s.judged, axis=-1)
    return _at_rank(cum_judged, k) / float(k)


def rbp(s: SortedBatch, p: float) -> jax.Array:
    """Rank-biased precision (Moffat & Zobel): ``(1-p)·Σ rel_i·p^(i-1)``.

    Binary relevance (>= the relevance level), geometric rank discount with
    persistence ``p``.  Documents beyond the retrieved depth contribute 0,
    i.e. this is the base RBP score without the residual.
    """
    weights = jnp.asarray(rbp_table(s.binrel.shape[-1], p))
    return jnp.sum(s.binrel * weights, axis=-1)


def err_at(s: SortedBatch, k: int) -> jax.Array:
    """Expected reciprocal rank at k (Chapelle et al.'s cascade model).

    ``ERR@k = Σ_{i<=k} (1/i) · R_i · Π_{j<i} (1 − R_j)`` with stop
    probability ``R_i = (2^max(rel_i, 0) − 1) / 2^G``.  ``G`` is the
    per-query maximum qrel grade (min 1) — each query's own grade scale
    normalizes its gains, the convention documented in docs/MEASURES.md.
    Unjudged documents have rel 0, hence stop probability 0.
    """
    d = s.rel.shape[-1]
    kk = min(int(k), d)
    g = jnp.maximum(s.ideal_rel[:, 0], 1.0)[:, None]
    # Static slice to the cutoff BEFORE reducing: the reduction width is
    # then k regardless of document padding, so the top-k path (d == k) and
    # the full-sort path produce bit-identical sums (no reassociation).
    rel_k = s.rel[:, :kk]
    stop = (jnp.power(2.0, jnp.maximum(rel_k, 0.0)) - 1.0) / jnp.power(2.0, g)
    no_stop = jnp.cumprod(1.0 - stop, axis=-1)
    prior = jnp.concatenate(
        [jnp.ones_like(no_stop[:, :1]), no_stop[:, :-1]], axis=-1)
    return jnp.sum(stop * prior / _ranks(kk), axis=-1)


# ---------------------------------------------------------------------------
# Measure-set plumbing (delegated to the declarative registry).
# ---------------------------------------------------------------------------


def parse_measures(measures: Sequence[str]) -> Tuple[Tuple[str, Tuple[float, ...]], ...]:
    """Normalize measure strings (either dialect) into (family, params).

    Accepts trec_eval-dialect family names (``"ndcg_cut"`` → all default
    cutoffs), explicit params (``"P.5,10"``), pytrec_eval output-style ids
    (``"P_5"``, ``"ndcg_cut_10"``), and ir-measures-dialect strings
    (``"nDCG@10"``, ``"P@5"``, ``"RBP(p=0.8)"``).  Selectors naming the
    same family merge into one entry with the union of their params
    (sorted), so a repeated measure list like ``("P_5", "P.5,10", "P@20")``
    yields each output key exactly once — the contract the sweep/compare
    CLI's repeatable ``-m`` flag relies on.  Delegates to
    :mod:`repro.core.registry`; ``rel=`` annotations require the
    level-aware :func:`registry.canonicalize`.
    """
    return registry.parse_measures(measures)


def family_keys(fam: str, params: Tuple[float, ...]) -> Tuple[str, ...]:
    """Output keys for one parsed (family, params) entry (registry rules)."""
    return registry.family_keys(fam, params)


def measure_keys(measures: Sequence[str]) -> Tuple[str, ...]:
    """The pytrec_eval-style output keys produced for a measure set."""
    return registry.measure_keys(measures)


def _mask_queries(out: Dict[str, jax.Array], s: SortedBatch) -> Dict[str, jax.Array]:
    zero = jnp.zeros_like(s.n_rel)
    qm = s.query_mask
    return {k: jnp.where(qm, v, zero) for k, v in out.items()}


def compute_measures(
    batch: EvalBatch,
    measures: Tuple[Tuple[str, Tuple[float, ...]], ...],
    relevance_level: float = 1.0,
    judged_only: bool = False,
) -> Dict[str, jax.Array]:
    """Compute every requested measure for every query in the batch.

    ``measures`` must be the output of :func:`parse_measures` (hashable, so
    this function can be jitted with ``static_argnums``).  Column dispatch
    is table-driven by :mod:`repro.core.registry`.  Returns a dict of
    pytrec_eval-style keys to ``[Q]`` float32 vectors.
    """
    s = sort_batch(batch, relevance_level, judged_only)
    return _mask_queries(registry.apply_columns(s, measures), s)


def compute_measures_topk(
    batch: EvalBatch,
    measures: Tuple[Tuple[str, Tuple[float, ...]], ...],
    relevance_level: float = 1.0,
    judged_only: bool = False,
) -> Dict[str, jax.Array]:
    """Depth-bounded measure computation via the top-k kernel.

    Requires every family in ``measures`` to be depth-bounded
    (``registry.topk_depth(measures) is not None``) AND the batch to use the
    **tiebreak-column layout**: each document scattered at column ==
    tiebreak rank (``RelevanceEvaluator.batch_from_buffer(...,
    topk_layout=True)``).  Under that layout the top-k kernel's
    smaller-index-wins tie rule IS trec_eval's smaller-tiebreak-wins rule,
    so the selected prefix equals the full sort's first k rows exactly, and
    every bounded column is bit-identical to :func:`compute_measures` —
    without ever sorting the full document axis.
    """
    from repro.kernels import ops

    depth = registry.topk_depth(measures)
    assert depth is not None, "top-k path needs depth-bounded measures"
    q, d = batch.scores.shape
    k = min(depth, d)
    eff = batch.mask & batch.judged if judged_only else batch.mask
    scores_m = jnp.where(eff, batch.scores, -jnp.inf)
    _, idx = ops.topk(scores_m, k)
    in_range = (idx >= 0) & (idx < d)
    idx_c = jnp.clip(idx, 0, d - 1)
    valid = in_range & jnp.take_along_axis(eff, idx_c, axis=-1)
    rel_s = jnp.where(valid, jnp.take_along_axis(batch.rel, idx_c, axis=-1),
                      0.0)
    judged_s = jnp.where(
        valid, jnp.take_along_axis(batch.judged, idx_c, axis=-1),
        False).astype(jnp.float32)
    binrel = jnp.where(rel_s >= relevance_level, 1.0, 0.0) * valid
    s = SortedBatch(
        rel=rel_s,
        binrel=binrel,
        judged=judged_s,
        mask=jnp.ones_like(rel_s),
        cum_rel=jnp.cumsum(binrel, axis=-1),
        ideal_rel=batch.ideal_rel,
        n_rel=batch.n_rel,
        n_judged_nonrel=batch.n_judged_nonrel,
        n_ret=jnp.sum(eff.astype(jnp.float32), axis=-1),
        query_mask=batch.query_mask,
    )
    return _mask_queries(registry.apply_columns(s, measures), s)


def pack_columns(columns: Dict[str, jax.Array], measures,
                 q: int) -> jax.Array:
    """The ``[Q]`` columns as one ``[K, Q]`` array, rows in
    ``registry.keys_for(measures)`` order (``measure_keys``'s)."""
    keys = registry.keys_for(measures)
    if not keys:
        return jnp.zeros((0, q), jnp.float32)
    return jnp.stack([columns[k] for k in keys])


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def compute_measures_packed_jit(batch, measures, relevance_level=1.0,
                                judged_only=False):
    """:func:`compute_measures`'s columns packed by :func:`pack_columns`
    in the same program: one output to fetch."""
    # Lazy import: repro.kernels pulls in this module at its own import time.
    # bucketing itself is dependency-free, so the in-body import is cheap and
    # cycle-safe; the call runs at trace time only (once per signature).
    from repro.kernels import bucketing
    bucketing.record_trace("measure_core")
    return pack_columns(
        compute_measures(batch, measures, relevance_level, judged_only),
        measures, batch.query_mask.shape[0])


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def compute_measures_topk_packed_jit(batch, measures, relevance_level=1.0,
                                     judged_only=False):
    """:func:`compute_measures_topk`'s columns packed by
    :func:`pack_columns` in the same program."""
    from repro.kernels import bucketing
    bucketing.record_trace("measure_core_topk")
    return pack_columns(
        compute_measures_topk(batch, measures, relevance_level, judged_only),
        measures, batch.query_mask.shape[0])


def packed_core(topk: bool, mesh=None):
    """The packed measure core of a depth class: the top-k route's
    (``topk``) or the full sort's.

    With a 1-D device ``mesh``, that core runs on every device over the
    device's share of the query axis (``shard_map``: the top-k kernel's
    ``pallas_call`` is not partitioned on its own), and the ``[K, q_pad]``
    result is split over the devices on its query axis.  Each row is the
    same program either way, since every measure is computed per query.
    """
    core = compute_measures_topk_packed_jit if topk else \
        compute_measures_packed_jit
    return core if mesh is None else _core_on_mesh(core, mesh)


@functools.lru_cache(maxsize=None)
def _core_on_mesh(core, mesh):
    from jax.sharding import PartitionSpec as P

    from repro.distributed import shard_map

    axis = mesh.axis_names[0]

    def run(batch, measures, relevance_level=1.0, judged_only=False):
        return shard_map(
            lambda b: core(b, measures, relevance_level, judged_only),
            mesh=mesh, in_specs=P(axis), out_specs=P(None, axis),
            check_vma=False)(batch)

    return jax.jit(run, static_argnums=(1, 2, 3))


def aggregate(per_query: Dict[str, jax.Array], query_mask: jax.Array) -> Dict[str, jax.Array]:
    """Mean over real queries (trec_eval 'all' row)."""
    n = jnp.maximum(jnp.sum(query_mask.astype(jnp.float32)), 1.0)
    return {k: jnp.sum(v * query_mask, axis=-1) / n for k, v in per_query.items()}


def finalize_aggregates(aggs: Dict[str, float]) -> Dict[str, float]:
    """Turn averaged per-query columns into user-facing summary values.

    Arithmetic-mean measures pass through unchanged; aggregate-only
    geometric measures (``gm_map``) arrive as the mean of per-query log
    contributions and leave as ``exp(mean)`` — trec_eval's geometric mean.
    """
    return {k: float(np.exp(v)) if k in AGGREGATE_ONLY_MEASURES else v
            for k, v in aggs.items()}


# ---------------------------------------------------------------------------
# Batch construction helpers.
# ---------------------------------------------------------------------------


class DepthClass(NamedTuple):
    """One padded batch of a :class:`FlatLayout`: its queries and padding.

    ``RelevanceEvaluator`` groups a buffer's queries by the padding class
    of their own list length (``kernels.bucketing.bucket_docs``), so each
    group is padded to its own ``[q_pad, d_pad]`` rectangle rather than to
    the buffer's longest list.  A buffer whose lists share one class is one
    class: its whole query axis.
    """

    queries: np.ndarray  # [n] intp — the buffer's query indices, ascending
    q_pad: int
    d_pad: int
    j_pad: int  # ideal-gain columns: the class's longest judged list
    topk: bool  # each document at column == its tiebreak rank (top-k layout)
    docs: int  # real documents in the class


class FlatLayout(NamedTuple):
    """Where a flat run's documents land in the padded ``EvalBatch`` of
    each depth class, with every field of those batches that does not
    depend on the scores.

    Built once by :func:`flat_layout`; :meth:`batches` then places one set
    of flat scores per call.  The classes' scores slabs lie end to end in
    one fresh array; ``dest`` is each document's position in it, or
    ``None`` where there is one class and the flat order is its query-major
    ``rows × depth`` block, so the scores are one reshape copy.  The static
    slabs are read-only: every batch shares them.

    ``device`` holds the static slabs' copies on the device, one tuple of
    batches (scores ``None``) per placement: ``None`` for the default
    device, or a mesh's ``NamedSharding``.  The first call at a placement
    sends them (``RelevanceEvaluator._measure_rows``); later calls send
    the scores alone.  They live and die with the layout.
    """

    classes: Tuple[DepthClass, ...]
    static: Tuple[EvalBatch, ...]  # one per class; scores is None
    dest: np.ndarray | None  # [n] intp
    rows: int
    depth: int
    device: Dict[Any, Tuple[EvalBatch, ...]]  # placement -> static slabs

    def batches(self, scores: np.ndarray) -> List[EvalBatch]:
        """The padded batch of each class for these flat scores."""
        shapes = [(c.q_pad, c.d_pad) for c in self.classes]
        flat = np.zeros(sum(q * d for q, d in shapes), dtype=np.float32)
        if self.dest is None:
            flat.reshape(shapes[0])[:self.rows, :self.depth] = scores.reshape(
                self.rows, self.depth)
        else:
            flat[self.dest] = scores
        out, lo = [], 0
        for static, (q, d) in zip(self.static, shapes):
            out.append(static._replace(
                scores=flat[lo:lo + q * d].reshape(q, d)))
            lo += q * d
        return out


def flat_layout(
    *,
    qidx: np.ndarray,
    col: np.ndarray,
    tiebreak: np.ndarray,
    rel: np.ndarray,
    judged: np.ndarray,
    ideal_rows: np.ndarray,
    n_rel: np.ndarray,
    n_judged_nonrel: np.ndarray,
    counts: np.ndarray,
    classes: Sequence[DepthClass],
) -> FlatLayout:
    """Scatter the score-independent flat arrays into each class's slabs.

    The host-side counterpart of :func:`batch_from_dense`; the layout's
    :meth:`FlatLayout.batches` completes each class's ``EvalBatch`` with
    each set of flat scores.  All per-document vectors are flat
    (concatenated in query order), with ``qidx`` each document's query,
    ``col`` its column (``tiebreak`` in a ``topk`` class), ``counts`` each
    query's documents and the per-query rows indexed by query.  ``classes``
    partition the queries.  When there is one class, every query retrieved
    the same depth and ``(qidx, col)`` is the query-major order (the
    fixed-depth case that dominates real runs and the RQ1 grid), each field
    is a reshape copy; otherwise one 1-D scatter through the flat
    destination index per field, into the classes' slabs laid end to end.
    The validity mask is a broadcast compare either way.
    """
    classes = tuple(classes)
    sizes = [c.q_pad * c.d_pad for c in classes]
    tiebreak_all = np.zeros(sum(sizes), dtype=np.int32)
    rel_all = np.zeros(sum(sizes), dtype=np.float32)
    judged_all = np.zeros(sum(sizes), dtype=bool)
    mask_all = np.zeros(sum(sizes), dtype=bool)
    n_queries = counts.shape[0]
    d = int(counts[0]) if n_queries else 0
    grid = (n_queries, d)
    if len(classes) == 1:
        only = classes[0]
        cols = tiebreak if only.topk else col
        # the reshape copy assumes query-major flat order; verify that
        # (qidx, cols) really is the implied layout rather than trusting it
        uniform = (d and int(counts.min()) == d == int(counts.max())
                   and qidx.shape[0] == n_queries * d
                   and bool((cols.reshape(grid) == np.arange(d)).all())
                   and bool((qidx.reshape(grid)
                             == np.arange(n_queries)[:, None]).all()))
        dest = None if uniform else np.multiply(qidx, only.d_pad,
                                                dtype=np.intp)
    else:
        uniform = False
        start = np.empty(n_queries, dtype=np.intp)  # each query's row start
        topk = np.empty(n_queries, dtype=bool)
        lo = 0
        for c, size in zip(classes, sizes):
            start[c.queries] = lo + np.arange(len(c.queries)) * c.d_pad
            topk[c.queries] = c.topk
            lo += size
        cols = np.where(topk[qidx], tiebreak, col)
        dest = start[qidx]
    if uniform:
        for flat, field in ((tiebreak_all, tiebreak), (rel_all, rel),
                            (judged_all, judged)):
            flat.reshape(only.q_pad, only.d_pad)[:n_queries, :d] = \
                field.reshape(grid)
    else:
        dest += cols
        tiebreak_all[dest] = tiebreak
        rel_all[dest] = rel
        judged_all[dest] = judged

    static, lo = [], 0
    for c, size in zip(classes, sizes):
        n = len(c.queries)
        shape = (c.q_pad, c.d_pad)
        tiebreak2, rel2, judged2, mask2 = (
            flat[lo:lo + size].reshape(shape)
            for flat in (tiebreak_all, rel_all, judged_all, mask_all))
        lo += size
        mask2[:n] = (np.arange(c.d_pad, dtype=np.int64)[None, :]
                     < counts[c.queries][:, None])
        ideal = np.zeros((c.q_pad, c.j_pad), dtype=np.float32)
        w = min(c.j_pad, ideal_rows.shape[1])
        ideal[:n, :w] = ideal_rows[c.queries, :w]
        n_rel2 = np.zeros((c.q_pad,), dtype=np.float32)
        n_rel2[:n] = n_rel[c.queries]
        n_nonrel2 = np.zeros((c.q_pad,), dtype=np.float32)
        n_nonrel2[:n] = n_judged_nonrel[c.queries]
        qmask = np.zeros((c.q_pad,), dtype=bool)
        qmask[:n] = True
        static.append(EvalBatch(
            scores=None, tiebreak=tiebreak2, rel=rel2, judged=judged2,
            mask=mask2, ideal_rel=ideal, n_rel=n_rel2,
            n_judged_nonrel=n_nonrel2, query_mask=qmask,
        ))
    for a in (*(f for s in static for f in s[1:]), dest):
        if a is not None:
            a.flags.writeable = False
    return FlatLayout(classes, tuple(static), dest, n_queries, d, {})


# ---------------------------------------------------------------------------
# Dense entry point for in-loop evaluation (no dicts, pure device).
# ---------------------------------------------------------------------------


def batch_from_dense(
    scores: jax.Array,
    rel: jax.Array,
    mask: jax.Array | None = None,
    judged: jax.Array | None = None,
    query_mask: jax.Array | None = None,
    tiebreak: jax.Array | None = None,
    relevance_level: float = 1.0,
) -> EvalBatch:
    """Build an EvalBatch from dense score/relevance tensors.

    Assumes the candidate set *is* the judged set (standard for in-loop model
    evaluation where every candidate has a known label).  The ideal ranking is
    derived by sorting ``rel`` — correct because all judged docs are present.
    """
    q, d = scores.shape
    if mask is None:
        mask = jnp.ones((q, d), dtype=bool)
    if judged is None:
        judged = mask
    if query_mask is None:
        query_mask = jnp.ones((q,), dtype=bool)
    if tiebreak is None:
        tiebreak = jnp.broadcast_to(jnp.arange(d, dtype=jnp.int32), (q, d))
    # unjudged docs are non-relevant by definition (trec_eval): zero their
    # rel so every engine sees consistent inputs
    rel = rel.astype(jnp.float32) * mask * judged
    ideal = -jnp.sort(-rel, axis=-1)
    binrel = (rel >= relevance_level) & mask & (judged > 0)
    n_rel = jnp.sum(binrel.astype(jnp.float32), axis=-1)
    n_nonrel = jnp.sum((judged & mask).astype(jnp.float32), axis=-1) - n_rel
    return EvalBatch(
        scores=scores.astype(jnp.float32),
        tiebreak=tiebreak,
        rel=rel,
        judged=judged,
        mask=mask,
        ideal_rel=ideal,
        n_rel=n_rel,
        n_judged_nonrel=n_nonrel,
        query_mask=query_mask,
    )
