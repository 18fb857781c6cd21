"""Streaming (in-loop) evaluation: metrics as scan-carry sufficient statistics.

The paper's Q-learning demo computes NDCG on every RL step; at pod scale the
equivalent is computing ranking metrics inside a jitted training/serving loop
over many microbatches without a host round-trip.  Every trec_eval measure in
``core.measures`` is a per-query scalar, so the sufficient statistic for the
corpus mean is just (sum, count) — perfectly shardable: each device accumulates
its local queries, one ``psum`` at the end.

Usage inside a scan/loop::

    state = metric_init(("ndcg", "recip_rank"))
    ...
    state = metric_update(state, batch)          # batch: measures.EvalBatch
    ...
    means = metric_finalize(state)               # dict of scalars

All three are pure and jit/scan/shard_map friendly.
"""

from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from repro.core import measures as M

MetricState = Dict[str, jax.Array]  # keys + "__count"


def metric_init(measure_names: Tuple[str, ...]) -> MetricState:
    keys = M.measure_keys(measure_names)
    state = {k: jnp.zeros((), dtype=jnp.float32) for k in keys}
    state["__count"] = jnp.zeros((), dtype=jnp.float32)
    return state


def metric_update(
    state: MetricState,
    batch: M.EvalBatch,
    measure_names: Tuple[str, ...],
    relevance_level: float = 1.0,
) -> MetricState:
    parsed = M.parse_measures(measure_names)
    per_query = M.compute_measures(batch, parsed, relevance_level)
    qm = batch.query_mask.astype(jnp.float32)
    new = dict(state)
    for k, v in per_query.items():
        new[k] = state[k] + jnp.sum(v * qm)
    new["__count"] = state["__count"] + jnp.sum(qm)
    return new


def metric_update_run(
    state: MetricState,
    evaluator,
    buf,
    scores,
    measure_names: Tuple[str, ...],
    relevance_level: float | None = None,
) -> MetricState:
    """In-loop update from a pre-tokenized ``RunBuffer`` + fresh scores.

    The session fast path for evaluating the *same* collection every step:
    ``evaluator.tokenize_run`` (or ``buffer_from_tokens``) paid the string
    cost once; each step here is a numeric scatter
    (``evaluator.batch_from_buffer``) plus the jitted measure core.
    ``scores`` is the flat per-document score array in the buffer's query
    order.  ``relevance_level`` defaults to the evaluator's own level — the
    buffer's qrel-side statistics (R, judged-non-relevant) were counted at
    that level, so overriding it only makes sense for matching evaluators.
    """
    if relevance_level is None:
        relevance_level = evaluator.relevance_level
    batch = evaluator.batch_from_buffer(buf, scores)
    return metric_update(state, batch, measure_names, relevance_level)


def metric_finalize(state: MetricState, axis_name: str | None = None) -> Dict[str, jax.Array]:
    """Means over all queries; cross-device reduce if ``axis_name`` given."""
    count = state["__count"]
    sums = {k: v for k, v in state.items() if k != "__count"}
    if axis_name is not None:
        count = jax.lax.psum(count, axis_name)
        sums = {k: jax.lax.psum(v, axis_name) for k, v in sums.items()}
    denom = jnp.maximum(count, 1.0)
    return {k: v / denom for k, v in sums.items()}


# ---------------------------------------------------------------------------
# Cheap in-loop metrics from gold ranks (LM / sequential recsys path).
# ---------------------------------------------------------------------------


def rank_metrics(gold_ranks: jax.Array, mask: jax.Array | None = None,
                 ks: Tuple[int, ...] = (1, 5, 10)) -> Dict[str, jax.Array]:
    """MRR + success@k from 1-based gold-item ranks (no sort needed).

    This is the single-relevant-document special case of trec_eval measures:
    recip_rank == 1/rank, success_k == rank <= k, ndcg == 1/log2(rank+1).
    Used for next-token / next-item evaluation fused into the train step.
    """
    r = gold_ranks.astype(jnp.float32)
    if mask is None:
        mask = jnp.ones_like(r, dtype=bool)
    m = mask.astype(jnp.float32)
    n = jnp.maximum(jnp.sum(m), 1.0)
    out = {
        "recip_rank": jnp.sum(m / r) / n,
        "ndcg": jnp.sum(m / jnp.log2(r + 1.0)) / n,
    }
    for k in ks:
        out[f"success_{k}"] = jnp.sum(m * (r <= k)) / n
    return out
