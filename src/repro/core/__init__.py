"""Device-resident IR evaluation (the paper's contribution, on TPU).

Public API mirrors pytrec_eval:

* :class:`RelevanceEvaluator` — dict-in / dict-out evaluation.
* :data:`supported_measures` — measure families available.
* ``registry`` — the declarative measure table (both dialects) everything
  else derives from.
* ``measures`` / ``streaming`` — batched + in-loop device entry points.
"""

from repro.core.evaluator import (RelevanceEvaluator, RunBuffer,
                                  aggregate_results, concat_run_buffers)
from repro.core.measures import (
    AGGREGATE_ONLY_MEASURES,
    DEFAULT_CUTOFFS,
    DepthClass,
    GM_MIN,
    SUPPORTED_MEASURES as supported_measures,
    EvalBatch,
    FlatLayout,
    batch_from_dense,
    compute_measures,
    compute_measures_topk,
    finalize_aggregates,
    flat_layout,
    measure_keys,
    parse_measures,
)
from repro.core.registry import MeasureError, MeasureSpec, REGISTRY
from repro.core.sweep import SweepResult, evaluate_sweep
from repro.core import registry, streaming, trec, sorting

__all__ = [
    "RelevanceEvaluator",
    "RunBuffer",
    "SweepResult",
    "aggregate_results",
    "concat_run_buffers",
    "evaluate_sweep",
    "flat_layout",
    "DepthClass",
    "FlatLayout",
    "supported_measures",
    "AGGREGATE_ONLY_MEASURES",
    "DEFAULT_CUTOFFS",
    "GM_MIN",
    "EvalBatch",
    "batch_from_dense",
    "compute_measures",
    "compute_measures_topk",
    "finalize_aggregates",
    "measure_keys",
    "parse_measures",
    "MeasureError",
    "MeasureSpec",
    "REGISTRY",
    "registry",
    "streaming",
    "trec",
    "sorting",
]
