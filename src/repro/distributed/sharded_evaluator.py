"""End-to-end multi-device evaluation on the tokenized (``RunBuffer``) path.

This is the ROADMAP's "sharded evaluation builds on the tokenized ingest
path" milestone: one call that scales from a single CPU to a full TPU mesh
with no per-query Python.  The pipeline is

    qrel/run files ──parse_run_arrays──► RunBuffer      (strings paid once)
    RunBuffer ──batch_from_buffer(q_multiple=mesh)──► EvalBatch  (padded)
    EvalBatch ──shard_map over the query axis──► per-device shard
    shard: sort_batch → make_scalars → fused Pallas kernel (all measures)
    aggregates: metric_update_cols → metric_finalize(axis_name)  (one psum)

Per-query results come back as one ``[Q, K]`` gather (out_spec sharded over
the query axis); aggregates are psum-reduced sufficient statistics, so the
collective payload is K+1 scalars per device regardless of corpus size.

Bit-identity: every per-query measure is computed row-independently (each
query's documents live in one row), so sharding the query axis cannot change
any value — mesh sizes 1, 2, 4, ... produce byte-identical outputs for the
same input (``tests/test_sharded.py`` asserts this on synthetic data).
Against :meth:`RelevanceEvaluator.evaluate` the contract is: the fused
kernel divides exactly where ``core.measures`` divides (see
``kernels.fused_measures._sdiv``), so results are bit-identical whenever the
per-rank cumulative sums are exactly representable (integer judgments at
fixture scale — the conformance acceptance tests); on arbitrary float gains
the kernel's log-step VMEM scan may associate a long sum differently from
``jnp.cumsum`` and drift by ~1 ulp (observed: ``ndcg_cut_k`` at 1.2e-7).
Measures without a fused-kernel column (``num_ret``, ``num_rel``,
``iprec_at_recall_*``, non-standard cutoffs) fall back to the reference
measure core inside the same shard and match it exactly.

Usage::

    from repro.core import RelevanceEvaluator
    from repro.distributed.sharded_evaluator import ShardedEvaluator

    ev = RelevanceEvaluator(qrel, {"map", "ndcg"})
    sev = ShardedEvaluator(ev)            # 1-D mesh over jax.devices()
    result = sev.evaluate(run)            # or .evaluate_buffer(buf, scores)
    result.per_query["q1"]["map"], result.aggregates["map"]
"""

from __future__ import annotations

import functools
from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core import measures as M
from repro.core import streaming
from repro.core.evaluator import concat_run_buffers
from repro.distributed import shard_map
from repro.kernels import bucketing, ops


class ShardedResult(NamedTuple):
    """Per-query results (pytrec_eval layout) + corpus-mean aggregates."""

    per_query: Dict[str, Dict[str, float]]
    aggregates: Dict[str, float]


@functools.lru_cache(maxsize=None)
def default_mesh(axis_name: str = "data"):
    """One shared 1-D mesh spanning every visible device.

    Memoized so every :class:`ShardedEvaluator` built without an explicit
    mesh (each serve-layer collection, every CLI ``--sharded`` call in a
    process) reuses ONE mesh object — and therefore one jit cache entry per
    batch geometry — instead of re-creating meshes per collection.
    """
    return jax.make_mesh((len(jax.devices()),), (axis_name,))


def select_backend(backend: str = "auto") -> str:
    """Resolve an evaluation-backend name to ``"single"`` or ``"sharded"``.

    ``"auto"`` picks the sharded pipeline exactly when more than one device
    is visible — on a 1-device host the single-device evaluator computes the
    same values without the shard_map dispatch overhead.  The serve layer
    calls this once per collection registration.
    """
    if backend in ("single", "sharded"):
        return backend
    if backend != "auto":
        raise ValueError(f"unknown backend {backend!r} "
                         "(expected auto|single|sharded)")
    return "sharded" if len(jax.devices()) > 1 else "single"


class ShardedEvaluator:
    """Shard a :class:`RelevanceEvaluator`'s batches across a device mesh.

    ``mesh`` must be 1-D (the query axis); it defaults to all visible
    devices.  The wrapped evaluator supplies the interned qrel state, the
    measure set, and the relevance level, so sharded results are directly
    comparable to its single-device ``evaluate``.

    ``interpret`` forwards to the Pallas kernel.  The default SNAPSHOTS the
    module-wide default (``kernels.ops.interpret_mode()``, backend-resolved:
    compiled on TPU, interpret elsewhere) at *construction* time — the value
    is baked into the compiled dispatch closure, so flipping ``ops.INTERPRET``
    afterwards does not affect an existing instance.  Build a new
    ``ShardedEvaluator`` (or pass ``interpret=`` explicitly) to change
    mode; see the ``kernels.ops`` docstring for the full precedence rules.
    """

    def __init__(self, evaluator, mesh=None, interpret: Optional[bool] = None):
        self.evaluator = evaluator
        self.mesh = mesh if mesh is not None else default_mesh()
        if len(self.mesh.axis_names) != 1:
            raise ValueError(
                f"need a 1-D query mesh, got axes {self.mesh.axis_names}")
        self.axis_name = self.mesh.axis_names[0]
        self.n_shards = int(np.prod(self.mesh.devices.shape))
        self.interpret = (ops.interpret_mode() if interpret is None
                          else interpret)
        self.keys: Tuple[str, ...] = tuple(evaluator.measure_keys)
        # Measures the fused kernel does not emit ride the reference core.
        self._rest = tuple(k for k in self.keys if k not in ops.FUSED_COLUMNS)
        self._dispatch = self._build_dispatch()

    @classmethod
    def from_files(cls, qrel_path: str, run_path: str, measures=None,
                   relevance_level: int = 1, mesh=None,
                   interpret: Optional[bool] = None):
        """Build (ShardedEvaluator, RunBuffer) straight from TREC files.

        The run file is parsed with ``trec.parse_run_arrays`` into flat
        arrays and tokenized once via ``buffer_from_arrays`` — the
        dict-of-dicts representation is never materialized.
        """
        from repro.core import RelevanceEvaluator, supported_measures, trec

        qrel = trec.load_qrel(qrel_path)
        ev = RelevanceEvaluator(qrel, measures or supported_measures,
                                relevance_level=relevance_level)
        buf = ev.buffer_from_arrays(*trec.load_run_arrays(run_path))
        return cls(ev, mesh=mesh, interpret=interpret), buf

    # -- the sharded computation ---------------------------------------------

    def _build_dispatch(self):
        level = self.evaluator.relevance_level
        judged_only = self.evaluator.judged_docs_only
        keys = self.keys
        rest = self._rest
        rest_parsed = M.parse_measures(rest) if rest else ()
        interpret = self.interpret
        axis = self.axis_name

        def local_eval(batch: M.EvalBatch):
            # One shard: rank locally, one fused VMEM pass for all standard
            # measures, reference core for the remainder.  Under
            # judged_docs_only the sort drops unjudged docs to the tail as
            # inert padding, so the fused columns stay correct unchanged.
            bucketing.record_trace("sharded_dispatch")  # once per signature
            s = M.sort_batch(batch, level, judged_only)
            scal = ops.make_scalars(batch.n_rel, batch.n_judged_nonrel,
                                    batch.ideal_rel)
            cols = ops.fused_measures_cols(s.rel, s.judged, scal,
                                           relevance_level=level,
                                           interpret=interpret)
            qm = batch.query_mask
            zero = jnp.zeros_like(batch.n_rel)
            per_query = {
                name: jnp.where(qm, cols[:, i], zero)
                for i, name in enumerate(ops.FUSED_COLUMNS) if name in keys
            }
            if rest_parsed:
                per_query.update(M.compute_measures(batch, rest_parsed, level,
                                                    judged_only))
            stacked = jnp.stack([per_query[k] for k in keys], axis=-1)
            # Aggregates: (sum, count) sufficient statistics, one psum.
            state = {k: jnp.zeros((), jnp.float32) for k in keys}
            state["__count"] = jnp.zeros((), jnp.float32)
            state = streaming.metric_update_cols(state, per_query, qm)
            aggs = streaming.metric_finalize(state, axis_name=axis)
            return stacked, aggs

        qspec = P(axis)
        dspec = P(axis, None)
        in_specs = M.EvalBatch(
            scores=dspec, tiebreak=dspec, rel=dspec, judged=dspec, mask=dspec,
            ideal_rel=dspec, n_rel=qspec, n_judged_nonrel=qspec,
            query_mask=qspec)
        return jax.jit(shard_map(
            local_eval, mesh=self.mesh, in_specs=(in_specs,),
            out_specs=(dspec, P()), check_vma=False))

    # -- entry points ---------------------------------------------------------

    def evaluate(self, run_or_buffer) -> ShardedResult:
        """Evaluate a ``{qid: {docno: score}}`` run or a ``RunBuffer``."""
        from repro.core.evaluator import RunBuffer

        if isinstance(run_or_buffer, RunBuffer):
            return self.evaluate_buffer(run_or_buffer)
        return self.evaluate_buffer(
            self.evaluator.tokenize_run(run_or_buffer))

    def evaluate_buffer(self, buf, scores=None) -> ShardedResult:
        """Evaluate a pre-tokenized buffer (optionally with fresh scores)."""
        if not len(buf):
            return ShardedResult({}, {})
        batch = self.evaluator.batch_from_buffer(
            buf, scores, q_multiple=self.n_shards)
        stacked, aggs = self._dispatch(batch)
        nq = len(buf.qids)
        table = np.asarray(stacked)[:nq]
        per_query = self._rows_to_dicts(buf.qids, table)
        return ShardedResult(per_query, M.finalize_aggregates(
            {k: float(v) for k, v in aggs.items()}))

    def evaluate_buffers(self, bufs: Sequence) -> List[ShardedResult]:
        """Evaluate several buffers in ONE sharded dispatch (serve layer).

        The multi-device counterpart of
        :meth:`repro.core.RelevanceEvaluator.evaluate_buffers`: the buffers
        are stacked on the query axis, padded to the mesh, and shard_mapped
        once; per-query rows split back by each buffer's query count.  The
        device-side psum aggregates cover the whole coalesced batch, so
        per-request aggregates are recomputed on host from each request's
        rows with the same (sum / count) formula.
        """
        bufs = list(bufs)
        if not bufs:
            return []
        nonempty = [b for b in bufs if len(b)]
        if not nonempty:
            return [ShardedResult({}, {}) for _ in bufs]
        big = concat_run_buffers(nonempty)
        batch = self.evaluator.batch_from_buffer(
            big, q_multiple=self.n_shards)
        stacked, _ = self._dispatch(batch)
        table = np.asarray(stacked)[:len(big.qids)]
        results: List[ShardedResult] = []
        lo = 0
        for buf in bufs:
            nq = len(buf.qids)
            rows = table[lo:lo + nq]
            lo += nq
            if not nq:
                results.append(ShardedResult({}, {}))
                continue
            aggs = {k: float(rows[:, j].sum(dtype=np.float32) / np.float32(nq))
                    for j, k in enumerate(self.keys)}
            results.append(ShardedResult(
                self._rows_to_dicts(buf.qids, rows),
                M.finalize_aggregates(aggs)))
        return results

    def evaluate_table(self, bufs: Sequence) -> np.ndarray:
        """Raw per-query measure rows for several buffers in ONE dispatch.

        The sweep-tensor primitive behind
        :func:`repro.core.sweep.evaluate_sweep`'s ``backend="sharded"``
        path: buffers are stacked on the query axis, padded to the mesh,
        shard_mapped once, and the unpadded ``[sum(len(b)), len(self.keys)]``
        float32 row block comes back with no per-query dict materialization
        — the caller reshapes it into the ``[K, Q, M]`` sweep tensor.
        """
        bufs = [b for b in bufs if len(b)]
        if not bufs:
            return np.empty((0, len(self.keys)), dtype=np.float32)
        big = concat_run_buffers(bufs) if len(bufs) > 1 else bufs[0]
        batch = self.evaluator.batch_from_buffer(
            big, q_multiple=self.n_shards)
        stacked, _ = self._dispatch(batch)
        return np.asarray(stacked)[:len(big.qids)]

    def _rows_to_dicts(self, qids, table) -> Dict[str, Dict[str, float]]:
        return {
            qid: {k: float(table[i, j]) for j, k in enumerate(self.keys)}
            for i, qid in enumerate(qids)
        }
