"""End-to-end multi-device evaluation on the tokenized (``RunBuffer``) path.

One call that scales from a single CPU to a full TPU mesh with no per-query
Python.  It runs the evaluator's own path; only the placement differs:

    qrel/run files ──parse_run_arrays──► RunBuffer      (strings paid once)
    RunBuffer ──_class_batches(q_multiple=mesh size)──► one EvalBatch per
        depth class, its query axis padded to divide over the mesh
    each batch ──device_put(NamedSharding(mesh, P(axis)))──► one shard per
        device
    each class's packed core (full sort or top-k) ──shard_map──► [K, q_pad]
        rows, split over the devices on the query axis
    rows ──one host copy per class──► per-query dicts; aggregates on the host

Every measure is computed per query (each query's documents live in one
row), so a shard runs the same per-row program that ``evaluate_buffer``
runs on one device.  Per-query values equal ``evaluate_buffer``'s wherever
a shard's class shape gives XLA the same program (``tests/test_sharded.py``
asserts which cases are bit-identical and holds the rest to 1e-6).
Aggregates are the float32 mean of each measure's rows over the real
queries (:meth:`ShardedEvaluator._aggregates`).

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
from typing import Dict, List, NamedTuple, Sequence, Tuple

import numpy as np

import jax

from repro import obs
from repro.core import measures as M
from repro.core.evaluator import concat_run_buffers


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
    """Shard a :class:`RelevanceEvaluator`'s depth classes across a mesh.

    ``mesh`` must be 1-D (the query axis); it defaults to all visible
    devices.  The wrapped evaluator supplies the interned qrel state, the
    measure set, the relevance level, the depth classes and the routing
    between the full sort and the top-k kernel, so sharded results are
    directly comparable to its single-device ``evaluate_buffer``.
    """

    def __init__(self, evaluator, mesh=None):
        self.evaluator = evaluator
        self.mesh = mesh if mesh is not None else default_mesh()
        if len(self.mesh.axis_names) != 1:
            raise ValueError(
                f"need a 1-D query mesh, got axes {self.mesh.axis_names}")
        self.n_shards = int(np.prod(self.mesh.devices.shape))
        self.keys: Tuple[str, ...] = tuple(evaluator.measure_keys)

    @classmethod
    def from_files(cls, qrel_path: str, run_path: str, measures=None,
                   relevance_level: int = 1, mesh=None):
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
        return cls(ev, mesh=mesh), buf

    # -- the sharded computation ---------------------------------------------

    def _rows(self, bufs: Sequence) -> np.ndarray:
        """The ``[K, n]`` float32 rows of the non-empty ``bufs`` end to
        end: the evaluator's depth classes, each padded to divide over the
        mesh, measured by the evaluator's own ``_measure_rows`` on it."""
        ev = self.evaluator
        with obs.span("repro.evaluate"):
            with obs.span("repro.ingest"):
                classes, batches = ev._class_batches(
                    concat_run_buffers(bufs), q_multiple=self.n_shards)
            return ev._measure_rows(classes, batches, mesh=self.mesh)

    def _aggregates(self, rows: np.ndarray) -> Dict[str, float]:
        """Corpus means of ``[K, n]`` rows: float32 sum / n per measure,
        geometric where the measure is (``finalize_aggregates``)."""
        n = np.float32(rows.shape[1])
        return M.finalize_aggregates({
            k: float(row.sum(dtype=np.float32) / n)
            for k, row in zip(self.keys, rows)})

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
        if scores is not None:
            buf = buf.with_scores(scores)
        rows = self._rows([buf])
        return ShardedResult(self._rows_to_dicts(buf.qids, rows),
                             self._aggregates(rows))

    def evaluate_buffers(self, bufs: Sequence) -> List[ShardedResult]:
        """Evaluate several buffers in ONE sharded call (serve layer).

        The multi-device counterpart of
        :meth:`repro.core.RelevanceEvaluator.evaluate_buffers`: the buffers
        are stacked on the query axis and measured in one pass over their
        depth classes; per-query rows split back by each buffer's query
        count, and each request's aggregates come from its own rows.
        """
        bufs = list(bufs)
        nonempty = [b for b in bufs if len(b)]
        if not nonempty:
            return [ShardedResult({}, {}) for _ in bufs]
        rows = self._rows(nonempty)
        results: List[ShardedResult] = []
        lo = 0
        for buf in bufs:
            nq = len(buf.qids)
            if not nq:
                results.append(ShardedResult({}, {}))
                continue
            mine = rows[:, lo:lo + nq]
            lo += nq
            results.append(ShardedResult(self._rows_to_dicts(buf.qids, mine),
                                         self._aggregates(mine)))
        return results

    def evaluate_table(self, bufs: Sequence) -> np.ndarray:
        """Raw per-query measure rows for several buffers in ONE call.

        The sweep-tensor primitive behind
        :func:`repro.core.sweep.evaluate_sweep`'s ``backend="sharded"``
        path: buffers are stacked on the query axis and measured in one
        pass, and the unpadded ``[sum(len(b)), len(self.keys)]`` float32
        row block comes back with no per-query dict materialization — the
        caller reshapes it into the ``[K, Q, M]`` sweep tensor.
        """
        bufs = [b for b in bufs if len(b)]
        if not bufs:
            return np.empty((0, len(self.keys)), dtype=np.float32)
        return self._rows(bufs).T

    def _rows_to_dicts(self, qids, rows) -> Dict[str, Dict[str, float]]:
        cols = rows.tolist()
        return {qid: {k: col[i] for k, col in zip(self.keys, cols)}
                for i, qid in enumerate(qids)}
