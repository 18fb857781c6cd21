"""jit'd public wrappers for the Pallas kernels.

Execution-mode (``interpret``) resolution, in priority order:

1. an explicit ``interpret=`` argument on any wrapper call;
2. the module global :data:`INTERPRET`, read at **call time**.  Every
   kernel jit treats ``interpret`` as a *static* argument, so flipping the
   global never mutates a warm executable — it selects a different jit
   cache entry on the next call (both modes can live in the cache side by
   side, and flipping back reuses the earlier entries).  The one caveat:
   objects that snapshot the global at construction —
   :class:`repro.distributed.ShardedEvaluator` captures it into its
   compiled dispatch closure — keep their captured mode for their
   lifetime; rebuild them after flipping (``tests/test_kernels.py``
   pins both behaviours);
3. :data:`INTERPRET` starts unresolved (``None``) and is resolved once, at
   the first kernel call (:func:`interpret_mode`), by
   :func:`resolve_interpret` — never at import, because asking for the
   backend initializes it, and a process that initializes the TPU backend
   holds the chip (a router that imports this module must leave the chips
   to its workers).  The ``REPRO_INTERPRET`` environment variable
   wins when set (``1/true/yes/on/interpret`` → interpret,
   ``0/false/no/off/compiled`` → compiled), otherwise the JAX backend
   decides — **compiled (``False``) on TPU**, interpret everywhere else
   (the kernels are Mosaic-TPU programs; CPU/GPU hosts can only interpret
   them).  ``interpret=False`` on a non-TPU backend fails loudly at
   lowering time rather than silently falling back.

The compiled path is the default wherever it is valid; the
compiled-vs-interpret conformance gate in ``tests/test_kernels.py`` keeps
the two modes interchangeable (bit-identical when the resolved default
*is* the interpreter, within the documented ~1-ulp float tolerance when a
real TPU compiles them).
"""

from __future__ import annotations

import os
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import measures as M
from repro.kernels import fused_measures as _fm
from repro.kernels import topk as _topk
from repro.kernels import embedding_bag as _eb

_TRUTHY = ("1", "true", "yes", "on", "interpret")
_FALSY = ("0", "false", "no", "off", "compiled")


def resolve_interpret(env: Optional[str] = None,
                      backend: Optional[str] = None) -> bool:
    """Resolve the default Pallas execution mode.

    ``env`` defaults to ``os.environ["REPRO_INTERPRET"]`` and overrides
    everything when non-empty; ``backend`` defaults to
    ``jax.default_backend()``.  Returns True (interpret) unless the
    backend can actually compile the Mosaic-TPU kernels.

    >>> resolve_interpret(env="0")
    False
    >>> resolve_interpret(env="true")
    True
    >>> resolve_interpret(env="", backend="tpu")
    False
    >>> resolve_interpret(env="", backend="cpu")
    True
    """
    if env is None:
        env = os.environ.get("REPRO_INTERPRET")
    if env is not None and env.strip():
        flag = env.strip().lower()
        if flag in _TRUTHY:
            return True
        if flag in _FALSY:
            return False
        raise ValueError(
            f"REPRO_INTERPRET={env!r} not understood "
            f"(truthy: {_TRUTHY}, falsy: {_FALSY})")
    if backend is None:
        backend = jax.default_backend()
    return backend != "tpu"


#: process-wide default execution mode; ``None`` until the first kernel call
#: resolves it (see the module docstring for the full precedence rules)
INTERPRET: Optional[bool] = None


def interpret_mode() -> bool:
    """The default execution mode, resolved on first use."""
    global INTERPRET
    if INTERPRET is None:
        INTERPRET = resolve_interpret()
    return INTERPRET


FUSED_COLUMNS: Tuple[str, ...] = tuple(_fm.COLUMNS)


def topk(scores, k, block_d=None, interpret=None):
    return _topk.topk(scores, k, block_d=block_d,
                      interpret=(interpret_mode() if interpret is None
                                 else interpret))


def embedding_bag(table, indices, segment_ids, n_bags, weights=None,
                  interpret=None):
    return _eb.embedding_bag(
        table, indices, segment_ids, n_bags, weights=weights,
        interpret=interpret_mode() if interpret is None else interpret)


def fused_measures_cols(rel_sorted, judged_sorted, scalars,
                        relevance_level=1.0, block_q=None, interpret=None):
    """All fused measure columns; ``block_q=None`` → roofline-autotuned."""
    return _fm.fused_measures(
        rel_sorted, judged_sorted, scalars, block_q=block_q,
        relevance_level=relevance_level,
        interpret=interpret_mode() if interpret is None else interpret)


def make_scalars(n_rel, n_judged_nonrel, ideal_rel):
    """Pack the per-query scalar block consumed by the fused kernel."""
    q = n_rel.shape[0]
    j = ideal_rel.shape[-1]
    ranks = jnp.arange(1, j + 1, dtype=jnp.float32)
    gains = jnp.maximum(ideal_rel, 0.0) * M.discount_table(j)
    idcg_full = jnp.sum(gains, axis=-1)
    scal = [n_rel, n_judged_nonrel, idcg_full]
    for k in _fm.CUTOFFS:
        within = (ranks <= k).astype(jnp.float32)
        scal.append(jnp.sum(gains * within, axis=-1))
    out = jnp.stack(scal, axis=-1)  # [Q, 12]
    return jnp.pad(out, ((0, 0), (0, 16 - out.shape[-1])))


def evaluate_fused(batch: M.EvalBatch, relevance_level: float = 1.0,
                   block_q=None, interpret=None, judged_only: bool = False):
    """EvalBatch → dict of per-query measures via the fused kernel path.

    Sort with the XLA multi-key sort (exact trec_eval order), then one fused
    VMEM pass for all measures.  This is the optimized beyond-paper engine;
    `core.measures.compute_measures` is the paper-faithful reference engine.
    ``judged_only`` drops unjudged retrieved docs before ranking
    (trec_eval ``-J``) — they sort to the tail as inert padding, so the
    fused columns need no changes.
    """
    s = M.sort_batch(batch, relevance_level, judged_only)
    scal = make_scalars(batch.n_rel, batch.n_judged_nonrel, batch.ideal_rel)
    cols = fused_measures_cols(s.rel, s.judged, scal,
                               relevance_level=relevance_level,
                               block_q=block_q, interpret=interpret)
    qm = batch.query_mask
    zero = jnp.zeros_like(cols[:, 0])
    return {
        name: jnp.where(qm, cols[:, i], zero)
        for i, name in enumerate(FUSED_COLUMNS)
    }
