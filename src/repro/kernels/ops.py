"""jit'd public wrappers for the Pallas kernels.

Execution-mode (``interpret``) resolution, in priority order:

1. an explicit ``interpret=`` argument on any wrapper call;
2. the module global :data:`INTERPRET`, read at **call time**.  Every
   kernel jit treats ``interpret`` as a *static* argument, so flipping the
   global never mutates a warm executable — it selects a different jit
   cache entry on the next call (both modes can live in the cache side by
   side, and flipping back reuses the earlier entries);
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
from typing import Optional

import jax

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


def topk(scores, k, block_d=None, interpret=None):
    return _topk.topk(scores, k, block_d=block_d,
                      interpret=(interpret_mode() if interpret is None
                                 else interpret))


def embedding_bag(table, indices, segment_ids, n_bags, weights=None,
                  interpret=None):
    return _eb.embedding_bag(
        table, indices, segment_ids, n_bags, weights=weights,
        interpret=interpret_mode() if interpret is None else interpret)
