"""Pallas TPU kernels for the framework's compute hot spots.

* ``topk``            — blocked top-K over the document axis (ranking sort).
* ``embedding_bag``   — scalar-prefetch gather + segment-sum (recsys tables).
* ``bucketing``       — power-of-two shape classes + retrace accounting.

Each kernel ships with a pure-jnp oracle in ``ref.py`` and a jit'd wrapper
in ``ops.py``.  Execution mode is backend-resolved at the first kernel call
(``ops.INTERPRET``: compiled on TPU, interpret elsewhere; override with
the ``REPRO_INTERPRET`` env var or per call) — see the ``ops`` module
docstring for the full precedence rules.
"""

from repro.kernels import bucketing  # noqa: F401  (dependency-free; first)
from repro.kernels import ops, ref  # noqa: F401
