"""Distribution layer: sharding rules, collective helpers, compression.

Exports :func:`shard_map` (``jax.shard_map``, with ``check_vma``), so repro
code has one import site for it.
"""

from __future__ import annotations

import jax

shard_map = jax.shard_map


__all__ = ["shard_map", "ShardedEvaluator", "ShardedResult",
           "default_mesh", "select_backend"]


def __getattr__(name):  # lazy: sharded_evaluator imports kernels/measures
    if name in ("ShardedEvaluator", "ShardedResult", "default_mesh",
                "select_backend"):
        from repro.distributed import sharded_evaluator as _se

        return getattr(_se, name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
