"""Pure-jnp oracles for every Pallas kernel in this package."""

from __future__ import annotations

import jax
import jax.numpy as jnp


def topk_ref(scores: jax.Array, k: int):
    """lax.top_k: same tie semantics (equal values → lower index first)."""
    d = scores.shape[-1]
    if k <= d:
        return jax.lax.top_k(scores, k)
    v, i = jax.lax.top_k(scores, d)
    pad_v = jnp.full(scores.shape[:-1] + (k - d,), -jnp.inf, scores.dtype)
    pad_i = jnp.zeros(scores.shape[:-1] + (k - d,), jnp.int32)
    return jnp.concatenate([v, pad_v], -1), jnp.concatenate([i, pad_i], -1)


def embedding_bag_ref(table, indices, segment_ids, n_bags, weights=None):
    """jnp.take + segment_sum (the models/embedding.py reference path)."""
    rows = jnp.take(table, indices, axis=0)
    if weights is not None:
        rows = rows * weights[:, None]
    return jax.ops.segment_sum(rows, segment_ids, num_segments=n_bags)
