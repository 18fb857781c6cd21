"""Gather-free bitonic sorting network for Pallas TPU kernels.

trec_eval's hot loop is a qsort over (score, docno); on TPU the equivalent is
a vectorized sorting network.  Arrays are 2-D ``[rows, n]``: every row is
sorted independently along the lane axis, so one call sorts a whole
``[8, n]`` sublane tile of queries.  Each compare-exchange stage pairs lane
``l`` with lane ``l XOR j``; the partner comes from two lane rotations
(``pltpu.roll``) and a select — no gathers, no lane reshapes, which Mosaic
does not lower.

Total order ("precedes"): x before y  iff  x.value > y.value, ties broken by
smaller index first — exactly trec_eval's score-desc / tiebreak-asc ranking
(see ``core.sorting``).  Indices must be unique within a row, which makes
the order strict.

All lengths must be powers of two (callers pad with -inf).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental.pallas import tpu as pltpu


def precedes(av, ai, bv, bi):
    """True where (av, ai) ranks before (bv, bi)."""
    return (av > bv) | ((av == bv) & (ai < bi))


def _stage(v, i, j: int, k: int, reverse: bool):
    """Compare-exchange at pair distance ``j`` within runs of length ``k``.

    A run's direction flips with bit ``log2(k)`` of the lane index; lane
    ``l`` keeps the better of itself and its partner ``l ^ j`` when it is
    the lower lane of an ascending (best-first) pair or the upper lane of a
    descending one.  ``reverse`` flips every direction, which sorts the
    rows worst-first.
    """
    n = v.shape[-1]
    lane = jax.lax.broadcasted_iota(jnp.int32, v.shape, 1)
    upper = (lane & j) != 0
    # jnp.roll semantics: roll(x, s)[l] == x[l - s]
    pv = jnp.where(upper, pltpu.roll(v, j, 1), pltpu.roll(v, n - j, 1))
    pi = jnp.where(upper, pltpu.roll(i, j, 1), pltpu.roll(i, n - j, 1))
    # bit log2(j) XOR bit log2(k) of the lane index (0 when k >= n)
    bits = (lane >> j.bit_length() - 1) ^ (lane >> k.bit_length() - 1)
    flip = (bits & 1) == (0 if reverse else 1)
    # keep the better element where ``flip`` is False, the worse where True
    take = ((flip & precedes(v, i, pv, pi))
            | (~flip & precedes(pv, pi, v, i)))
    return jnp.where(take, pv, v), jnp.where(take, pi, i)


def sort(v, i, reverse: bool = False):
    """Bitonic sort of each row of (values, indices) into precedes order
    (worst-first when ``reverse``)."""
    n = v.shape[-1]
    assert n & (n - 1) == 0, "bitonic sort needs a power-of-two length"
    k = 2
    while k <= n:
        j = k // 2
        while j >= 1:
            v, i = _stage(v, i, j, k, reverse)
            j //= 2
        k *= 2
    return v, i


def merge(v, i):
    """Bitonic merge of each row into precedes order.

    Each row must be bitonic with respect to the precedes order, e.g. the
    better half of a best-first run compared lane by lane with a
    worst-first run of the same length.
    """
    n = v.shape[-1]
    assert n & (n - 1) == 0, "bitonic merge needs a power-of-two length"
    j = n // 2
    while j >= 1:
        v, i = _stage(v, i, j, n, False)  # lane & n == 0: every run best-first
        j //= 2
    return v, i
