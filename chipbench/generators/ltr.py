"""Learning-to-rank collection (MSLR-WEB30K shape) from a seed.

Every query lists its own number of documents: the lengths follow a
lognormal, clipped to ``1..longest_list``, and hold exactly ``rows``
documents (a mean of ``rows / queries``); one list is the
``longest_list``.  The set of lengths is the same for every seed, which
deals them out to the queries (:func:`list_lengths`).  Every listed
document is judged, graded ``0..len(grade_p)-1``, and no other document
is: the list is the judged set.  A document scores ``relevance_weight x grade`` plus unit normal
noise, on a ``score_grid`` grid.  The set carries no document ids, so row
``j`` of a query's list is named ``D<j>`` (zero-padded), unique within
the query.
"""

from __future__ import annotations

from statistics import NormalDist

import numpy as np

from chipbench.generators import Collection


def list_lengths(cfg: dict, rng: np.random.Generator) -> np.ndarray:
    """Each query's list length: ``cfg["rows"]`` in all, each in
    ``1..cfg["longest_list"]``, one list the ``longest_list``.

    The lengths are a lognormal's quantiles at the midpoints of
    ``queries`` equal steps of probability, scaled (and clipped) to hold
    ``rows`` documents and rounded by largest remainder.  So every seed
    lists the same set of lengths, as the one published collection does;
    the seed deals them out to the queries."""
    nq, rows, longest = cfg["queries"], cfg["rows"], cfg["longest_list"]
    z = NormalDist().inv_cdf
    shape = np.exp(cfg["length_sigma"]
                   * np.array([z((i + 0.5) / nq) for i in range(nq - 1)]))
    rest = rows - longest  # what the other lists hold

    def fill(scale: float) -> np.ndarray:
        return np.clip(shape * scale, 1, longest)

    lo, hi = 0.0, float(rows)
    for _ in range(200):  # the scale at which the clipped lists hold rest
        mid = (lo + hi) / 2
        lo, hi = (mid, hi) if fill(mid).sum() < rest else (lo, mid)
    want = fill(hi)
    lengths = np.floor(want).astype(np.int64)
    short = rest - int(lengths.sum())
    room = np.where(lengths < longest, want - lengths, -1.0)
    lengths[np.argsort(-room, kind="stable")[:short]] += 1
    lengths = np.append(lengths, longest)
    assert int(lengths.sum()) == rows and 1 <= lengths.min()
    return lengths[rng.permutation(nq)]


def generate(cfg: dict, seed: int) -> Collection:
    rng = np.random.default_rng(seed)
    # query ids 1..queries, laid out in string order (the buffer's order)
    qstr = np.sort(np.arange(1, cfg["queries"] + 1).astype(str))
    lengths = list_lengths(cfg, rng)
    rows = int(lengths.sum())
    starts = np.cumsum(lengths) - lengths
    pos = np.arange(rows, dtype=np.int64) - np.repeat(starts, lengths)
    width = len(str(cfg["longest_list"] - 1))
    names = np.array([f"D{j:0{width}d}" for j in range(cfg["longest_list"])])
    grade_p = np.asarray(cfg["grade_p"], dtype=np.float64)
    grades = rng.choice(len(grade_p), rows, p=grade_p)
    grid = cfg["score_grid"]
    scores = np.round((cfg["relevance_weight"] * grades
                       + rng.normal(size=rows)) / grid) * grid
    docnos = names[pos]
    qrel = {}
    for q, lo, n in zip(qstr.tolist(), starts.tolist(), lengths.tolist()):
        qrel[q] = dict(zip(names[:n].tolist(), grades[lo:lo + n].tolist()))
    return Collection(qrel, np.repeat(qstr, lengths), docnos,
                      scores.astype(np.float32))
