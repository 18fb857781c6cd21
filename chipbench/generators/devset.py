"""Sparse-judgment dev set (MS MARCO passage dev shape) from a seed.

Each query has one binary judgment, a share of them a second; a judged
passage is retrieved somewhere in the top ``depth`` with probability
``retrieved_share`` (and lifted by ``relevant_boost``), else it is a
passage the run missed.  Scores sit on a ``score_grid`` grid.
"""

from __future__ import annotations

import numpy as np

from chipbench.generators import Collection


def generate(cfg: dict, seed: int) -> Collection:
    rng = np.random.default_rng(seed)
    nq, depth, grid = cfg["queries"], cfg["depth"], cfg["score_grid"]
    qid_ids = np.sort(rng.choice(cfg["query_id_space"], nq, replace=False))
    pids = rng.choice(cfg["collection_docs"], nq * depth + 2 * nq,
                      replace=False)
    ret = pids[:nq * depth].reshape(nq, depth)
    missed = pids[nq * depth:].reshape(nq, 2)
    scores = np.round(rng.normal(size=(nq, depth)) / grid) * grid
    # judgment slot 0 for every query, slot 1 for a share of them
    n_second = int(round(nq * cfg["second_judgment_share"]))
    has = np.zeros((nq, 2), dtype=bool)
    has[:, 0] = True
    has[rng.choice(nq, n_second, replace=False), 1] = True
    hit = rng.random((nq, 2)) < cfg["retrieved_share"]
    col = rng.integers(0, depth, (nq, 2))
    rows = np.arange(nq)[:, None].repeat(2, 1)
    lift = has & hit
    np.add.at(scores, (rows[lift], col[lift]), cfg["relevant_boost"])
    judged = np.where(hit, ret[rows, col], missed)
    qstr = qid_ids.astype(str)  # numeric order; rows follow the string order
    rows_order = np.argsort(qstr, kind="stable")
    qstr, ret, scores = qstr[rows_order], ret[rows_order], scores[rows_order]
    has, judged = has[rows_order], judged[rows_order]
    qrel = {}
    for q, slots, docs in zip(qstr.tolist(), has.tolist(),
                              judged.astype(str).tolist()):
        qrel[q] = {d: 1 for d, h in zip(docs, slots) if h}
    return Collection(qrel, np.repeat(qstr, depth),
                      ret.reshape(-1).astype(str),
                      scores.reshape(-1).astype(np.float32))
