"""Deep-judged ad hoc collection (TREC Robust04 shape) from a seed.

Every topic gets a pool of judged documents graded ``0..len(grade_p)-1``
and a run of ``depth`` documents, ``judged_share`` of them judged.  Scores
sit on a ``score_grid`` grid, so ties across judged and unjudged documents
exercise trec_eval's docno tie-break.
"""

from __future__ import annotations

import numpy as np

from chipbench.generators import Collection


def topic_ids(cfg: dict) -> list:
    """The configuration's topic numbers as strings, ranges given as
    ``[first, last]`` pairs, ``skip`` left out."""
    ids = [t for lo, hi in cfg["topic_ranges"] for t in range(lo, hi + 1)
           if t not in cfg.get("skip_topics", ())]
    if len(ids) != cfg["topics"]:
        raise ValueError(f"topic ranges give {len(ids)} topics, "
                         f"the configuration states {cfg['topics']}")
    return [str(t) for t in ids]


def generate(cfg: dict, seed: int) -> Collection:
    rng = np.random.default_rng(seed)
    depth, judged = cfg["depth"], cfg["judged_per_topic"]
    grade_p = np.asarray(cfg["grade_p"], dtype=np.float64)
    grid = cfg["score_grid"]
    qrel, qids, docnos, scores = {}, [], [], []
    for qid in topic_ids(cfg):
        n_j = int(rng.integers(judged * 3 // 5, judged * 7 // 5))
        pool = rng.choice(cfg["collection_docs"], n_j + depth, replace=False)
        grades = rng.choice(len(grade_p), n_j, p=grade_p)
        names = np.char.add("D", np.char.zfill(pool.astype(str), 6))
        qrel[qid] = dict(zip(names[:n_j].tolist(), grades.tolist()))
        n_in = min(n_j, int(depth * cfg["judged_share"]))
        pick = rng.choice(n_j, n_in, replace=False)
        ret = np.concatenate([pick, np.arange(n_j, n_j + depth - n_in)])
        gain = np.concatenate([grades[pick], np.zeros(depth - n_in)])
        score = np.round((1.5 * gain + rng.normal(size=depth)) / grid) * grid
        qids.append(np.full(depth, qid))
        docnos.append(names[ret])
        scores.append(score.astype(np.float32))
    return Collection(qrel, np.concatenate(qids), np.concatenate(docnos),
                      np.concatenate(scores))
