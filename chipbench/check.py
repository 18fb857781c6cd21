"""The comparison that decides ``correct``: answers against the reference.

Answers are compared one by one: every query of a sampled answer, every
measure key the configuration names.  Three numbers come out, each held to
its own limit:

* ``max_abs_diff`` — the widest |program - reference| over the compared
  values (limit from the configuration's ``check``);
* ``wrong_answers`` — compared queries that are missing, extra, lack a key,
  carry another key, or carry a value that is no finite number (limit 0);
* ``unanswered`` — requests or calls of the window whose answer never came
  or came as an error (limit 0).
"""

from __future__ import annotations

import math
import random
from typing import Dict, Iterable, List, Mapping, Tuple

PerQuery = Mapping[str, Mapping[str, float]]


class Reading:
    """Running readings of one run's comparison."""

    def __init__(self) -> None:
        self.max_abs_diff = 0.0
        self.where = ""
        self.wrong_answers = 0
        self.compared_queries = 0
        self.compared_values = 0

    def add(self, got: PerQuery, want: PerQuery, keys: Iterable[str]) -> None:
        """Compare one answer (``got``) with the reference's (``want``)."""
        keys = tuple(keys)
        self.wrong_answers += len(set(got) ^ set(want))
        for qid, ref in want.items():
            row = got.get(qid)
            if row is None:
                continue
            self.compared_queries += 1
            if set(row) != set(keys):
                self.wrong_answers += 1
                continue
            for key in keys:
                value = row[key]
                if not (isinstance(value, (int, float))
                        and math.isfinite(value)):
                    self.wrong_answers += 1
                    break
                d = abs(value - ref[key])
                self.compared_values += 1
                if d > self.max_abs_diff:
                    self.max_abs_diff = d
                    self.where = f"{qid}/{key}: {value!r} vs {ref[key]!r}"

    def checks(self, limit: float, unanswered: int) -> Dict[str, Tuple[float, float]]:
        """Each number compared, with its limit."""
        return {"max_abs_diff": (self.max_abs_diff, limit),
                "wrong_answers": (self.wrong_answers, 0),
                "unanswered": (unanswered, 0)}


def passed(checks: Mapping[str, Tuple[float, float]]) -> bool:
    return bool(checks) and all(v <= lim for v, lim in checks.values())


class Reservoir:
    """``k`` items drawn uniformly from a stream of unknown length
    (Vitter's algorithm R), seeded: the same seed and stream keep the same
    items."""

    def __init__(self, k: int, seed: int):
        self.k = int(k)
        self.rng = random.Random(seed)
        self.items: List[Tuple[int, object]] = []
        self.seen = 0

    def offer(self, key: int, item) -> None:
        if len(self.items) < self.k:
            self.items.append((key, item))
        else:
            j = self.rng.randrange(self.seen + 1)
            if j < self.k:
                self.items[j] = (key, item)
        self.seen += 1


def run_dict(qids, docnos, scores) -> Dict[str, Dict[str, float]]:
    """Flat arrays → ``{qid: {docno: score}}`` in file order."""
    run: Dict[str, Dict[str, float]] = {}
    for q, d, s in zip(qids.tolist(), docnos.tolist(), scores.tolist()):
        run.setdefault(q, {})[d] = s
    return run
