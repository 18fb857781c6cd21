"""Collection generators: each turns a configuration's shape and a seed
into qrels and a run, found by the configuration's ``generator`` key.

A run's rows come grouped by query, queries in string order: the order in
which ``RelevanceEvaluator.buffer_from_arrays`` lays a run out, and so the
order of the fresh scores a caller passes to ``evaluate_buffer``."""

from typing import Dict, NamedTuple

import numpy as np


class Collection(NamedTuple):
    """Qrels and one run as flat arrays, in the run's file order."""

    qrel: Dict[str, Dict[str, int]]
    qids: np.ndarray
    docnos: np.ndarray
    scores: np.ndarray
