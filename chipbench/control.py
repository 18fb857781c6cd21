"""The control: the plain reference in the program's place, in bfloat16.

The program computes in float32.  The precision below it, the step that
would tempt a later change, is bfloat16: the control rounds the scores and
every arithmetic result of the reference to bfloat16
(``reference.evaluate(..., rnd=bf16)``) and its answers go through the same
comparison as the program's.  It has to come out as not correct; its
smallest reading over seeds is the upper reading a limit is set below.
"""

from __future__ import annotations

import ml_dtypes

from chipbench import check, reference


def bf16(x: float) -> float:
    return float(ml_dtypes.bfloat16(x))


def reading(cfg: dict, coll, score_sets) -> check.Reading:
    """The comparison's readings with the control's answers for each of
    ``score_sets`` (flat scores in the run's order) in the program's place."""
    out = check.Reading()
    keys = cfg["keys"]
    for scores in score_sets:
        run = check.run_dict(coll.qids, coll.docnos, scores)
        want = reference.evaluate(run, coll.qrel, cfg["reference_measures"])
        got = reference.evaluate(run, coll.qrel, cfg["reference_measures"],
                                 rnd=bf16)
        out.add({q: {k: v[k] for k in keys} for q, v in got.items()},
                want, keys)
    return out
