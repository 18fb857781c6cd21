"""The paper's own workload as a first-class arch: the batched evaluator.

Shapes mirror the paper's benchmark grid corners (Fig. 1): the largest
configuration (10,000 queries × 1,000 docs) plus a deep-ranking cell
(1,024 queries × 65,536 candidate docs).  The "model" is the measure core
itself: queries shard over every mesh axis (they are independent), docs stay
local, and a single psum of sufficient statistics produces corpus means —
pytrec_eval's in-process evaluation at pod scale.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import measures as M
from repro.launch.api import ArchDef, ShapeSpec, StepBundle, register

MEASURES = ("map", "ndcg", "ndcg_cut", "P", "recall", "recip_rank",
            "Rprec", "bpref", "success", "map_cut")
_PARSED = M.parse_measures(MEASURES)


@dataclasses.dataclass(frozen=True)
class EvalConfig:
    name: str
    relevance_level: float = 1.0


SHAPES = {
    "eval_10k_1k": ShapeSpec("eval_10k_1k", "serve",
                             (("n_queries", 10_000), ("n_docs", 1000),
                              ("n_judged", 128))),
    "eval_1k_64k": ShapeSpec("eval_1k_64k", "serve",
                             (("n_queries", 1024), ("n_docs", 65_536),
                              ("n_judged", 128))),
}


def make_config(smoke: bool = False) -> EvalConfig:
    return EvalConfig(name="pytrec-eval-smoke" if smoke else "pytrec-eval")


def _make_step(cfg: EvalConfig, shape: ShapeSpec, mesh) -> StepBundle:
    q = shape.get("n_queries")
    d = shape.get("n_docs")
    j = shape.get("n_judged")
    if mesh is not None:
        # pad the query axis to a mesh multiple (query_mask covers the rest)
        m = int(mesh.devices.size)
        q = ((q + m - 1) // m) * m

    f32, i32, b_ = jnp.float32, jnp.int32, jnp.bool_
    sds = jax.ShapeDtypeStruct

    def eval_step(batch: M.EvalBatch):
        per_q = M.compute_measures(batch, _PARSED, cfg.relevance_level)
        return M.aggregate(per_q, batch.query_mask)

    batch_abs = M.EvalBatch(
        scores=sds((q, d), f32), tiebreak=sds((q, d), i32),
        rel=sds((q, d), f32), judged=sds((q, d), b_),
        mask=sds((q, d), b_),
        ideal_rel=sds((q, j), f32), n_rel=sds((q,), f32),
        n_judged_nonrel=sds((q,), f32), query_mask=sds((q,), b_))
    if mesh is not None:
        qaxes = tuple(mesh.axis_names)  # queries shard over EVERY axis
        in_specs = jax.tree.map(
            lambda s: P(qaxes, *([None] * (len(s.shape) - 1))), batch_abs)
        out_abs = jax.eval_shape(eval_step, batch_abs)
        in_sh = jax.tree.map(lambda s: NamedSharding(mesh, s), in_specs,
                             is_leaf=lambda x: isinstance(x, P))
        out_sh = jax.tree.map(lambda _: NamedSharding(mesh, P()), out_abs)
    else:
        in_sh = out_sh = None
    return StepBundle(eval_step, (batch_abs,), (in_sh,), out_sh)


ARCH = register(ArchDef(
    name="pytrec-eval",
    family="eval",
    shapes=SHAPES,
    make_config=make_config,
    make_step=_make_step,
    notes="The paper's contribution itself as a dry-run workload.",
))
