"""Ahead-of-time compiles for a described TPU v5e chip (no chip needed).

The TPU compiler is installed wherever JAX is, and it compiles for a chip
that is described rather than attached.  Mosaic refuses here exactly what
it would refuse on the chip (unaligned block shapes, primitives without a
TPU lowering), so these compiles guard both Pallas kernels and the measure
core at the sizes users run, at no chip time.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and every pytest worker
imports this file.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import measures as M
from repro.kernels import autotune, fused_measures, ops, topk


@pytest.fixture(scope="module")
def one_chip():
    import os

    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure: cannot describe
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


def _spec(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _batch_spec(sharding, q, d, j):
    f32 = lambda *s: _spec(sharding, s)  # noqa: E731
    return M.EvalBatch(
        scores=f32(q, d), tiebreak=_spec(sharding, (q, d), jnp.int32),
        rel=f32(q, d), judged=_spec(sharding, (q, d), jnp.bool_),
        mask=_spec(sharding, (q, d), jnp.bool_), ideal_rel=f32(q, j),
        n_rel=f32(q), n_judged_nonrel=f32(q),
        query_mask=_spec(sharding, (q,), jnp.bool_))


def test_fused_measures_compiles_for_v5e(one_chip):
    q, d = 256, 1024
    block_q = autotune.block_q_for(q, d)
    args = (_spec(one_chip, (q, d)), _spec(one_chip, (q, d)),
            _spec(one_chip, (q, 16)))
    compiled = jax.jit(lambda r, j, s: fused_measures.fused_measures(
        r, j, s, block_q=block_q, interpret=False)).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("k", [10, 1000])
def test_topk_compiles_for_v5e(one_chip, k):
    compiled = jax.jit(lambda s: topk.topk(s, k, interpret=False)).lower(
        _spec(one_chip, (8, 65536))).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_measure_core_compiles_for_v5e(one_chip):
    parsed = M.parse_measures(("map", "bpref", "ndcg", "Rprec", "recip_rank",
                               "P", "recall", "ndcg_cut"))
    compiled = M.compute_measures_jit.lower(
        _batch_spec(one_chip, 256, 1024, 2048), parsed, 1.0,
        False).compile()
    # the full-sort core is plain XLA: no kernel expected
    assert "tpu_custom_call" not in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < 64 << 20


def test_topk_measure_core_compiles_for_v5e(one_chip, monkeypatch):
    """The depth-bounded route at the MS MARCO dev shape runs the kernel."""
    monkeypatch.setattr(ops, "INTERPRET", False)
    parsed = M.parse_measures(("nDCG@10", "P@10"))
    compiled = M.compute_measures_topk_jit.lower(
        _batch_spec(one_chip, 8192, 1024, 8), parsed, 1.0, False).compile()
    assert "tpu_custom_call" in compiled.as_text()
