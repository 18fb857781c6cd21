"""Pallas kernels vs pure-jnp oracles (interpret mode), shape/dtype sweeps."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import measures as M
from repro.kernels import ops, ref

RNG = np.random.default_rng(7)


@pytest.mark.parametrize("q,d,k", [
    (1, 257, 10), (3, 1000, 100), (2, 4096, 1000), (5, 64, 64), (1, 10000, 13),
])
def test_topk_matches_lax(q, d, k):
    scores = jnp.asarray(RNG.standard_normal((q, d)).astype(np.float32))
    v, i = ops.topk(scores, k)
    rv, ri = ref.topk_ref(scores, k)
    kk = min(k, d)
    np.testing.assert_allclose(np.asarray(v)[:, :kk], np.asarray(rv)[:, :kk])
    np.testing.assert_array_equal(np.asarray(i)[:, :kk],
                                  np.asarray(ri)[:, :kk])


@pytest.mark.parametrize("dtype", [np.float32, np.float64])
def test_topk_ties_break_by_index(dtype):
    # heavy ties: the kernel must match lax.top_k's lower-index-first rule
    scores = jnp.asarray(
        RNG.choice(np.array([0.0, 1.0, 2.0], np.float32), size=(4, 2000)))
    v, i = ops.topk(scores, 50)
    rv, ri = jax.lax.top_k(scores, 50)
    np.testing.assert_array_equal(np.asarray(i), np.asarray(ri))


@pytest.mark.parametrize("q,d,k", [(13, 3000, 100), (9, 1500, 10),
                                   (17, 700, 300)])
def test_topk_multirow_blocks_ragged_q_with_ties(q, d, k):
    """8-row blocks over a query count that is not a multiple of 8, with
    heavy ties and -inf (masked) entries: indices equal lax.top_k's."""
    scores = RNG.choice(np.array([0.0, 1.0, 2.0, -np.inf], np.float32),
                        size=(q, d), p=(0.5, 0.3, 0.1, 0.1))
    v, i = ops.topk(jnp.asarray(scores), k)
    rv, ri = jax.lax.top_k(jnp.asarray(scores), k)
    np.testing.assert_array_equal(np.asarray(i), np.asarray(ri))
    np.testing.assert_array_equal(np.asarray(v), np.asarray(rv))


def test_topk_handles_short_rows():
    scores = jnp.asarray(RNG.standard_normal((2, 5)).astype(np.float32))
    v, i = ops.topk(scores, 8)
    rv, ri = ref.topk_ref(scores, 8)
    np.testing.assert_allclose(np.asarray(v)[:, :5], np.asarray(rv)[:, :5])


@pytest.mark.parametrize("q,d", [(3, 64), (8, 200), (13, 1024), (1, 4096)])
def test_fused_measures_matches_ref(q, d):
    scores = jnp.asarray(RNG.standard_normal((q, d)).astype(np.float32))
    rel = jnp.asarray(RNG.integers(0, 4, (q, d)).astype(np.float32))
    judged = jnp.asarray(RNG.random((q, d)) < 0.6)
    batch = M.batch_from_dense(scores, rel, judged=judged)
    s = M.sort_batch(batch)
    scal = ops.make_scalars(batch.n_rel, batch.n_judged_nonrel,
                            batch.ideal_rel)
    got = ops.fused_measures_cols(s.rel, s.judged, scal)
    want = ref.fused_measures_ref(s.rel, s.judged, scal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-4, rtol=2e-4)


@pytest.mark.parametrize("q,block_q", [(21, 8), (37, 16)])
def test_fused_measures_multirow_blocks_ragged_q(q, block_q):
    """Several grid steps of several rows each, plus a padded last block."""
    d = 512
    scores = jnp.asarray(RNG.standard_normal((q, d)).astype(np.float32))
    rel = jnp.asarray(RNG.integers(0, 3, (q, d)).astype(np.float32))
    judged = jnp.asarray(RNG.random((q, d)) < 0.5)
    batch = M.batch_from_dense(scores, rel, judged=judged)
    s = M.sort_batch(batch)
    scal = ops.make_scalars(batch.n_rel, batch.n_judged_nonrel,
                            batch.ideal_rel)
    got = ops.fused_measures_cols(s.rel, s.judged, scal, block_q=block_q)
    want = ref.fused_measures_ref(s.rel, s.judged, scal)
    assert got.shape == (q, 64)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-4, rtol=2e-4)


def test_fused_evaluate_matches_measure_core():
    q, d = 9, 300
    scores = jnp.asarray(RNG.standard_normal((q, d)).astype(np.float32))
    rel = jnp.asarray(RNG.integers(0, 3, (q, d)).astype(np.float32))
    batch = M.batch_from_dense(scores, rel)
    fused = ops.evaluate_fused(batch)
    parsed = M.parse_measures(("map", "ndcg", "ndcg_cut", "P", "recall",
                               "recip_rank", "Rprec", "bpref", "success"))
    want = M.compute_measures(batch, parsed)
    for k, v in want.items():
        np.testing.assert_allclose(np.asarray(fused[k]), np.asarray(v),
                                   atol=2e-4, rtol=2e-4, err_msg=k)


@pytest.mark.parametrize("v,e,b,l", [(30, 8, 4, 20), (100, 32, 10, 64),
                                     (11, 16, 3, 7)])
def test_embedding_bag_matches_ref(v, e, b, l):
    table = jnp.asarray(RNG.standard_normal((v, e)).astype(np.float32))
    seg = jnp.asarray(np.sort(RNG.integers(0, b, l)).astype(np.int32))
    idx = jnp.asarray(RNG.integers(0, v, l).astype(np.int32))
    w = jnp.asarray(RNG.random(l).astype(np.float32))
    got = ops.embedding_bag(table, idx, seg, b, w)
    want = ref.embedding_bag_ref(table, idx, seg, b, w)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)


def test_embedding_bag_empty_bags_zero():
    table = jnp.ones((5, 4), jnp.float32)
    idx = jnp.asarray([1, 2], jnp.int32)
    seg = jnp.asarray([2, 2], jnp.int32)
    out = ops.embedding_bag(table, idx, seg, 4)
    np.testing.assert_allclose(np.asarray(out[0]), 0.0)
    np.testing.assert_allclose(np.asarray(out[2]), 2.0)
    np.testing.assert_allclose(np.asarray(out[3]), 0.0)


def test_embedding_module_kernel_path_matches_reference_path():
    from repro.models import embedding as E

    table = jnp.asarray(RNG.standard_normal((50, 8)).astype(np.float32))
    idx = jnp.asarray(np.sort(RNG.integers(0, 50, 30)).astype(np.int32))
    seg = jnp.asarray(np.sort(RNG.integers(0, 6, 30)).astype(np.int32))
    a = E.embedding_bag(table, idx, seg, 6, use_kernel=False)
    b = E.embedding_bag(table, idx, seg, 6, use_kernel=True)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


# -- execution-mode resolution (REPRO_INTERPRET / backend) -------------------

def test_resolve_interpret_env_parsing():
    for flag in ("1", "true", "YES", " on ", "interpret"):
        assert ops.resolve_interpret(env=flag) is True
    for flag in ("0", "false", "No", " off ", "compiled"):
        assert ops.resolve_interpret(env=flag) is False
    with pytest.raises(ValueError, match="REPRO_INTERPRET"):
        ops.resolve_interpret(env="maybe")


def test_resolve_interpret_backend_fallback():
    # empty/blank env falls through to the backend rule
    assert ops.resolve_interpret(env="", backend="tpu") is False
    assert ops.resolve_interpret(env="  ", backend="cpu") is True
    assert ops.resolve_interpret(env="", backend="gpu") is True
    # env wins over backend when set
    assert ops.resolve_interpret(env="1", backend="tpu") is True
    assert ops.resolve_interpret(env="0", backend="cpu") is False


def test_module_default_matches_this_host():
    assert ops.interpret_mode() == ops.resolve_interpret(env=None)
    # on this host the resolved default must be valid: interpret anywhere,
    # compiled only on TPU
    if jax.default_backend() != "tpu":
        assert ops.resolve_interpret(env="") is True


# -- toggle semantics: global read at CALL time, static jit argument ---------

def test_wrappers_read_global_at_call_time(monkeypatch):
    """Flipping ops.INTERPRET takes effect on the very next wrapper call."""
    seen = []

    def fake_topk(scores, k, block_d=None, interpret=None):
        seen.append(interpret)
        return scores[:, :k], jnp.zeros((scores.shape[0], k), jnp.int32)

    monkeypatch.setattr(ops._topk, "topk", fake_topk)
    x = jnp.zeros((2, 16), jnp.float32)
    monkeypatch.setattr(ops, "INTERPRET", False)
    ops.topk(x, 4)
    monkeypatch.setattr(ops, "INTERPRET", True)
    ops.topk(x, 4)
    ops.topk(x, 4, interpret=False)  # per-call arg outranks the global
    assert seen == [False, True, False]


def test_fused_wrapper_reads_global_at_call_time(monkeypatch):
    seen = []

    def fake_fused(rel, judged, scal, block_q=None, relevance_level=1.0,
                   interpret=None):
        seen.append(interpret)
        return jnp.zeros((rel.shape[0], 64), jnp.float32)

    monkeypatch.setattr(ops._fm, "fused_measures", fake_fused)
    rel = jnp.zeros((2, 8), jnp.float32)
    scal = jnp.zeros((2, 16), jnp.float32)
    monkeypatch.setattr(ops, "INTERPRET", True)
    ops.fused_measures_cols(rel, rel, scal)
    monkeypatch.setattr(ops, "INTERPRET", False)
    ops.fused_measures_cols(rel, rel, scal)
    ops.fused_measures_cols(rel, rel, scal, interpret=True)
    assert seen == [True, False, True]


def test_sharded_evaluator_snapshots_interpret(monkeypatch):
    """ShardedEvaluator captures the mode at construction — documented caveat."""
    from repro.core import RelevanceEvaluator
    from repro.distributed.sharded_evaluator import ShardedEvaluator

    ev = RelevanceEvaluator({"q1": {"d1": 1}}, ("map",))
    live = ops.interpret_mode()
    se = ShardedEvaluator(ev)
    assert se.interpret == live
    # flipping the global does NOT change an existing instance...
    monkeypatch.setattr(ops, "INTERPRET", not live)
    assert se.interpret == live
    # ...but a rebuilt one (or an explicit arg) picks the new mode up
    assert ShardedEvaluator(ev).interpret == (not live)
    assert ShardedEvaluator(ev, interpret=live).interpret == live


# -- compiled-vs-interpret conformance gate ----------------------------------
#
# On a TPU host the resolved default is the COMPILED path and this gate
# compares real Mosaic executables against the interpreter (documented
# tolerance: ~1 ulp on float accumulations).  On CPU/GPU hosts both modes
# resolve to the interpreter, so the gate degenerates to a bit-identity
# check through the same call path — the resolution plumbing itself is
# exercised either way.

def _assert_mode_parity(got, want, what):
    got, want = np.asarray(got), np.asarray(want)
    if ops.interpret_mode():
        np.testing.assert_array_equal(got, want, err_msg=what)
    else:
        np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6,
                                   err_msg=what)


def test_parity_fused_measures_default_vs_interpret():
    q, d = 7, 200
    scores = jnp.asarray(RNG.standard_normal((q, d)).astype(np.float32))
    rel = jnp.asarray(RNG.integers(0, 3, (q, d)).astype(np.float32))
    batch = M.batch_from_dense(scores, rel)
    s = M.sort_batch(batch)
    scal = ops.make_scalars(batch.n_rel, batch.n_judged_nonrel,
                            batch.ideal_rel)
    got = ops.fused_measures_cols(s.rel, s.judged, scal)  # resolved default
    want = ops.fused_measures_cols(s.rel, s.judged, scal, interpret=True)
    _assert_mode_parity(got, want, "fused_measures default vs interpret")


def test_parity_topk_default_vs_interpret():
    scores = jnp.asarray(RNG.standard_normal((3, 1000)).astype(np.float32))
    v, i = ops.topk(scores, 50)
    vi, ii = ops.topk(scores, 50, interpret=True)
    _assert_mode_parity(v, vi, "topk values default vs interpret")
    np.testing.assert_array_equal(np.asarray(i), np.asarray(ii))


def test_parity_embedding_bag_default_vs_interpret():
    table = jnp.asarray(RNG.standard_normal((40, 16)).astype(np.float32))
    seg = jnp.asarray(np.sort(RNG.integers(0, 6, 30)).astype(np.int32))
    idx = jnp.asarray(RNG.integers(0, 40, 30).astype(np.int32))
    got = ops.embedding_bag(table, idx, seg, 6)
    want = ops.embedding_bag(table, idx, seg, 6, interpret=True)
    _assert_mode_parity(got, want, "embedding_bag default vs interpret")


def test_explicit_block_q_matches_autotuned():
    """block_q only tiles the VMEM walk; results are block-size invariant."""
    q, d = 13, 128
    scores = jnp.asarray(RNG.standard_normal((q, d)).astype(np.float32))
    rel = jnp.asarray(RNG.integers(0, 2, (q, d)).astype(np.float32))
    batch = M.batch_from_dense(scores, rel)
    s = M.sort_batch(batch)
    scal = ops.make_scalars(batch.n_rel, batch.n_judged_nonrel,
                            batch.ideal_rel)
    auto = ops.fused_measures_cols(s.rel, s.judged, scal)
    for bq in (8, 16, 128):
        manual = ops.fused_measures_cols(s.rel, s.judged, scal, block_q=bq)
        np.testing.assert_array_equal(np.asarray(auto), np.asarray(manual),
                                      err_msg=f"block_q={bq}")
