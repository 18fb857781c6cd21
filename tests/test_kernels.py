"""Pallas kernels vs pure-jnp oracles (interpret mode), shape/dtype sweeps."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

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
