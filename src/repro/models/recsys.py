"""RecSys architectures: SASRec, xDeepFM (CIN), MIND (capsules), AutoInt.

Shared anatomy: sparse embedding tables (the hot path, see ``embedding.py``)
→ feature-interaction op → small MLP → logit(s).  Every model exposes:

  * ``init(rng, cfg)``                → params
  * ``score(params, batch, cfg)``     → pCTR logits / ranking scores
  * ``loss(params, batch, cfg)``      → scalar training loss (+ aux)
  * ``retrieval_scores(params, batch, cfg)`` → [B, n_candidates] for the
    ``retrieval_cand`` shape (one query vs 10⁶ candidates — batched matmul
    into the top-K kernel, never a loop).

In-loop evaluation: serving paths return score tensors that feed directly
into ``core.measures`` without leaving the
device — the paper's in-process evaluation at pod scale.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

from repro.models import embedding as E
from repro.models import layers as L


# ===========================================================================
# SASRec — self-attentive sequential recommendation (arXiv:1808.09781)
# ===========================================================================


@dataclasses.dataclass(frozen=True)
class SASRecConfig:
    name: str
    n_items: int
    embed_dim: int
    n_blocks: int
    n_heads: int
    seq_len: int
    dtype: str = "float32"
    unroll_layers: bool = False  # cost-probe only

    @property
    def np_dtype(self):
        return jnp.dtype(self.dtype)


def sasrec_init(rng, cfg: SASRecConfig):
    d = cfg.embed_dim
    keys = jax.random.split(rng, 3)

    def stack(key, shape, fan_in):
        ks = jax.random.split(key, cfg.n_blocks)
        return jax.vmap(
            lambda k: jax.random.normal(k, shape) * (1.0 / fan_in) ** 0.5
        )(ks).astype(cfg.np_dtype)

    return {
        "item_emb": (jax.random.normal(keys[0], (cfg.n_items, d)) * 0.02
                     ).astype(cfg.np_dtype),
        "pos_emb": (jax.random.normal(keys[1], (cfg.seq_len, d)) * 0.02
                    ).astype(cfg.np_dtype),
        "blocks": {
            "wqkv": stack(keys[2], (d, 3 * d), d),
            "wo": stack(jax.random.fold_in(rng, 7), (d, d), d),
            "w1": stack(jax.random.fold_in(rng, 8), (d, d), d),
            "w2": stack(jax.random.fold_in(rng, 9), (d, d), d),
        },
    }


def sasrec_encode(params, item_ids, cfg: SASRecConfig):
    """item_ids [B, S] → sequence representations [B, S, D] (causal)."""
    b, s = item_ids.shape
    d = cfg.embed_dim
    x = jnp.take(params["item_emb"], item_ids, axis=0)
    x = x + params["pos_emb"][None, :s]
    causal = jnp.tril(jnp.ones((s, s), bool))

    def body(x, bp):
        qkv = L.nonparam_layernorm(x) @ bp["wqkv"]
        q, k, v = jnp.split(qkv, 3, axis=-1)
        hd = d // cfg.n_heads
        q = q.reshape(b, s, cfg.n_heads, hd)
        k = k.reshape(b, s, cfg.n_heads, hd)
        v = v.reshape(b, s, cfg.n_heads, hd)
        sc = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) / hd**0.5
        sc = jnp.where(causal[None, None], sc, -1e30)
        p = jax.nn.softmax(sc, -1).astype(x.dtype)
        o = jnp.einsum("bhqk,bkhd->bqhd", p, v).reshape(b, s, d)
        x = x + o @ bp["wo"]
        h = jax.nn.relu(L.nonparam_layernorm(x) @ bp["w1"]) @ bp["w2"]
        return x + h, None

    from repro.models.scan_utils import scan_layers
    x, _ = scan_layers(body, x, params["blocks"], cfg.unroll_layers)
    return L.nonparam_layernorm(x)


def sasrec_loss(params, batch, cfg: SASRecConfig):
    """BCE over (positive next item, sampled negative) — the paper's loss."""
    h = sasrec_encode(params, batch["items"], cfg)  # [B, S, D]
    pos = jnp.take(params["item_emb"], batch["pos"], axis=0)  # [B, S, D]
    neg = jnp.take(params["item_emb"], batch["neg"], axis=0)
    pos_logit = jnp.sum(h * pos, -1)
    neg_logit = jnp.sum(h * neg, -1)
    m = batch["mask"].astype(jnp.float32)
    loss = -(jax.nn.log_sigmoid(pos_logit) + jax.nn.log_sigmoid(-neg_logit))
    return jnp.sum(loss * m) / jnp.maximum(jnp.sum(m), 1.0)


def sasrec_retrieval_scores(params, batch, cfg: SASRecConfig):
    """Last-position user state vs candidate item set → [B, n_cand]."""
    h = sasrec_encode(params, batch["items"], cfg)[:, -1]  # [B, D]
    cand = params["item_emb"]
    if "candidates" in batch:
        cand = jnp.take(params["item_emb"], batch["candidates"], axis=0)
    return h @ cand.T


# ===========================================================================
# CTR models: shared sparse-feature front-end
# ===========================================================================


@dataclasses.dataclass(frozen=True)
class CTRConfig:
    name: str
    table: E.TableConfig
    # xDeepFM
    cin_layers: tuple = ()
    mlp_dims: tuple = ()
    # AutoInt
    n_attn_layers: int = 0
    n_attn_heads: int = 0
    d_attn: int = 0
    # MIND
    n_interests: int = 0
    capsule_iters: int = 0
    hist_len: int = 0
    n_multi_hot: int = 0  # leading fields that are multi-hot (bags)
    multi_hot_len: int = 8
    dtype: str = "float32"

    @property
    def np_dtype(self):
        return jnp.dtype(self.dtype)


def _sparse_features(params, batch, cfg: CTRConfig):
    """ids [B, F] (+ optional multi-hot bags) → field embeddings [B, F, D]."""
    tab = params["table"]
    emb = E.field_lookup(tab, batch["ids"], cfg.table)  # [B, F, D]
    if cfg.n_multi_hot and "mh_ids" in batch:
        # First n_multi_hot fields also receive a bag of extra values.
        bags = []
        for f in range(cfg.n_multi_hot):
            bag = E.multi_hot_lookup(tab, batch["mh_ids"][:, f],
                                     batch["mh_mask"][:, f], cfg.table, f)
            bags.append(bag)
        mh = jnp.stack(bags, axis=1)  # [B, n_mh, D]
        emb = emb.at[:, : cfg.n_multi_hot].add(mh)
    return emb


def _mlp(x, ws, bs):
    for i, (w, b) in enumerate(zip(ws, bs)):
        x = x @ w + b
        if i < len(ws) - 1:
            x = jax.nn.relu(x)
    return x


def _mlp_init(rng, dims, dtype):
    ws, bs = [], []
    for i, (a, b) in enumerate(zip(dims[:-1], dims[1:])):
        ws.append(L.dense_init(jax.random.fold_in(rng, i), a, b, dtype))
        bs.append(jnp.zeros((b,), dtype))
    return ws, bs


# ===========================================================================
# xDeepFM — Compressed Interaction Network (arXiv:1803.05170)
# ===========================================================================


def xdeepfm_init(rng, cfg: CTRConfig):
    d = cfg.table.dim
    f = cfg.table.n_fields
    params = {
        "table": E.init_table(jax.random.fold_in(rng, 0), cfg.table,
                              cfg.np_dtype),
        "linear": (jax.random.normal(jax.random.fold_in(rng, 1),
                                     (cfg.table.total_rows,)) * 0.01
                   ).astype(cfg.np_dtype),
        "cin": [],
        "bias": jnp.zeros((), cfg.np_dtype),
    }
    h_prev = f
    for i, h in enumerate(cfg.cin_layers):
        params["cin"].append(
            (jax.random.normal(jax.random.fold_in(rng, 10 + i),
                               (h, h_prev, f)) * (1.0 / (h_prev * f)) ** 0.5
             ).astype(cfg.np_dtype))
        h_prev = h
    mlp_dims = (f * d,) + tuple(cfg.mlp_dims) + (1,)
    params["mlp_w"], params["mlp_b"] = _mlp_init(jax.random.fold_in(rng, 50),
                                                 mlp_dims, cfg.np_dtype)
    params["cin_out"] = L.dense_init(jax.random.fold_in(rng, 51),
                                     sum(cfg.cin_layers), 1, cfg.np_dtype)
    return params


def xdeepfm_score(params, batch, cfg: CTRConfig):
    emb = _sparse_features(params, batch, cfg)  # [B, F, D]
    b, f, d = emb.shape
    x0 = emb
    xk = emb
    pooled = []
    for w in params["cin"]:
        # CIN: x^{k+1}_h = Σ_{i,j} W_h[i,j] (x^k_i ∘ x^0_j)
        xk = jnp.einsum("bhd,bmd,phm->bpd", xk, x0, w)
        pooled.append(jnp.sum(xk, axis=-1))  # sum-pool over D → [B, H_k]
    cin_logit = jnp.concatenate(pooled, -1) @ params["cin_out"]
    deep_logit = _mlp(emb.reshape(b, f * d), params["mlp_w"], params["mlp_b"])
    offsets = jnp.arange(f, dtype=batch["ids"].dtype) * cfg.table.vocab_per_field
    lin_logit = jnp.sum(
        jnp.take(params["linear"], batch["ids"] + offsets[None], axis=0), -1)
    return (cin_logit[:, 0] + deep_logit[:, 0] + lin_logit + params["bias"])


# ===========================================================================
# AutoInt — self-attentive feature interaction (arXiv:1810.11921)
# ===========================================================================


def autoint_init(rng, cfg: CTRConfig):
    d = cfg.table.dim
    da, nh = cfg.d_attn, cfg.n_attn_heads
    params = {
        "table": E.init_table(jax.random.fold_in(rng, 0), cfg.table,
                              cfg.np_dtype),
        "attn": [],
    }
    d_in = d
    for i in range(cfg.n_attn_layers):
        key = jax.random.fold_in(rng, 10 + i)
        params["attn"].append({
            "wq": L.dense_init(jax.random.fold_in(key, 0), d_in, da * nh,
                               cfg.np_dtype),
            "wk": L.dense_init(jax.random.fold_in(key, 1), d_in, da * nh,
                               cfg.np_dtype),
            "wv": L.dense_init(jax.random.fold_in(key, 2), d_in, da * nh,
                               cfg.np_dtype),
            "wres": L.dense_init(jax.random.fold_in(key, 3), d_in, da * nh,
                                 cfg.np_dtype),
        })
        d_in = da * nh
    params["head"] = L.dense_init(jax.random.fold_in(rng, 99),
                                  cfg.table.n_fields * d_in, 1, cfg.np_dtype)
    return params


def autoint_score(params, batch, cfg: CTRConfig):
    x = _sparse_features(params, batch, cfg)  # [B, F, D]
    b, f, _ = x.shape
    nh, da = cfg.n_attn_heads, cfg.d_attn
    for lp in params["attn"]:
        q = (x @ lp["wq"]).reshape(b, f, nh, da)
        k = (x @ lp["wk"]).reshape(b, f, nh, da)
        v = (x @ lp["wv"]).reshape(b, f, nh, da)
        sc = jnp.einsum("bfhd,bghd->bhfg", q, k).astype(jnp.float32) / da**0.5
        p = jax.nn.softmax(sc, -1).astype(x.dtype)
        o = jnp.einsum("bhfg,bghd->bfhd", p, v).reshape(b, f, nh * da)
        x = jax.nn.relu(o + x @ lp["wres"])
    return (x.reshape(b, -1) @ params["head"])[:, 0]


# ===========================================================================
# MIND — multi-interest capsule routing (arXiv:1904.08030)
# ===========================================================================


def mind_init(rng, cfg: CTRConfig):
    d = cfg.table.dim
    return {
        "item_emb": (jax.random.normal(jax.random.fold_in(rng, 0),
                                       (cfg.table.vocab_per_field, d)) * 0.02
                     ).astype(cfg.np_dtype),
        "bilinear": L.dense_init(jax.random.fold_in(rng, 1), d, d,
                                 cfg.np_dtype),
        "proj1": L.dense_init(jax.random.fold_in(rng, 2), d, 4 * d,
                              cfg.np_dtype),
        "proj2": L.dense_init(jax.random.fold_in(rng, 3), 4 * d, d,
                              cfg.np_dtype),
    }


def mind_interests(params, batch, cfg: CTRConfig):
    """Behavior sequence → K interest capsules via B2I dynamic routing."""
    hist = jnp.take(params["item_emb"], batch["hist"], axis=0)  # [B, T, D]
    mask = batch["hist_mask"].astype(jnp.float32)  # [B, T]
    b, t, d = hist.shape
    k = cfg.n_interests
    u = hist @ params["bilinear"]  # shared bilinear map S·e_i

    logits = jnp.zeros((b, k, t), jnp.float32)  # routing logits b_ij
    caps = jnp.zeros((b, k, d), hist.dtype)
    for _ in range(cfg.capsule_iters):
        w = jax.nn.softmax(logits, axis=1) * mask[:, None, :]
        s = jnp.einsum("bkt,btd->bkd", w.astype(hist.dtype), u)
        # squash
        nrm2 = jnp.sum(jnp.square(s.astype(jnp.float32)), -1, keepdims=True)
        caps = (s * (nrm2 / (1 + nrm2) / jnp.sqrt(nrm2 + 1e-9)).astype(s.dtype))
        logits = logits + jnp.einsum("bkd,btd->bkt", caps, u).astype(jnp.float32)
    # per-interest MLP (H-layer)
    caps = jax.nn.relu(caps @ params["proj1"]) @ params["proj2"]
    return caps  # [B, K, D]


def mind_loss(params, batch, cfg: CTRConfig):
    """Sampled-softmax with label-aware attention (hard max at train)."""
    caps = mind_interests(params, batch, cfg)  # [B, K, D]
    pos = jnp.take(params["item_emb"], batch["pos"], axis=0)  # [B, D]
    negs = jnp.take(params["item_emb"], batch["negs"], axis=0)  # [B, Nneg, D]
    # label-aware attention: pick the interest most aligned with the label
    att = jnp.einsum("bkd,bd->bk", caps, pos)
    best = jnp.take_along_axis(caps, jnp.argmax(att, -1)[:, None, None], 1)[:, 0]
    pos_logit = jnp.sum(best * pos, -1, keepdims=True)
    neg_logit = jnp.einsum("bd,bnd->bn", best, negs)
    logits = jnp.concatenate([pos_logit, neg_logit], -1)
    labels = jnp.zeros((caps.shape[0],), jnp.int32)
    return L.cross_entropy(logits, labels)


def mind_retrieval_scores(params, batch, cfg: CTRConfig):
    """max over interests of ⟨candidate, interest⟩ → [B, n_cand]."""
    caps = mind_interests(params, batch, cfg)
    cand = params["item_emb"]
    if "candidates" in batch:
        cand = jnp.take(params["item_emb"], batch["candidates"], axis=0)
    scores = jnp.einsum("bkd,nd->bkn", caps, cand)
    return jnp.max(scores, axis=1)


# ===========================================================================
# Shared CTR loss
# ===========================================================================


def ctr_loss(score_fn, params, batch, cfg: CTRConfig):
    logits = score_fn(params, batch, cfg)
    y = batch["labels"].astype(jnp.float32)
    lf = logits.astype(jnp.float32)
    loss = jnp.mean(
        jnp.maximum(lf, 0) - lf * y + jnp.log1p(jnp.exp(-jnp.abs(lf))))
    return loss, logits
