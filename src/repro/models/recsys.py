"""RecSys architectures: DeepFM, SASRec, BERT4Rec, MIND.

The hot path for all four is the sparse embedding lookup. JAX has no
native EmbeddingBag / CSR — `embedding_bag` below implements it with
``jnp.take`` + ``jax.ops.segment_sum``. Embedding
tables are row-sharded over the 'model' mesh axis (the tables ARE the
model); the dense towers are small and replicated.

Every model exposes:
  init(key) / logical_axes() / forward / loss / train_step /
  serve(...)            — pointwise scoring (serve_p99 / serve_bulk cells)
  retrieval_scores(...) — one user vs n_candidates items (retrieval_cand),
                          feeding the paper's constrained-ranking head.
  user_covariates(...)  — the covariate vector X consumed by the paper's
                          lambda predictor (Algorithm 1).
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any

import jax
import jax.numpy as jnp

from repro.distributed.sharding import logical_shard
from repro.models.layers import dense_init, rms_norm

Array = jax.Array


# --------------------------------------------------------------------------
# EmbeddingBag — take + segment_sum (THE recsys substrate op)
# --------------------------------------------------------------------------

def embedding_bag(
    table: Array,          # (V, D) — row-sharded over 'model'
    indices: Array,        # (n_bags, bag) int32; < 0 = padding
    weights: Array | None = None,
) -> Array:
    """Sum-mode EmbeddingBag: out[i] = sum_j w[i,j] * table[idx[i,j]]."""
    n_bags, bag = indices.shape
    valid = indices >= 0
    idx = jnp.where(valid, indices, 0)
    rows = jnp.take(table, idx.reshape(-1), axis=0)          # (n*bag, D)
    w = valid.astype(table.dtype)
    if weights is not None:
        w = w * weights.astype(table.dtype)
    rows = rows * w.reshape(-1, 1)
    seg = jnp.repeat(jnp.arange(n_bags), bag)
    return jax.ops.segment_sum(rows, seg, num_segments=n_bags)


# --------------------------------------------------------------------------
# Config
# --------------------------------------------------------------------------

@dataclass(frozen=True)
class RecsysConfig:
    name: str = "recsys"
    kind: str = "deepfm"           # deepfm | sasrec | bert4rec | mind
    # deepfm
    n_sparse: int = 39
    field_vocab: int = 1_000_000
    embed_dim: int = 10
    mlp_dims: tuple = (400, 400, 400)
    # sequence models
    n_items: int = 1_000_000
    seq_len: int = 50
    n_blocks: int = 2
    n_heads: int = 1
    # mind
    n_interests: int = 4
    capsule_iters: int = 3
    # training
    n_neg: int = 127               # sampled-softmax negatives
    dtype: Any = jnp.float32
    param_dtype: Any = jnp.float32
    # §Perf variant: replicate the item/field table instead of row-sharding
    # it over 'model' — wins when the table is small enough to fit per-chip
    # (every lookup/negative-sampling gather becomes local; the DP gradient
    # all-reduce replaces the per-step gather collectives)
    replicate_tables: bool = False

    @property
    def n_params(self) -> int:
        if self.kind == "deepfm":
            emb = self.n_sparse * self.field_vocab * (self.embed_dim + 1)
            d_in = self.n_sparse * self.embed_dim
            mlp = 0
            prev = d_in
            for h in self.mlp_dims:
                mlp += prev * h + h
                prev = h
            return emb + mlp + prev + 1
        return self.n_items * self.embed_dim  # dominated by the item table


# --------------------------------------------------------------------------
# DeepFM
# --------------------------------------------------------------------------

class DeepFM:
    """Factorization-machine + deep tower CTR model (arXiv:1703.04247).

    Input: (B, n_sparse) global ids (field f uses rows
    [f*field_vocab, (f+1)*field_vocab)). One flat (n_sparse*field_vocab, D)
    table so row-sharding covers all fields uniformly.
    """

    def __init__(self, cfg: RecsysConfig):
        self.cfg = cfg

    def init(self, key):
        cfg = self.cfg
        V = cfg.n_sparse * cfg.field_vocab
        ks = jax.random.split(key, 4 + len(cfg.mlp_dims))
        params = {
            "table": (jax.random.normal(ks[0], (V, cfg.embed_dim), jnp.float32)
                      * 0.01).astype(cfg.param_dtype),
            "w_linear": (jax.random.normal(ks[1], (V, 1), jnp.float32)
                         * 0.01).astype(cfg.param_dtype),
            "bias": jnp.zeros((), cfg.param_dtype),
            "mlp": {},
        }
        prev = cfg.n_sparse * cfg.embed_dim
        for i, h in enumerate(cfg.mlp_dims):
            params["mlp"][f"w{i}"] = dense_init(ks[2 + i], (prev, h), cfg.param_dtype)
            params["mlp"][f"b{i}"] = jnp.zeros((h,), cfg.param_dtype)
            prev = h
        params["mlp"]["w_out"] = dense_init(ks[-1], (prev, 1), cfg.param_dtype)
        return params

    def logical_axes(self):
        cfg = self.cfg
        axes = {
            "table": ("table_rows", "table_dim"),
            "w_linear": ("table_rows", None),
            "bias": (),
            "mlp": {},
        }
        for i in range(len(cfg.mlp_dims)):
            axes["mlp"][f"w{i}"] = ("dense_in", "dense_out")
            axes["mlp"][f"b{i}"] = (None,)
        axes["mlp"]["w_out"] = ("dense_in", None)
        return axes

    def forward(self, params, ids: Array) -> Array:
        """ids: (B, n_sparse) -> logits (B,)."""
        cfg = self.cfg
        B = ids.shape[0]
        emb = jnp.take(params["table"], ids.reshape(-1), axis=0)
        emb = emb.reshape(B, cfg.n_sparse, cfg.embed_dim)
        emb = logical_shard(emb, "batch", None, None)
        # FM 2nd order: 0.5 * ((sum v)^2 - sum v^2) summed over dim
        s = jnp.sum(emb, axis=1)
        fm2 = 0.5 * jnp.sum(s * s - jnp.sum(emb * emb, axis=1), axis=-1)
        # 1st order
        lin = jnp.take(params["w_linear"], ids.reshape(-1), axis=0)
        fm1 = jnp.sum(lin.reshape(B, cfg.n_sparse), axis=1)
        # deep tower
        h = emb.reshape(B, cfg.n_sparse * cfg.embed_dim)
        for i in range(len(cfg.mlp_dims)):
            h = jax.nn.relu(h @ params["mlp"][f"w{i}"] + params["mlp"][f"b{i}"])
        deep = (h @ params["mlp"]["w_out"])[:, 0]
        return fm1 + fm2 + deep + params["bias"]

    def loss(self, params, batch):
        logits = self.forward(params, batch["ids"])
        y = batch["labels"].astype(jnp.float32)
        loss = jnp.mean(
            jnp.maximum(logits, 0) - logits * y + jnp.log1p(jnp.exp(-jnp.abs(logits)))
        )
        return loss, {"loss": loss}

    def serve(self, params, ids: Array) -> Array:
        return jax.nn.sigmoid(self.forward(params, ids))

    def user_covariates(self, params, ids: Array) -> Array:
        """Mean field embedding = the user-side covariate vector X.
        Accepts any number of fields (context-only ids at retrieval)."""
        cfg = self.cfg
        B = ids.shape[0]
        emb = jnp.take(params["table"], ids.reshape(-1), axis=0)
        return jnp.mean(emb.reshape(B, -1, cfg.embed_dim), axis=1)

    def retrieval_scores(self, params, user_ids: Array, cand_ids: Array) -> Array:
        """user_ids: (B, n_sparse-1) context fields; cand_ids: (n_cand,)
        candidate values for the item field (field 0). Scores (B, n_cand):
        batch-free recompute of the FM + deep tower per candidate would be
        O(n_cand * mlp); instead we score with the FM interaction between
        the candidate embedding and the summed context (dot-product
        decomposition), which is the standard retrieval-tower reduction."""
        cfg = self.cfg
        B = user_ids.shape[0]
        ctx = jnp.take(params["table"], user_ids.reshape(-1), axis=0)
        ctx = ctx.reshape(B, -1, cfg.embed_dim).sum(axis=1)       # (B, D)
        cand = jnp.take(params["table"], cand_ids, axis=0)        # (n, D)
        cand = logical_shard(cand, "candidates", None)
        lin = jnp.take(params["w_linear"], cand_ids, axis=0)[:, 0]
        return ctx @ cand.T + lin[None, :]

    def train_step(self, params, opt_state, batch, *, lr=1e-3):
        return _generic_train_step(self, params, opt_state, batch, lr)


# --------------------------------------------------------------------------
# Shared transformer block for SASRec / BERT4Rec
# --------------------------------------------------------------------------

def _block_init(key, d: int, n_heads: int, d_ff: int, dtype):
    k1, k2, k3, k4 = jax.random.split(key, 4)
    return {
        "wqkv": dense_init(k1, (d, 3 * d), dtype),
        "wo": dense_init(k2, (d, d), dtype),
        "w1": dense_init(k3, (d, d_ff), dtype),
        "w2": dense_init(k4, (d_ff, d), dtype),
        "ln1": jnp.zeros((d,), dtype),
        "ln2": jnp.zeros((d,), dtype),
    }


_BLOCK_AXES = {
    "wqkv": ("dense_in", "dense_out"),
    "wo": ("dense_in", "dense_out"),
    "w1": ("dense_in", "dense_out"),
    "w2": ("dense_in", "dense_out"),
    "ln1": (None,),
    "ln2": (None,),
}


def _block_apply(p, x: Array, n_heads: int, causal: bool) -> Array:
    B, S, D = x.shape
    Dh = D // n_heads
    h = rms_norm(x, p["ln1"])
    qkv = (h @ p["wqkv"]).reshape(B, S, 3, n_heads, Dh)
    q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(Dh)
    if causal:
        mask = jnp.tril(jnp.ones((S, S), bool))
        logits = jnp.where(mask, logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    att = jnp.einsum("bhqk,bkhd->bqhd", probs, v).reshape(B, S, D)
    x = x + att @ p["wo"]
    h = rms_norm(x, p["ln2"])
    x = x + jax.nn.relu(h @ p["w1"]) @ p["w2"]
    return x


class _SeqRecBase:
    """Shared machinery for sequential recommenders."""

    causal = True

    def __init__(self, cfg: RecsysConfig):
        self.cfg = cfg

    def init(self, key):
        cfg = self.cfg
        ks = jax.random.split(key, 3 + cfg.n_blocks)
        params = {
            "items": (jax.random.normal(ks[0], (cfg.n_items, cfg.embed_dim),
                                        jnp.float32) * 0.02).astype(cfg.param_dtype),
            "pos": (jax.random.normal(ks[1], (cfg.seq_len, cfg.embed_dim),
                                      jnp.float32) * 0.02).astype(cfg.param_dtype),
            "blocks": [
                _block_init(ks[2 + i], cfg.embed_dim, cfg.n_heads,
                            4 * cfg.embed_dim, cfg.param_dtype)
                for i in range(cfg.n_blocks)
            ],
            "final_ln": jnp.zeros((cfg.embed_dim,), cfg.param_dtype),
        }
        return params

    def logical_axes(self):
        return {
            "items": ("table_rows", "table_dim"),
            "pos": (None, None),
            "blocks": [dict(_BLOCK_AXES) for _ in range(self.cfg.n_blocks)],
            "final_ln": (None,),
        }

    def encode(self, params, seq: Array) -> Array:
        """seq: (B, S) item ids (< 0 = padding) -> (B, S, D) states."""
        cfg = self.cfg
        valid = seq >= 0
        ids = jnp.where(valid, seq, 0)
        x = jnp.take(params["items"], ids, axis=0)
        x = x * valid[..., None].astype(x.dtype)
        x = x + params["pos"][None, : seq.shape[1]]
        x = logical_shard(x, "batch", "seq", None)
        for blk in params["blocks"]:
            x = _block_apply(blk, x, cfg.n_heads, self.causal)
        return rms_norm(x, params["final_ln"])

    def user_repr(self, params, seq: Array) -> Array:
        """(B, D) — last-position state (the query vector for retrieval)."""
        return self.encode(params, seq)[:, -1]

    # covariates for the paper's lambda predictor
    def user_covariates(self, params, seq: Array) -> Array:
        return self.user_repr(params, seq)

    def retrieval_scores(self, params, seq: Array, cand_ids: Array) -> Array:
        """(B, n_cand): user query dot candidate item embeddings."""
        q = self.user_repr(params, seq)                         # (B, D)
        cand = jnp.take(params["items"], cand_ids, axis=0)      # (n, D)
        cand = logical_shard(cand, "candidates", None)
        return q @ cand.T

    def serve(self, params, seq: Array, target: Array) -> Array:
        """Pointwise scoring of (user sequence, target item) pairs."""
        q = self.user_repr(params, seq)
        t = jnp.take(params["items"], target, axis=0)
        return jnp.sum(q * t, axis=-1)

    def _sampled_softmax(self, q: Array, pos_ids: Array, neg_ids: Array,
                         params) -> Array:
        """q: (B, D); pos: (B,); neg: (B, n_neg) -> mean CE loss."""
        pos_e = jnp.take(params["items"], pos_ids, axis=0)
        neg_e = jnp.take(params["items"], neg_ids, axis=0)
        pos_logit = jnp.sum(q * pos_e, axis=-1, keepdims=True)   # (B,1)
        neg_logit = jnp.einsum("bd,bnd->bn", q, neg_e)
        logits = jnp.concatenate([pos_logit, neg_logit], axis=-1)
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        return jnp.mean(lse - pos_logit[:, 0])

    def train_step(self, params, opt_state, batch, *, lr=1e-3):
        return _generic_train_step(self, params, opt_state, batch, lr)


class SASRec(_SeqRecBase):
    """Self-attentive sequential recommendation (arXiv:1808.09781).

    Next-item prediction: state at position t scores item t+1. Sampled
    softmax (1 pos + n_neg uniform negatives) — full-softmax over 10^6
    items would be a (B*S, 10^6) matmul; sampled softmax is the standard
    industrial reduction (noted in DESIGN.md)."""

    causal = True

    def loss(self, params, batch):
        # batch: seq (B,S), pos (B,S) next items, neg (B,S,n_neg)
        h = self.encode(params, batch["seq"])                   # (B,S,D)
        B, S, D = h.shape
        q = h.reshape(B * S, D)
        loss = self._sampled_softmax(
            q, batch["pos"].reshape(-1), batch["neg"].reshape(B * S, -1), params
        )
        return loss, {"loss": loss}


class BERT4Rec(_SeqRecBase):
    """Bidirectional masked-item model (arXiv:1904.06690). Encoder-only:
    no decode step exists for this arch (noted in DESIGN.md)."""

    causal = False

    def loss(self, params, batch):
        # batch: seq with [MASK]=id 0 at masked slots, mask_pos (B, n_mask),
        # mask_target (B, n_mask), neg (B, n_mask, n_neg)
        h = self.encode(params, batch["seq"])
        q = jnp.take_along_axis(
            h, batch["mask_pos"][..., None].astype(jnp.int32), axis=1
        )                                                       # (B,n_mask,D)
        B, M, D = q.shape
        loss = self._sampled_softmax(
            q.reshape(B * M, D),
            batch["mask_target"].reshape(-1),
            batch["neg"].reshape(B * M, -1),
            params,
        )
        return loss, {"loss": loss}


class MIND(_SeqRecBase):
    """Multi-Interest Network with Dynamic routing (arXiv:1904.08030).

    Behaviour sequence -> n_interests capsules via B2I dynamic routing
    (fixed `capsule_iters` iterations, squash nonlinearity); label-aware
    attention at train; serve = max over interests."""

    causal = False

    def init(self, key):
        params = super().init(key)
        cfg = self.cfg
        kb = jax.random.fold_in(key, 7)
        params["bilinear"] = dense_init(kb, (cfg.embed_dim, cfg.embed_dim),
                                        cfg.param_dtype)
        return params

    def logical_axes(self):
        axes = super().logical_axes()
        axes["bilinear"] = ("dense_in", "dense_out")
        return axes

    @staticmethod
    def _squash(x: Array) -> Array:
        n2 = jnp.sum(x * x, axis=-1, keepdims=True)
        return (n2 / (1.0 + n2)) * x / jnp.sqrt(n2 + 1e-9)

    def interests(self, params, seq: Array) -> Array:
        """(B, n_interests, D) capsules from the behaviour sequence."""
        cfg = self.cfg
        valid = (seq >= 0)
        ids = jnp.where(valid, seq, 0)
        e = jnp.take(params["items"], ids, axis=0)              # (B,S,D)
        e = e * valid[..., None].astype(e.dtype)
        eh = e @ params["bilinear"]                             # (B,S,D)
        B, S, D = eh.shape
        K = cfg.n_interests
        b_logit = jnp.zeros((B, K, S), eh.dtype)
        neg_mask = jnp.where(valid[:, None, :], 0.0, -1e30).astype(eh.dtype)
        u = jnp.zeros((B, K, D), eh.dtype)
        for _ in range(cfg.capsule_iters):
            w = jax.nn.softmax(b_logit + neg_mask, axis=1)      # over capsules
            u = self._squash(jnp.einsum("bks,bsd->bkd", w, eh))
            b_logit = b_logit + jnp.einsum("bkd,bsd->bks", u, eh)
        return u

    def user_repr(self, params, seq: Array) -> Array:
        # single-vector fallback: mean of interests
        return jnp.mean(self.interests(params, seq), axis=1)

    def user_covariates(self, params, seq: Array) -> Array:
        B = seq.shape[0]
        return self.interests(params, seq).reshape(B, -1)

    def retrieval_scores(self, params, seq: Array, cand_ids: Array) -> Array:
        """max over interests of interest·candidate (the MIND serving rule)."""
        u = self.interests(params, seq)                         # (B,K,D)
        cand = jnp.take(params["items"], cand_ids, axis=0)      # (n,D)
        cand = logical_shard(cand, "candidates", None)
        scores = jnp.einsum("bkd,nd->bkn", u, cand)
        return jnp.max(scores, axis=1)

    def loss(self, params, batch):
        # label-aware attention: weight interests by similarity^p to target
        u = self.interests(params, batch["seq"])                # (B,K,D)
        pos_e = jnp.take(params["items"], batch["pos"], axis=0)  # (B,D)
        att = jax.nn.softmax(
            jnp.einsum("bkd,bd->bk", u, pos_e) * 2.0, axis=-1
        )
        q = jnp.einsum("bk,bkd->bd", att, u)
        loss = self._sampled_softmax(q, batch["pos"], batch["neg"], params)
        return loss, {"loss": loss}


# --------------------------------------------------------------------------
# Generic train step
# --------------------------------------------------------------------------

def _generic_train_step(model, params, opt_state, batch, lr):
    from repro.optim import adam_update
    from repro.optim.clip import clip_by_global_norm

    (loss, metrics), grads = jax.value_and_grad(
        lambda p: model.loss(p, batch), has_aux=True
    )(params)
    grads, gnorm = clip_by_global_norm(grads, 1.0)
    params, opt_state = adam_update(grads, opt_state, params, lr=lr)
    return params, opt_state, dict(metrics, grad_norm=gnorm)


RECSYS_REGISTRY = {
    "deepfm": DeepFM,
    "sasrec": SASRec,
    "bert4rec": BERT4Rec,
    "mind": MIND,
}
