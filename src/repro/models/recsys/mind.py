"""MIND: Multi-Interest Network with Dynamic routing (Li et al. 2019,
arXiv:1904.08030) — the plain float32 reference of the user tower, its
training loss and its retrieval scores.

The tower, as served (``serving/corpus.py`` ``MINDScorer`` runs this code
on the device for every launch):

1. behaviour embeddings ``e_l`` of the user's history (item ids; padded
   positions masked out);
2. Behavior-to-Interest (B2I) dynamic routing with the shared bilinear
   map ``S`` and fixed random routing logits ``b_init`` (not learned: per
   the paper, a random start breaks the symmetry of the interests),
   ``capsule_iters`` rounds, squash nonlinearity;
3. the adaptive interest count ``K'_u = max(1, min(K, floor(log2
   |I_u|)))`` (section 3.3.2 of the paper): routing runs over the first
   K'_u capsules only, the rest are dead;
4. each capsule concatenated with the user-profile embedding (the
   layout's context fields: age, gender, city, device, one embedding row
   each, concatenated) and passed through two ReLU layers back to
   ``embed_dim``: ``hidden`` wide, then ``embed_dim``;
5. retrieval: ``score(u, i) = max over live k of <u_k, e_i>``; training:
   label-aware attention over the live interests, sampled softmax.

Departures from the paper, all assumed for want of a published value:
the two ReLU layers' widths (``hidden`` 256, then ``embed_dim``), the
floor in K'_u's log2, and the item side: the layout holds the item id
alone, so an item's embedding is its id's row (the paper also pools
category and brand).  Every contraction runs at
``jax.default_matmul_precision("highest")``: on the TPU an f32 matmul
otherwise takes one bf16 pass.
"""
from __future__ import annotations

import dataclasses
from typing import Any

import numpy as np

import jax
import jax.numpy as jnp

from repro.core.fields import FeatureLayout
from repro.embedding.bag import init_embedding_table, padded_rows
from repro.models.layers import glorot

NEG = -1e30           # routing / attention logit of a dead interest


@dataclasses.dataclass(frozen=True)
class MINDConfig:
    layout: FeatureLayout          # profile (context) fields + 1 item field
    embed_dim: int = 64
    n_interests: int = 4
    capsule_iters: int = 3
    seq_len: int = 50
    hidden: int = 256              # the profile MLP's hidden ReLU width
    label_pow: float = 2.0         # p in label-aware attention
    n_neg: int = 8                 # sampled-softmax negatives (training)
    dtype: Any = jnp.float32


def init(rng: jax.Array, cfg: MINDConfig) -> dict:
    k_emb, k_s, k_b, k_1, k_2 = jax.random.split(rng, 5)
    d = cfg.embed_dim
    x = d * (1 + cfg.layout.n_context)     # capsule ++ profile embedding
    return {
        "embedding": init_embedding_table(k_emb, padded_rows(cfg.layout.total_vocab),
                                          d, dtype=cfg.dtype),
        "S": glorot(k_s, (d, d), cfg.dtype),               # shared bilinear map
        "b_init": (jax.random.normal(k_b, (cfg.n_interests, cfg.seq_len))).astype(cfg.dtype),
        "W1": glorot(k_1, (x, cfg.hidden), cfg.dtype),
        "b1": jnp.zeros((cfg.hidden,), cfg.dtype),
        "W2": glorot(k_2, (cfg.hidden, d), cfg.dtype),
        "b2": jnp.zeros((d,), cfg.dtype),
    }


def _item_arena_offset(cfg: MINDConfig) -> int:
    return int(cfg.layout.field_offsets[cfg.layout.n_context])


def _take(take_fn):
    return take_fn or (lambda t, i: jnp.take(t, i, axis=0))


def interest_counts(lengths, n_interests: int):
    """K'_u = max(1, min(K, floor(log2 |I_u|))) for history lengths
    ``lengths`` (numpy or jax integers): floor(log2 n) >= j exactly when
    n >= 2**j."""
    xp = np if isinstance(lengths, np.ndarray) else jnp
    count = sum((lengths >= (1 << j)).astype(xp.int32)
                for j in range(1, n_interests + 1))
    return xp.maximum(count, 1)


def _squash(v: jax.Array) -> jax.Array:
    n2 = (v * v).sum(axis=-1, keepdims=True)
    return (n2 / (1.0 + n2)) * v * jax.lax.rsqrt(n2 + 1e-9)


def routed_capsules(params: dict, cfg: MINDConfig, hist_ids: jax.Array,
                    hist_mask: jax.Array, take_fn=None):
    """(batch..., L) histories -> ((batch..., K, d) interest capsules,
    (batch..., K) bool live interests) by B2I routing over the first K'_u
    capsules."""
    off = _item_arena_offset(cfg)
    e = _take(take_fn)(params["embedding"], hist_ids + off)    # (..., L, d)
    low = (e @ params["S"]) * hist_mask[..., None]              # S e_i
    K = cfg.n_interests
    live = jnp.arange(K) < interest_counts(
        (hist_mask > 0).sum(-1), K)[..., None]                  # (..., K)
    b = jax.lax.stop_gradient(params["b_init"])                 # (K, L), frozen
    b = jnp.broadcast_to(b, (*low.shape[:-2], K, cfg.seq_len))
    neg = jnp.asarray(NEG, low.dtype)
    open_ = (hist_mask[..., None, :] > 0) & live[..., None]     # (..., K, L)
    for _ in range(cfg.capsule_iters):
        w = jax.nn.softmax(jnp.where(open_, b, neg), axis=-2)   # over interests
        caps = _squash(jnp.einsum("...kl,...ld->...kd",
                                  w * hist_mask[..., None, :], low))
        b = b + jnp.einsum("...kd,...ld->...kl", caps, low)
    return caps, live


def profile_embedding(params: dict, cfg: MINDConfig, profile_ids: jax.Array,
                      take_fn=None) -> jax.Array:
    """(batch..., m_p) profile ids -> (batch..., m_p * d): the context
    fields' embedding rows, concatenated."""
    lay = cfg.layout
    offs = jnp.asarray(lay.field_offsets[:lay.n_context], jnp.int32)
    rows = _take(take_fn)(params["embedding"], profile_ids + offs)
    return rows.reshape(*rows.shape[:-2], -1)


def user_tower(params: dict, cfg: MINDConfig, profile_ids: jax.Array,
               hist_ids: jax.Array, hist_mask: jax.Array, take_fn=None):
    """The user tower: ((batch..., K, d) interest vectors, (batch..., K)
    bool live interests).  Each routed capsule is concatenated with the
    profile embedding and passed through ReLU(W1) and ReLU(W2)."""
    with jax.default_matmul_precision("highest"):
        caps, live = routed_capsules(params, cfg, hist_ids, hist_mask,
                                     take_fn)
        prof = profile_embedding(params, cfg, profile_ids, take_fn)
        prof = jnp.broadcast_to(prof[..., None, :],
                                (*caps.shape[:-1], prof.shape[-1]))
        x = jnp.concatenate([caps, prof], axis=-1)
        h = jax.nn.relu(x @ params["W1"] + params["b1"])
        return jax.nn.relu(h @ params["W2"] + params["b2"]), live


def label_aware_user_vec(cfg: MINDConfig, interests: jax.Array,
                         live: jax.Array, target_e: jax.Array) -> jax.Array:
    """Label-aware attention over the live interests: softmax((K e_t)^p
    scaled dots) combination."""
    dots = jnp.einsum("...kd,...d->...k", interests, target_e)
    attn = jax.nn.softmax(jnp.where(live, cfg.label_pow * dots, NEG),
                          axis=-1)
    return jnp.einsum("...k,...kd->...d", attn, interests)


def _max_live(scores: jax.Array, live: jax.Array) -> jax.Array:
    """(..., K) scores -> (...,) max over the live interests."""
    return jnp.where(live, scores, NEG).max(axis=-1)


def loss(params: dict, cfg: MINDConfig, batch: dict, take_fn=None) -> jax.Array:
    """Sampled-softmax over {target} + n_neg sampled item ids.

    batch: profile_ids (B, m_p), hist_ids (B, L), hist_mask (B, L),
           target_id (B,), neg_ids (B, n_neg).
    """
    off = _item_arena_offset(cfg)
    take = _take(take_fn)
    interests, live = user_tower(params, cfg, batch["profile_ids"],
                                 batch["hist_ids"], batch["hist_mask"],
                                 take_fn=take_fn)
    with jax.default_matmul_precision("highest"):
        tgt_e = take(params["embedding"], batch["target_id"] + off)
        user = label_aware_user_vec(cfg, interests, live, tgt_e)
        neg_e = take(params["embedding"], batch["neg_ids"] + off)
        pos_logit = (user * tgt_e).sum(-1, keepdims=True)
        neg_logit = jnp.einsum("...d,...nd->...n", user, neg_e)
    logits = jnp.concatenate([pos_logit, neg_logit], axis=-1)
    return -jax.nn.log_softmax(logits, axis=-1)[..., 0].mean()


def apply(params: dict, cfg: MINDConfig, batch: dict) -> jax.Array:
    """Pointwise score of (user, target item): max over live interests."""
    off = _item_arena_offset(cfg)
    interests, live = user_tower(params, cfg, batch["profile_ids"],
                                 batch["hist_ids"], batch["hist_mask"])
    tgt_e = jnp.take(params["embedding"], batch["target_id"] + off, axis=0)
    with jax.default_matmul_precision("highest"):
        dots = jnp.einsum("...kd,...d->...k", interests, tgt_e)
    return _max_live(dots, live)


def rank_items(params: dict, cfg: MINDConfig, query: dict,
               take_fn=None) -> jax.Array:
    """Retrieval scoring: max over live interests of <interest, item>.

    query: profile_ids (Bq, m_p), hist_ids (Bq, L), hist_mask (Bq, L),
    item_ids (Bq, n, 1).  The tower runs once per query; a candidate
    costs one (K, d) x (d,) product and a max over K.
    """
    off = _item_arena_offset(cfg)
    interests, live = user_tower(params, cfg, query["profile_ids"],
                                 query["hist_ids"], query["hist_mask"],
                                 take_fn=take_fn)
    item_e = _take(take_fn)(params["embedding"], query["item_ids"][..., 0] + off)
    with jax.default_matmul_precision("highest"):
        scores = jnp.einsum("...nd,...kd->...nk", item_e, interests)
    return _max_live(scores, live[..., None, :])
