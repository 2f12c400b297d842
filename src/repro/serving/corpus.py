"""The serving contract of a model, and DPLR-FwFM's and MIND's item slabs.

A served model supplies three things, through a scorer object that
``ScorerRuntime`` picks from the config it is given (``scorer_for``):

  * slab rows from item ids — ``rows`` (one churn delta), ``build`` (the
    whole slab, at refresh) — held in a NamedTuple cache whose every leaf
    has the slab ``capacity`` as its leading axis and whose last leaf is
    the ``valid`` mask, so the runtime writes, drops and grows any model's
    slab the same way;
  * a query state from a batch of query rows — ``query``;
  * scores — ``scores`` (the jnp form, ``(Bq, capacity)``, dead slots
    ``NEG_INF``), ``kernel`` (the Pallas launch, fused top-K) and
    ``kernel_multi`` (S tenants' micro-batches in one program).

Plus ``query_width`` (slots of one query row), ``vectors`` (the query
vectors a batch scans and how many of them are live, counted on the host)
and ``span`` (a host span around the launch, or None).

``DPLRScorer`` is DPLR-FwFM: the slab is ``ItemCorpusCache`` and the query
state the context cache, below.  ``MINDScorer`` is MIND
(``models/recsys/mind.py``): the slab is one item-embedding row per slot
(``MINDSlab``), the query state the user tower's interest vectors, and a
score the max over live interests of an inner product
(``kernels/mind_corpus_score.py``).

Item-corpus precomputation for DPLR-FwFM serving
------------------------------------------------

The paper's Algorithm 1 caches the CONTEXT side per query; this module
extends the same caching argument to the ITEM side, across queries.  The
Proposition-1 projection ``P = U V`` is linear in the field embeddings, so
the entire item-side computation is context-independent and can be hoisted
out of the query loop — computed once per row per model refresh, and
re-computed for just the touched rows when the catalog churns:

    Q_I[i]   = U_I @ V_I[i]                      (cap, rho, k)
    t_I[i]   = sum_{f in item fields} d_f ||v_f||^2        (cap,)
    lin_I[i] = <b_item, x_item[i]>                         (cap,)

Per query, the scorer then only computes the context cache (P_C, s_C,
lin_C) and combines:

    score[q, i] = b0 + lin_C[q] + lin_I[i]
                + 0.5 * (s_C[q] + t_I[i] + sum_r e_r ||P_C[q,r] + Q_I[i,r]||^2)

dropping per-query per-item work from O(rho m_I k + m_I k) (Algorithm 1:
gather + project every candidate, every query) to O(rho k) — an
optimization the dense FwFM baseline structurally cannot do, because its
context-item term mixes the sides before any square is taken.

Slab/mask invariants (the mutable-corpus contract)
--------------------------------------------------
A deployed corpus is never static: ads enter and leave the marketplace
continuously (Section 5.3).  To absorb that churn without reshaping — and
therefore without ever retracing a jitted scorer — the cache is a
**capacity-padded slab**:

  * every array's leading axis is ``capacity`` (a fixed power of two),
    not the live item count; slot i of every array describes the same item;
  * ``valid`` (capacity,) bool marks live slots.  Scoring must treat
    ``valid[i] == False`` slots as score ``-inf`` so they can never win a
    top-K slot; values in dead slots are unspecified (stale or zero);
  * slot assignments are STABLE: mutations write only the touched rows and
    a model refresh rebuilds every row in place, so a corpus index returned
    to a caller keeps meaning the same item across add/remove/update and
    across model swaps;
  * growth is by slab doubling (amortized O(1) per added item); doubling is
    the only operation that changes shapes, hence the only one that can
    retrace downstream consumers.

A cache is a pure pytree, so it rebuilds under jit with one dispatch on
model refresh (the sliding-window retrain mode of Section 5.3) and the
engine's jitted scorer never retraces: only the array *values* change.

Sharded layout: when the engine runs with a mesh, every leaf's leading
``capacity`` axis is stored in the physical ``(capacity / D, D)`` view of
``repro.serving.sharded`` — global slot ``g`` at ``[g // D, g % D]``,
axis 1 sharded over the model axis — and ALL of the invariants above hold
per shard (the validity mask is shard-local, growth pads the local axis,
slot ids never renumber).
"""
from __future__ import annotations

from typing import NamedTuple

import numpy as np

import jax
import jax.numpy as jnp

from repro.core import ranking as rk
from repro.core.dplr import DPLRParams, dplr_diagonal
from repro.embedding.bag import (
    item_arena_ids,
    lookup_item_embeddings,
    lookup_linear_terms,
)
# Mask fill for dead slots — the ONE definition, shared with the Pallas
# kernel so the jnp and kernel paths return bit-identical scores for
# invalid slots.
from repro.kernels.dplr_corpus_score import NEG_INF
from repro.models.recsys import mind


def serving_precision():
    """Context for every f32 contraction on the serving path (item rows,
    context cache, the jnp scorer): full f32 precision.  The TPU's default
    for an f32 matmul is a single bf16 pass (~1e-3 relative), which would
    reorder near-tied candidates against the f32 model; the CPU computes
    f32 either way.  A fresh manager per use: one instance is not
    reentrant."""
    return jax.default_matmul_precision("highest")


def next_pow2(n: int) -> int:
    """Smallest power of two >= n (>= 1)."""
    return 1 << max(int(n) - 1, 0).bit_length()


class ItemCorpusCache(NamedTuple):
    """Context-independent per-item precomputations (one model, one corpus).

    Leading axis is the slab ``capacity``; ``valid`` marks live slots.
    """

    Q_I: jax.Array     # (cap, rho, k)  rank-space item projections U_I V_I
    t_I: jax.Array     # (cap,)         sum_f d_f ||v_f||^2 (item fields)
    lin_I: jax.Array   # (cap,)         first-order item term
    valid: jax.Array   # (cap,)         bool liveness mask

    @property
    def capacity(self) -> int:
        return self.Q_I.shape[0]

    @property
    def a_I(self) -> jax.Array:
        """(cap,) fused per-item scalar addend: lin_I + 0.5 * t_I."""
        return self.lin_I + 0.5 * self.t_I


def corpus_rows(params: dict, cfg, item_ids: jax.Array,
                item_weights: jax.Array, take_fn=None):
    """(Q_I, t_I, lin_I) rows for a batch of items — the per-row math of
    ``build_corpus_cache``, shared verbatim by the full build and the
    engine's delta updates so a scattered row is bit-identical to the same
    row in a from-scratch rebuild."""
    layout = cfg.layout
    nC = layout.n_context
    with serving_precision():
        p = DPLRParams(params["U"], params["e"])
        d = dplr_diagonal(p)
        V_I = lookup_item_embeddings(params["embedding"], layout, item_ids,
                                     item_weights, take_fn=take_fn)
        Q_I = jnp.einsum("rm,...mk->...rk", p.U[:, nC:], V_I)  # (n, rho, k)
        t_I = jnp.einsum("...mk,m->...", V_I * V_I, d[nC:])
        lin_I = lookup_linear_terms(params["linear"], layout.subset("item"),
                                    item_arena_ids(layout, item_ids),
                                    item_weights, take_fn=take_fn)
    return Q_I, t_I, lin_I


def masked_slab_scores(params: dict, Q_I, t_I, lin_I, valid,
                       P_C, s_C, lin_C) -> jax.Array:
    """(Bq, n) fused masked scores for a slab slice against a batch of
    context caches — the ONE definition of the jnp scoring math, shared by
    the single-device engine (full slab) and every shard of the sharded
    engine (its local slice), so the two paths are bit-identical per slot:
    the reduction runs over (rho, k) only, which splitting the ITEM axis
    across shards cannot perturb."""
    P = P_C[:, None] + Q_I[None]                       # (Bq, n, rho, k)
    with serving_precision():
        term_e = jnp.einsum("qnrk,r->qn", P * P, params["e"])
    pw = 0.5 * (s_C[:, None] + t_I[None, :] + term_e)
    s = params["bias"] + lin_C[:, None] + lin_I[None, :] + pw
    # dead slots pinned to -inf: they can never win a top-K slot, and the
    # fill matches the Pallas kernel's padding sentinel bit-for-bit.
    return jnp.where(valid[None, :], s, NEG_INF)


def build_corpus_cache(params: dict, cfg, item_ids: jax.Array,
                       item_weights: jax.Array, take_fn=None, *,
                       capacity: int | None = None,
                       valid: jax.Array | None = None) -> ItemCorpusCache:
    """Precompute the item side for a candidate-corpus slab.

    ``item_ids``/``item_weights``: (n, n_item_slots) local item-side slot
    ids, exactly the per-candidate rows ``rank_items`` receives per query.

    ``capacity``: pad the slab's leading axis to this size (rows beyond n
    are zero-id filler marked invalid).  Default: no padding (capacity=n).
    ``valid``: (capacity,) liveness mask — pass the engine's mask when
    rebuilding a churned slab in place so dead slots STAY dead; default
    marks exactly the first n rows live.

    Pure and traceable — the engine jits it so a model refresh is one
    dispatch.  O(cap m_I k) once per (corpus, model), amortized over every
    subsequent query.
    """
    assert cfg.interaction == "dplr", "corpus precompute requires DPLR"
    item_ids = jnp.asarray(item_ids)
    n = item_ids.shape[0]
    if capacity is not None:
        if capacity < n:
            raise ValueError(f"capacity={capacity} < corpus size n={n}")
        pad = capacity - n
        if pad:
            item_ids = jnp.pad(item_ids, ((0, pad), (0, 0)))
            item_weights = jnp.pad(jnp.asarray(item_weights),
                                   ((0, pad), (0, 0)))
    cap = item_ids.shape[0]
    if valid is None:
        valid = jnp.arange(cap) < n
    Q_I, t_I, lin_I = corpus_rows(params, cfg, item_ids, item_weights,
                                  take_fn=take_fn)
    return ItemCorpusCache(Q_I=Q_I, t_I=t_I, lin_I=lin_I,
                           valid=jnp.asarray(valid))


# -- the models on the serving contract -------------------------------------

def scorer_for(cfg):
    """The serving scorer of a model config: MIND for a ``MINDConfig``,
    else DPLR-FwFM (which requires ``interaction='dplr'``)."""
    if isinstance(cfg, mind.MINDConfig):
        return MINDScorer(cfg)
    return DPLRScorer(cfg)


class DPLRScorer:
    """DPLR-FwFM on the serving contract: slab rows ``(Q_I, t_I, lin_I)``,
    the per-query context cache ``(P_C, s_C, lin_C)`` as the query state,
    and the fused square-sum scorer (``kernels/dplr_corpus_score.py``)."""

    span = None

    def __init__(self, cfg):
        if cfg.interaction != "dplr":
            raise ValueError("ScorerRuntime requires interaction='dplr'")
        self.cfg = cfg
        self.query_width = len(cfg.layout.slots_of("context"))

    def signature(self) -> tuple:
        lay = self.cfg.layout
        return (lay.n_context, lay.n_item, self.cfg.embed_dim, self.cfg.rank)

    def rows(self, params, ids, w):
        return corpus_rows(params, self.cfg, ids, w)

    def build(self, params, ids, w, valid):
        return build_corpus_cache(params, self.cfg, ids, w, valid=valid)

    def query(self, params, ctx_ids, ctx_w):
        """Per-query context cache: P_C (Bq, rho, k), s_C (Bq,), lin_C (Bq,)."""
        from repro.models.recsys.fwfm import context_inputs
        with serving_precision():
            V_C, lin_C = context_inputs(params, self.cfg, ctx_ids, ctx_w)
            p = DPLRParams(params["U"], params["e"])
            ctx = rk.dplr_context_cache(p, V_C, self.cfg.layout.n_context)
        return ctx.P_C, ctx.s_C, lin_C

    def scores(self, params, cache, q):
        # direct fused form — same reduction order as rank_items, so the
        # corpus-cached path is float32-epsilon-close to the per-query
        # path; shared with the sharded runtime so the two are
        # bit-identical per slot.
        return masked_slab_scores(params, cache.Q_I, cache.t_I, cache.lin_I,
                                  cache.valid, *q)

    def kernel(self, params, cache, q, K, block_n):
        from repro.kernels import ops as kops
        P_C, s_C, lin_C = q
        a_C = params["bias"] + lin_C + 0.5 * s_C
        return kops.dplr_corpus_score(cache.Q_I, cache.a_I, params["e"],
                                      P_C, a_C, valid=cache.valid, topk=K,
                                      block_n=block_n)

    def kernel_multi(self, params_parts, cache_parts, q_parts, K, block_n):
        """ONE tenant-segmented kernel launch
        (``kernels.dplr_corpus_score_multi``) covers every segment's slab
        — the per-segment running top-K never mixes tenants' slots."""
        from repro.kernels import ops as kops
        pcs, acs, es = [], [], []
        for params, (P_C, s_C, lin_C) in zip(params_parts, q_parts):
            pcs.append(P_C)
            acs.append(params["bias"] + lin_C + 0.5 * s_C)
            es.append(params["e"])
        return kops.dplr_corpus_score_multi(
            tuple(c.Q_I for c in cache_parts),
            tuple(c.a_I for c in cache_parts),
            tuple(c.valid for c in cache_parts),
            jnp.stack(es), jnp.stack(pcs), jnp.stack(acs),
            topk=K, block_n=block_n)

    @staticmethod
    def vectors(ctx_ids) -> tuple[int, int]:
        """One query vector per row, always live."""
        return len(ctx_ids), len(ctx_ids)


def take_rows(table: jax.Array, ids: jax.Array) -> jax.Array:
    """Rows ``ids`` of a ``(rows, d)`` table, gathered through its
    ``(rows, d / 16, 16)`` view when 16 divides a wider ``d``.  The TPU
    keeps an ``(rows, 64)`` f32 array items-minor and, asked for whole
    rows of it, first relayouts the whole table (a 2x padded copy: 6.4 GB
    for MIND's arena of 12.5M rows, on every launch); the 16-wide view
    gathers the same bytes in place (v5e compile: no temporary)."""
    n, d = table.shape
    if d <= 16 or d % 16:
        return jnp.take(table, ids, axis=0)
    return jnp.take(table.reshape(n, d // 16, 16), ids, axis=0).reshape(
        *ids.shape, d)


class MINDSlab(NamedTuple):
    """MIND's item slab: each slot's item-embedding row (the arena row of
    its item id) and the liveness mask.  The TPU stores ``E`` with the
    slot axis minor, so the kernel reads it transposed at no copy."""

    E: jax.Array       # (cap, d) item embedding rows
    valid: jax.Array   # (cap,)   bool liveness mask

    @property
    def capacity(self) -> int:
        return self.E.shape[0]


class MINDScorer:
    """MIND on the serving contract.  A query row is the ``m_p`` profile
    ids followed by the ``seq_len`` history item ids, a history id below
    0 marking padding (its weight is not read: padding rides in the
    ids).  The query state is the user tower's ``(Bq, K, d)`` interest
    vectors and ``(Bq, K)`` live mask, computed on the device in the
    launch's own program; an item row is one id (weights are not read
    on either side)."""

    span = "engine.mind_launch"

    def __init__(self, cfg):
        self.cfg = cfg
        self.n_profile = cfg.layout.n_context
        self.query_width = self.n_profile + cfg.seq_len
        self.item_offset = int(cfg.layout.field_offsets[cfg.layout.n_context])

    def signature(self) -> tuple:
        c = self.cfg
        return ("mind", c.layout.n_context, c.embed_dim, c.n_interests,
                c.capsule_iters, c.seq_len, c.hidden)

    def rows(self, params, ids, w):
        return (take_rows(params["embedding"], ids[:, 0] + self.item_offset),)

    def build(self, params, ids, w, valid):
        return MINDSlab(self.rows(params, ids, w)[0], valid)

    def query(self, params, ids, w):
        hist = ids[:, self.n_profile:]
        mask = (hist >= 0).astype(params["S"].dtype)
        return mind.user_tower(params, self.cfg, ids[:, :self.n_profile],
                               jnp.maximum(hist, 0), mask, take_fn=take_rows)

    def scores(self, params, cache, q):
        U, live = q
        with serving_precision():
            s = jnp.einsum("qkd,nd->qkn", U, cache.E)
        s = jnp.where(live[..., None], s, NEG_INF).max(axis=1)
        return jnp.where(cache.valid[None, :], s, NEG_INF)

    def kernel(self, params, cache, q, K, block_n):
        from repro.kernels import ops as kops
        U, live = q
        return kops.mind_corpus_score(cache.E, U, live, cache.valid, topk=K,
                                      block_n=block_n)

    def kernel_multi(self, params_parts, cache_parts, q_parts, K, block_n):
        """S launches of the kernel in one program, one per segment."""
        vals, idx = zip(*(self.kernel(p, c, q, K, block_n) for p, c, q in
                          zip(params_parts, cache_parts, q_parts)))
        return jnp.stack(vals), jnp.stack(idx)

    def vectors(self, ids) -> tuple[int, int]:
        """(interest vectors scanned, those live) for a batch of query
        rows: K per row, of which K'_u live."""
        K = self.cfg.n_interests
        lengths = (np.asarray(ids)[:, self.n_profile:] >= 0).sum(axis=1)
        return len(lengths) * K, int(mind.interest_counts(lengths, K).sum())
