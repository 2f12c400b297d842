"""Pure-jnp oracles for every Pallas kernel (the tests sweep shapes/dtypes
and assert_allclose kernel-vs-ref)."""
from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp


def dplr_score_items_ref(V_I, U_I, e, d_I, P_C, s_C):
    P = P_C[None] + jnp.einsum("rm,nmk->nrk", U_I, V_I)
    term_e = jnp.einsum("nrk,r->n", P * P, e)
    term_d = jnp.einsum("nmk,m->n", V_I * V_I, d_I)
    return 0.5 * (s_C + term_d + term_e)


def dplr_corpus_score_ref(Q_I, a_I, e, P_C, a_C, valid=None):
    """(Bq, n) corpus-cached scores: a_C + a_I + 0.5 e.||P_C + Q_I||^2,
    with dead slots (``valid[i] == False``) pinned to the kernel's
    NEG_INF sentinel."""
    from repro.kernels.dplr_corpus_score import NEG_INF
    P = P_C[:, None] + Q_I[None]
    term_e = jnp.einsum("qnrk,r->qn", P * P, e)
    s = a_C[:, None] + a_I[None, :] + 0.5 * term_e
    if valid is not None:
        s = jnp.where(jnp.asarray(valid)[None, :], s, NEG_INF)
    return s


def dplr_corpus_topk_ref(Q_I, a_I, e, P_C, a_C, topk, valid=None,
                         index_offset=0, index_stride=1):
    """argsort-based top-K oracle: ((Bq, K) scores, (Bq, K) indices).

    ``index_offset``/``index_stride`` relabel local row ``i`` as
    ``offset + stride * i`` — the sharded slab's striped global slot ids
    (mirrors the kernel's shard-local index semantics)."""
    s = dplr_corpus_score_ref(Q_I, a_I, e, P_C, a_C, valid)
    idx = jnp.argsort(-s, axis=1)[:, :topk].astype(jnp.int32)
    vals = jnp.take_along_axis(s, idx, axis=1)
    return vals, index_offset + index_stride * idx


def dplr_corpus_multi_topk_ref(Q_parts, a_parts, valid_parts, e, P_C, a_C,
                               topk, index_offset=0, index_stride=1):
    """Tenant-segmented top-K oracle: the fused multi-segment kernel must
    equal S independent single-segment top-K passes stacked to
    ``((S, Bq, K) scores, (S, Bq, K) indices)`` — segment ``s`` scored
    only against its own corpus part with its own eigen-weights, indices
    segment-local before the offset/stride relabel."""
    if valid_parts is None:
        valid_parts = (None,) * len(Q_parts)
    vals, idx = zip(*(
        dplr_corpus_topk_ref(Q_parts[s], a_parts[s], e[s], P_C[s], a_C[s],
                             topk, valid_parts[s], index_offset,
                             index_stride)
        for s in range(len(Q_parts))))
    return jnp.stack(vals), jnp.stack(idx)


def mind_corpus_score_ref(E, U, live, valid=None):
    """(Bq, n) max-over-live-interests scores ``max_k <U[q, k], E[i]>``
    at full f32 precision, dead slots pinned to NEG_INF."""
    from repro.kernels.dplr_corpus_score import NEG_INF
    with jax.default_matmul_precision("highest"):
        s = jnp.einsum("qkd,nd->qkn", U.astype(jnp.float32),
                       E.astype(jnp.float32))
    s = jnp.where(jnp.asarray(live)[..., None], s, NEG_INF).max(axis=1)
    if valid is not None:
        s = jnp.where(jnp.asarray(valid)[None, :], s, NEG_INF)
    return s


def mind_corpus_topk_ref(E, U, live, topk, valid=None):
    """``lax.top_k`` of ``mind_corpus_score_ref``: ((Bq, K) scores,
    (Bq, K) slots), ties to the lowest slot."""
    return jax.lax.top_k(mind_corpus_score_ref(E, U, live, valid), topk)


def fwfm_pairwise_ref(V, R):
    G = jnp.einsum("bik,bjk->bij", V, V)
    return 0.5 * jnp.einsum("bij,ij->b", G, R)


def embedding_bag_ref(table, ids, weights, segment_ids, n_bags):
    flat = jnp.take(table, ids, axis=0)
    weighted = flat * weights[..., None].astype(flat.dtype)
    out = jnp.zeros((ids.shape[0], n_bags, table.shape[-1]), flat.dtype)
    return out.at[:, np.asarray(segment_ids), :].add(weighted)


def flash_attention_ref(q, k, v, *, causal=True, window=None):
    """(B, S, H, hd) x (B, S, KV, hd) GQA reference in f32."""
    B, Sq, H, hd = q.shape
    KV = k.shape[2]
    G = H // KV
    qg = q.reshape(B, Sq, KV, G, hd).astype(jnp.float32)
    logits = jnp.einsum("bqkgh,bskh->bkgqs", qg, k.astype(jnp.float32))
    logits = logits / np.sqrt(hd)
    q_pos = jnp.arange(Sq)[:, None]
    k_pos = jnp.arange(k.shape[1])[None, :]
    mask = jnp.ones((Sq, k.shape[1]), bool)
    if causal:
        mask &= k_pos <= q_pos
    if window is not None:
        mask &= k_pos > q_pos - window
    logits = jnp.where(mask, logits, -1e30)
    p = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bkgqs,bskh->bqkgh", p, v.astype(jnp.float32))
    return out.reshape(B, Sq, H, hd)


# The declared kernel -> oracle contract.  Every Pallas entry point in
# this package MUST appear here (tools/analyze rule KRN-ORACLE checks
# the map statically; tests/test_kernels.py sweeps each pair).  A kernel
# with two output modes maps to a tuple of oracles.
ORACLES = {
    "dplr_score_items": (dplr_score_items_ref,),
    "dplr_corpus_score": (dplr_corpus_score_ref, dplr_corpus_topk_ref),
    "dplr_corpus_score_multi": (dplr_corpus_multi_topk_ref,),
    "mind_corpus_score": (mind_corpus_score_ref, mind_corpus_topk_ref),
    "fwfm_pairwise": (fwfm_pairwise_ref,),
    "embedding_bag": (embedding_bag_ref,),
    "flash_attention": (flash_attention_ref,),
}
