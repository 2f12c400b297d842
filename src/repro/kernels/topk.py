"""The running top-K merge the corpus kernels share.

A corpus kernel walks the slab in item tiles and keeps each query row's
best K (values and slot labels) in output blocks that stay resident in
VMEM across the grid.  Mosaic has no ``top_k`` lowering, so each tile is
merged in by ``merge_running_topk``: K rounds of row max and
lowest-position select, which is ``lax.top_k``'s contract on the concat
``[running, tile]`` (rows best-first, ties to the lowest position).
``dplr_corpus_score`` and ``mind_corpus_score`` both call it, so a change
to the merge is measured on both models' cells.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def merge_running_topk(val_ref, idx_ref, scores, labels):
    """Merge one tile's ``(Bq, block_n)`` scores (with their int32 index
    labels) into the running ``(Bq, K)`` top-K held in ``val_ref`` /
    ``idx_ref``: ``lax.top_k`` over the concat ``[running, tile]``, built
    from primitives Mosaic lowers.  Round ``r`` takes each row's max, picks
    the LOWEST concat position holding it (running entries first, then
    the tile's rows in order — the top_k tie rule), writes it to column
    ``r`` and retires it to ``-inf`` (below every live score and below
    the NEG_INF dead-slot sentinel, so a retired entry is never re-picked
    while untaken candidates remain)."""
    run_v, run_i = val_ref[...], idx_ref[...]
    K = run_v.shape[1]
    pos_r = jax.lax.broadcasted_iota(jnp.int32, run_v.shape, 1)
    pos_t = K + jax.lax.broadcasted_iota(jnp.int32, scores.shape, 1)
    past = K + scores.shape[1]               # one past the last position
    lowest = jnp.iinfo(jnp.int32).min

    def row_max(a, b):
        return jnp.maximum(jnp.max(a, axis=1, keepdims=True),
                           jnp.max(b, axis=1, keepdims=True))

    def pick(r, carry):
        cand_r, cand_t, out_v, out_i = carry
        m = row_max(cand_r, cand_t)
        p = jnp.minimum(
            jnp.min(jnp.where(cand_r == m, pos_r, past), axis=1,
                    keepdims=True),
            jnp.min(jnp.where(cand_t == m, pos_t, past), axis=1,
                    keepdims=True))
        lab = row_max(jnp.where(pos_r == p, run_i, lowest),
                      jnp.where(pos_t == p, labels, lowest))
        col = pos_r == r
        return (jnp.where(pos_r == p, -jnp.inf, cand_r),
                jnp.where(pos_t == p, -jnp.inf, cand_t),
                jnp.where(col, m, out_v), jnp.where(col, lab, out_i))

    _, _, top_v, top_i = jax.lax.fori_loop(
        0, K, pick, (run_v, scores, run_v, run_i))
    val_ref[...] = top_v
    idx_ref[...] = top_i
