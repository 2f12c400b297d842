"""Pallas TPU kernel for MIND retrieval over an item slab (+ fused top-K).

MIND (``models/recsys/mind.py``) scores an item by the best of a user's
interest vectors:

    score[q, i] = max over live k of <U[q, k], E[i]>

with ``U (Bq, K, d)`` the user tower's interest vectors (computed per
launch, on the device, in the same jitted program) and ``E (n, d)`` the
item slab, one embedding row per slot (``serving/corpus.py``
``MINDScorer``).  Interests past a user's adaptive count K'_u are dead
(``live[q, k] == False``) and never win.

Layout: the TPU stores an ``(n, d)`` f32 array with the item axis minor
(``{0,1}`` tiling), so the wrapper's ``transpose`` to ``E_T (d, n)`` is a
bitcast and the kernel streams ``(d, block_n)`` tiles with the items on
the vreg's lanes.  Per tile the interest-by-item product is ONE MXU
matmul, ``(K * rows, d) x (d, block_n)``, at full f32 precision
(``Precision.HIGHEST``: the default for an f32 matmul on the TPU is a
single bf16 pass, ~1e-3 relative, far outside the f32 contract).  The
interest rows are laid out k-major with the query rows padded to a
multiple of 8 (``rows``), so the max over interests is K sublane-aligned
slices.  Dead interests are pinned to ``NEG_INF`` before the max and dead
slots after it, so neither can win a top-K slot.

Two output modes, as ``dplr_corpus_score``: ``(Bq, n)`` scores, or the
fused running top-K (``topk.merge_running_topk``, the merge the DPLR
kernel uses; ties to the lowest slot, the ``lax.top_k`` rule) carried in
VMEM-resident output blocks, so only ``(Bq, K)`` values and slots leave
the kernel.  A bf16 slab is read as stored and widened to f32 inside the
kernel.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import blocks
from repro.kernels.dplr_corpus_score import NEG_INF
from repro.kernels.topk import merge_running_topk


def _tile_scores(e_t, u, live, m, rows):
    """``(rows, block_n)`` scores of one item tile: ``e_t (d, block_n)``
    the tile, ``u (K * rows, d)`` the interest rows (k-major), ``live (K
    * rows, 1)`` and ``m (1, block_n)`` the {0,1} interest and slot
    liveness."""
    s = jax.lax.dot_general(u, e_t.astype(jnp.float32),
                            (((1,), (0,)), ((), ())),
                            precision=jax.lax.Precision.HIGHEST,
                            preferred_element_type=jnp.float32)
    s = jnp.where(live != 0, s, NEG_INF)
    best = s[:rows]
    for k in range(1, s.shape[0] // rows):
        best = jnp.maximum(best, s[k * rows:(k + 1) * rows])
    return jnp.where(m != 0, best, NEG_INF)


def _kernel_full(e_ref, m_ref, u_ref, live_ref, out_ref, *, rows):
    out_ref[...] = _tile_scores(e_ref[...], u_ref[...], live_ref[...],
                                m_ref[...], rows)


def _kernel_topk(e_ref, m_ref, u_ref, live_ref, val_ref, idx_ref, *,
                 rows, block_n):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        val_ref[...] = jnp.full_like(val_ref, NEG_INF)
        idx_ref[...] = jnp.zeros_like(idx_ref)

    scores = _tile_scores(e_ref[...], u_ref[...], live_ref[...], m_ref[...],
                          rows)
    labels = i * block_n + jax.lax.broadcasted_iota(jnp.int32, scores.shape,
                                                    1)
    merge_running_topk(val_ref, idx_ref, scores, labels)


@functools.partial(jax.jit, static_argnames=("topk", "block_n", "interpret"))
def mind_corpus_score(
    E: jax.Array,      # (n, d)     item slab: one embedding row per slot
    U: jax.Array,      # (Bq, K, d) interest vectors of each query
    live: jax.Array,   # (Bq, K)    bool: interest k of query q is live
    valid: jax.Array | None = None,   # (n,) slot liveness; None = all live
    *,
    topk: int | None = None,
    block_n: int = blocks.MIND_TILE_N,
    interpret: bool = False,
):
    """Max-over-interests scorer.  Returns ``(Bq, n)`` scores (dead slots
    exactly ``NEG_INF``), or with ``topk=K`` the fused ``((Bq, K) scores,
    (Bq, K) int32 slots)`` over live slots, best first.  Every query needs
    at least one live interest."""
    n, d = E.shape
    Bq, K, _ = U.shape
    rows = -(-Bq // 8) * 8
    block_n = blocks.clamp_tile(block_n, n)
    pad = blocks.pad_amount(n, block_n)
    mask = (jnp.ones((n,), jnp.int32) if valid is None
            else jnp.asarray(valid).astype(jnp.int32))
    E_T = jnp.pad(jnp.transpose(E), ((0, 0), (0, pad)))
    m_row = jnp.pad(mask, (0, pad))[None]
    u = jnp.pad(jnp.transpose(U.astype(jnp.float32), (1, 0, 2)),
                ((0, 0), (0, rows - Bq), (0, 0))).reshape(K * rows, d)
    lv = jnp.pad(jnp.transpose(live).astype(jnp.int32),
                 ((0, 0), (0, rows - Bq))).reshape(K * rows, 1)
    grid = blocks.grid_1d(n + pad, block_n)
    in_specs = [blocks.col_tiles(d, block_n), blocks.col_tiles(1, block_n),
                blocks.broadcast(K * rows, d), blocks.broadcast(K * rows, 1)]

    if topk is None:
        return pl.pallas_call(
            functools.partial(_kernel_full, rows=rows),
            grid=grid, in_specs=in_specs,
            out_specs=blocks.col_tiles(rows, block_n),
            out_shape=jax.ShapeDtypeStruct((rows, n + pad), jnp.float32),
            interpret=interpret,
            name="mind_corpus_score_full",
        )(E_T, m_row, u, lv)[:Bq, :n]

    if not 0 < topk <= n:
        raise ValueError(f"topk={topk} out of range for n={n}")
    vals, idx = pl.pallas_call(
        functools.partial(_kernel_topk, rows=rows, block_n=block_n),
        grid=grid, in_specs=in_specs,
        # constant index map => the running (values, slots) pair stays
        # VMEM-resident across every item tile
        out_specs=[blocks.broadcast(rows, topk),
                   blocks.broadcast(rows, topk)],
        out_shape=[jax.ShapeDtypeStruct((rows, topk), jnp.float32),
                   jax.ShapeDtypeStruct((rows, topk), jnp.int32)],
        interpret=interpret,
        name="mind_corpus_score_topk",
    )(E_T, m_row, u, lv)
    return vals[:Bq], idx[:Bq]
