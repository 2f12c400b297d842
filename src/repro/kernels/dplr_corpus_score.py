"""Pallas TPU kernel for corpus-precomputed DPLR-FwFM scoring (+ fused top-K).

This is the serving-engine hot op.  Everything item-side is context-
independent, so it is PRECOMPUTED into the mutable corpus slab
(``repro.serving.corpus``) — once per model refresh for the full slab,
per-row for churn deltas:

    Q_I[i] = U_I @ V_I[i]                  (rho, k)   rank-space projection
    a_I[i] = lin_I[i] + 0.5 * t_I[i]       ()         per-item scalar addend

Per (query q, item i) the score is then

    score[q, i] = a_C[q] + a_I[i] + 0.5 * sum_r e_r ||P_C[q, r] + Q_I[i, r]||^2

with ``P_C (Bq, rho, k)`` / ``a_C (Bq,)`` the per-query context cache.  The
kernel tiles the ITEM axis: one grid step holds a ``(rho, k, block_n)``
tile of ``Q_T = transpose(Q_I)`` in VMEM, so HBM traffic is ONE pass over
the slab — strictly less than the ``(n, m_I, k)`` pass of
``dplr_score.py`` (the Algorithm-1 kernel that still re-projects item
embeddings per query), by the factor m_I / rho (~12x for the paper's
deployed geometry).

Layout: the item axis sits on the vreg's 128 lanes and k on its
sublanes, so every add, square and k-sum of the scoring runs on full
vregs (a ``(.., rho, k)`` minor pair would pad (3, 16) to one (8, 128)
tile, 21x).  The wrapper transposes the slab once per launch; ``a_I`` and
the liveness mask enter as ``(1, n)`` rows, ``P_C`` as ``(rho, rows, k,
1)`` so each query's k-vector broadcasts across lanes, and the tile's
scores come out ``(rows, block_n)`` — the layout of the merge and of the
full-mode output.

Two output modes:
  * full   — ``(Bq, n)`` logits, out block revisited per item tile.
  * top-K  — running per-query top-K carried in the OUTPUT blocks across
    grid steps (constant index_map => the block stays resident in VMEM);
    each step merges its tile's scores into the running (values, indices)
    pair, so only ``(Bq, K)`` floats + ints ever leave the scorer.  Mosaic
    has no ``top_k`` lowering, so the merge (``_merge_topk``) is K rounds
    of row max + lowest-position select over ``[running, tile]`` — the
    exact ``lax.top_k`` contract on that concat: rows best-first, ties to
    the lowest position (running entries first, then the tile in order).

Validity mask: the serving corpus is a capacity-padded MUTABLE slab
(``repro.serving.corpus``), so the kernel takes an optional ``valid`` (n,)
mask and pins dead slots to exactly ``NEG_INF`` inside each tile — before
the running top-K merge — so a dead (or phantom-padding) slot can never win
a top-K slot.  Padding: n is padded up to a block multiple with
``valid = 0`` phantom rows; the full mode slices them off.

Shard-local semantics: when the slab is sharded across a device mesh
(``repro.serving.sharded``), each shard calls this kernel on its LOCAL
(n/D, rho, k) slice with its LOCAL validity mask — masking is a per-shard
concern and needs no cross-device view.  The top-K indices the kernel
emits, however, must be mesh-GLOBAL so the D-way candidate merge can
compare them; ``index_offset``/``index_stride`` relabel row ``i`` of the
local slice as ``index_offset + index_stride * i`` inside the running
top-K (striped slot ownership uses ``offset=shard, stride=D``; the
single-device engine keeps the identity labeling 0,1,2,...).

Accumulation dtype: ``acc_dtype='bfloat16'`` runs the O(Bq n rho k)
eigen-weighted square-sum reduction in bf16 (halving the MXU/VPU input
traffic where the slab dtype already sacrificed the precision) and
upcasts to f32 BEFORE masking and the running top-K merge, so sentinel
comparisons and tie-breaking stay exact.  The default ``'float32'``
keeps every step in f32.  The autotuner sweeps this
knob only for bf16 slabs; scores are tolerance-gated, not bit-exact.

Multi-segment mode: ``dplr_corpus_score_multi`` scores S tenants'
micro-batches in ONE launch.  The per-segment corpus slabs concatenate
on the item axis (each padded to a whole number of tiles), the S
micro-batches stack into one (S*Bq, ...) context block, and each grid
step derives its segment from ``program_id`` against the STATIC per-
segment tile boundaries (trace-time constants — no metadata operand):
rows outside the segment's query window are pinned to NEG_INF before the
running top-K merge, so a segment's top-K can NEVER surface a neighbor
segment's slot, and emitted indices are segment-LOCAL (the row base
restarts at 0 per segment) relabeled by the same
``index_offset``/``index_stride`` rule as the single-tenant mode.
``index_offset`` is a scalar read from SMEM in both modes.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import blocks

NEG_INF = -1e30


def _tile_scores(q, a_i, e, pc, a_c, m, acc_dtype=jnp.float32):
    """``(rows, block_n)`` scores of one item tile, items on lanes.

    ``q (rho, k, block_n)`` is the tile of ``Q_T``, ``a_i``/``m`` its
    ``(1, block_n)`` item scalars and {0,1} liveness, ``e (rows, rho)``
    each query row's eigen-weights, ``pc (rho, rows, k, 1)`` and ``a_c
    (rows, 1)`` the context side.  Per rank: ``sum_k (P + Q)^2`` in the
    direct fused form, then the e-weighted sum over rho, in
    ``acc_dtype``, as VPU adds and multiplies: an in-kernel matmul would
    run at the TPU's default f32 matmul precision (a single bf16 pass),
    far outside the f32 contract.  Dead slots are pinned to exactly
    NEG_INF so they can never win a top-K slot."""
    term = None
    for r in range(q.shape[0]):
        d = q[r][None] + pc[r]                            # (rows, k, bn)
        sq = jnp.sum((d * d).astype(acc_dtype), axis=1)   # (rows, bn)
        t = sq * e[:, r:r + 1].astype(acc_dtype)
        term = t if term is None else term + t
    s = a_c + a_i + 0.5 * term.astype(jnp.float32)
    return jnp.where(m != 0, s, NEG_INF)


def _kernel_full(q_ref, a_ref, e_ref, pc_ref, ac_ref, m_ref, out_ref, *,
                 acc_dtype):
    out_ref[...] = _tile_scores(
        q_ref[...], a_ref[...], e_ref[...], pc_ref[...], ac_ref[...],
        m_ref[...], acc_dtype)


def _merge_topk(val_ref, idx_ref, scores, labels):
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


def _kernel_topk(q_ref, a_ref, e_ref, pc_ref, ac_ref, m_ref, off_ref,
                 val_ref, idx_ref, *, seg_tiles: tuple, Bq: int,
                 block_n: int, index_stride: int, acc_dtype):
    """One item tile's scores merged into the running top-K.  With S > 1
    segments (``seg_tiles`` holds each one's static ``[first, stop)``
    tile range) the rows are S stacked micro-batches of ``Bq`` and a tile
    counts only for its own segment's rows."""
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        val_ref[...] = jnp.full_like(val_ref, NEG_INF)
        idx_ref[...] = jnp.zeros_like(idx_ref)

    scores = _tile_scores(
        q_ref[...], a_ref[...], e_ref[...], pc_ref[...], ac_ref[...],
        m_ref[...], acc_dtype)
    # column c of this tile is segment-local slot row_base + c; the
    # emitted index is its caller-defined label off + stride * local
    row_base = i * block_n
    if len(seg_tiles) > 1:
        # which segment this tile belongs to, from the static tile
        # boundaries: its stacked query rows [q_off, q_off + Bq) and its
        # first segment-LOCAL item row
        q_off = 0
        for s, (first, stop) in enumerate(seg_tiles):
            in_seg = (i >= first) & (i < stop)
            q_off = jnp.where(in_seg, s * Bq, q_off)
            row_base = jnp.where(in_seg, (i - first) * block_n, row_base)
        qidx = jax.lax.broadcasted_iota(jnp.int32, scores.shape, 0)
        own = (qidx >= q_off) & (qidx < q_off + Bq)
        # a foreign row sees this tile as all-NEG_INF, so its running
        # top-K is untouched by neighbor segments' item tiles
        scores = jnp.where(own, scores, NEG_INF)
    labels = off_ref[0] + index_stride * (
        row_base + jax.lax.broadcasted_iota(jnp.int32, scores.shape, 1))
    _merge_topk(val_ref, idx_ref, scores, labels)


def _item_rows(Q_I, a_I, valid, pad):
    """The item side, lane-dense: ``Q_T (rho, k, n + pad)`` and ``(1, n +
    pad)`` rows of ``a_I`` and the int32 liveness mask; phantom padding
    is dead."""
    n = Q_I.shape[0]
    mask = (jnp.ones((n,), jnp.int32) if valid is None
            else jnp.asarray(valid).astype(jnp.int32))
    Q_T = jnp.transpose(Q_I.astype(jnp.float32), (1, 2, 0))
    return (jnp.pad(Q_T, ((0, 0), (0, 0), (0, pad))),
            jnp.pad(a_I.astype(jnp.float32), (0, pad))[None],
            jnp.pad(mask, (0, pad))[None])


def _context_args(e_rows, P_C, a_C):
    """The query side for ``rows`` stacked query rows: ``e (rows, rho)``,
    ``P_C (rho, rows, k, 1)`` (a query's k-vector on sublanes, broadcast
    across the item lanes) and ``a_C (rows, 1)``."""
    pc = jnp.transpose(P_C.astype(jnp.float32), (1, 0, 2))[..., None]
    return e_rows.astype(jnp.float32), pc, a_C.astype(jnp.float32)[:, None]


def _in_specs(rho, k, rows, block_n):
    """Blocks of ``(Q_T, a_I, e, P_C, a_C, mask)``: item operands tiled
    on the lane axis, query operands whole and VMEM-resident."""
    return [
        blocks.col_tiles(rho, k, block_n),
        blocks.col_tiles(1, block_n),
        blocks.broadcast(rows, rho),
        blocks.broadcast(rho, rows, k, 1),
        blocks.broadcast(rows, 1),
        blocks.col_tiles(1, block_n),
    ]


def _topk_call(args, *, seg_tiles, Bq, topk, block_n, index_offset,
               index_stride, acc_dtype, interpret, name):
    """The fused top-K ``pallas_call`` over ``args = (Q_T, a_I, e, P_C,
    a_C, mask)`` laid out by ``_item_rows`` / ``_context_args``."""
    rho, k, n_pad = args[0].shape
    rows = args[2].shape[0]
    off = jnp.asarray(index_offset, jnp.int32).reshape(1)
    kernel = functools.partial(_kernel_topk, seg_tiles=seg_tiles, Bq=Bq,
                               block_n=block_n, index_stride=index_stride,
                               acc_dtype=acc_dtype)
    return pl.pallas_call(
        kernel,
        grid=blocks.grid_1d(n_pad, block_n),
        in_specs=_in_specs(rho, k, rows, block_n) + [blocks.smem()],
        out_specs=[
            # constant index map => the running (values, indices) pair
            # stays VMEM-resident across every item tile
            blocks.broadcast(rows, topk),
            blocks.broadcast(rows, topk),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((rows, topk), jnp.float32),
            jax.ShapeDtypeStruct((rows, topk), jnp.int32),
        ],
        interpret=interpret,
        name=name,
    )(*args, off)


@functools.partial(jax.jit,
                   static_argnames=("topk", "block_n", "interpret",
                                    "index_stride", "acc_dtype"))
def dplr_corpus_score(
    Q_I: jax.Array,    # (n, rho, k)  precomputed item projections
    a_I: jax.Array,    # (n,)         per-item scalar (lin_I + 0.5 * t_I)
    e: jax.Array,      # (rho,)       DPLR eigen-weights
    P_C: jax.Array,    # (Bq, rho, k) cached context projections
    a_C: jax.Array,    # (Bq,)        per-query scalar (b0 + lin_C + 0.5*s_C)
    valid: jax.Array | None = None,   # (n,) slot liveness; None = all live
    *,
    topk: int | None = None,
    block_n: int = blocks.CORPUS_TILE_N,
    interpret: bool = False,
    index_offset: jax.Array | int = 0,
    index_stride: int = 1,
    acc_dtype: str = "float32",
):
    """Corpus-cached batched scorer.  Returns ``(Bq, n)`` scores (dead
    slots exactly ``NEG_INF``), or with ``topk=K`` the fused ``((Bq, K)
    scores, (Bq, K) int32 indices)`` over LIVE slots only.

    ``index_offset``/``index_stride`` relabel the top-K indices: local row
    ``i`` reports as ``index_offset + index_stride * i`` (used by the
    sharded slab, whose shard ``s`` of ``D`` owns the striped global slots
    ``s, s + D, s + 2D, ...``).  ``index_offset`` may be traced (e.g. an
    ``axis_index`` inside ``shard_map``); the stride is static.

    ``acc_dtype``: accumulation dtype of the rank-space reduction
    (``'float32'`` default = every step in f32; ``'bfloat16'``
    trades the reduction's precision for bandwidth — autotuner-gated,
    tolerance-bounded vs the oracle, never used on f32 slabs)."""
    n, rho, k = Q_I.shape
    Bq = P_C.shape[0]
    acc = jnp.dtype(acc_dtype)
    block_n = blocks.clamp_tile(block_n, n)
    pad = blocks.pad_amount(n, block_n)
    Q_T, a_row, m_row = _item_rows(Q_I, a_I, valid, pad)
    args = (Q_T, a_row,
            *_context_args(jnp.broadcast_to(e[None], (Bq, rho)), P_C, a_C),
            m_row)

    if topk is None:
        return pl.pallas_call(
            functools.partial(_kernel_full, acc_dtype=acc),
            grid=blocks.grid_1d(n + pad, block_n),
            in_specs=_in_specs(rho, k, Bq, block_n),
            out_specs=blocks.col_tiles(Bq, block_n),
            out_shape=jax.ShapeDtypeStruct((Bq, n + pad), jnp.float32),
            interpret=interpret,
            name="dplr_corpus_score_full",
        )(*args)[:, :n]

    if not 0 < topk <= n:
        raise ValueError(f"topk={topk} out of range for n={n}")
    return _topk_call(
        args, seg_tiles=((0, (n + pad) // block_n),), Bq=Bq,
        topk=topk, block_n=block_n, index_offset=index_offset,
        index_stride=index_stride, acc_dtype=acc, interpret=interpret,
        name="dplr_corpus_score_topk")


# ---------------------------------------------------------------------------
# Multi-segment mode: S tenants' micro-batches in ONE launch
# ---------------------------------------------------------------------------

@functools.partial(jax.jit,
                   static_argnames=("topk", "block_n", "interpret",
                                    "index_stride", "acc_dtype"))
def dplr_corpus_score_multi(
    Q_parts: tuple,    # S x (n_s, rho, k) per-segment item projections
    a_parts: tuple,    # S x (n_s,)        per-segment item scalars
    valid_parts,       # S x (n_s,) liveness masks, or None = all live
    e: jax.Array,      # (S, rho)          per-segment eigen-weights
    P_C: jax.Array,    # (S, Bq, rho, k)   stacked context projections
    a_C: jax.Array,    # (S, Bq)           stacked per-query scalars
    *,
    topk: int,
    block_n: int = blocks.CORPUS_TILE_N,
    interpret: bool = False,
    index_offset: jax.Array | int = 0,
    index_stride: int = 1,
    acc_dtype: str = "float32",
):
    """Tenant-segmented fused top-K: scores S segments' micro-batches in
    ONE kernel launch and returns ``((S, Bq, topk) scores, (S, Bq, topk)
    int32 indices)`` — row ``[s, q]`` is bitwise the running top-K of
    segment ``s``'s corpus alone (foreign tiles contribute only NEG_INF,
    which the merge's lowest-position tie-break can never promote over a
    live score while ``topk <= n_live(s)``).

    Indices are segment-LOCAL slots relabeled by ``index_offset``/
    ``index_stride`` (same striping rule as the single-segment mode, so
    the sharded path reuses it with ``offset=shard, stride=D``).

    The per-segment corpus slabs concatenate on the item axis, each
    padded to a whole number of ``block_n`` tiles with phantom dead
    rows; each grid step finds its segment from ``program_id`` and the
    static per-segment tile boundaries, and windows the tile to that
    segment's stacked query rows.
    Retrace keying: the tuple length S is part of the pytree structure,
    so callers bucket S (the frontend pads to power-of-two segment
    counts) exactly like Bq and K."""
    S = len(Q_parts)
    if S == 0:
        raise ValueError("dplr_corpus_score_multi needs >= 1 segment")
    S_a = len(a_parts)                  # tuple arity: trace-static
    if not (S_a == S and P_C.shape[0] == S and a_C.shape[0] == S
            and e.shape[0] == S):
        raise ValueError(
            f"segment-count mismatch: {S} Q_parts vs {S_a} "
            f"a_parts, e {e.shape}, P_C {P_C.shape}, a_C {a_C.shape}")
    if valid_parts is None:
        valid_parts = (None,) * S
    rho, k = Q_parts[0].shape[1:]
    Bq = P_C.shape[1]
    SB = S * Bq
    n_min = min(int(q.shape[0]) for q in Q_parts)
    if not 0 < topk <= n_min:
        raise ValueError(f"topk={topk} out of range for smallest segment "
                         f"n={n_min}")
    block_n = blocks.clamp_tile(block_n, max(int(q.shape[0])
                                             for q in Q_parts))

    parts, seg_tiles = [], []
    for s in range(S):
        n_s = Q_parts[s].shape[0]
        pad = blocks.pad_amount(n_s, block_n)
        parts.append(_item_rows(Q_parts[s], a_parts[s], valid_parts[s], pad))
        first = seg_tiles[-1][1] if seg_tiles else 0
        seg_tiles.append((first, first + (n_s + pad) // block_n))
    # segments concatenate on the lane (item) axis
    Q_T, a_row, m_row = (jnp.concatenate(x, axis=-1) for x in zip(*parts))
    context = _context_args(jnp.repeat(e, Bq, axis=0),
                            P_C.reshape(SB, rho, k), a_C.reshape(SB))
    vals, idx = _topk_call(
        (Q_T, a_row, *context, m_row), seg_tiles=tuple(seg_tiles), Bq=Bq,
        topk=topk, block_n=block_n, index_offset=index_offset,
        index_stride=index_stride,
        acc_dtype=jnp.dtype(acc_dtype), interpret=interpret,
        name="dplr_corpus_score_multi_topk")
    return vals.reshape(S, Bq, topk), idx.reshape(S, Bq, topk)
