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
kernel tiles the ITEM axis: one grid step holds a ``(block_n, rho, k)``
slab of Q_I in VMEM, so HBM traffic is ONE pass over ``(n, rho, k)`` —
strictly less than the ``(n, m_I, k)`` pass of ``dplr_score.py`` (the
Algorithm-1 kernel that still re-projects item embeddings per query), by
the factor m_I / rho (~12x for the paper's deployed geometry).

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
comparisons and tie-breaking stay exact.  The default ``'float32'`` is
byte-identical to the historical kernel.  The autotuner sweeps this
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


def _rank_reduce(pp, e, acc_dtype):
    """``sum_{r,k} e[..., r] * pp[..., r, k]`` over the trailing (rho, k)
    axes, in the requested accumulation dtype, as VPU multiplies and
    sums: an in-kernel matmul would run at the TPU's default f32 matmul
    precision (a single bf16 pass), far outside the f32 contract.
    ``e`` broadcasts as ``(rows, 1, rho)``."""
    sq = jnp.sum(pp.astype(acc_dtype), axis=-1)            # (q, n, rho)
    return jnp.sum(sq * e.astype(acc_dtype), axis=-1).astype(jnp.float32)


def _tile_scores(q, a_i, e, pc, a_c, m, acc_dtype=jnp.float32):
    """(Bq, block_n) scores for one item tile.  All operands f32 in VMEM;
    ``m`` is the tile's (block_n,) {0,1} validity mask — dead slots are
    pinned to exactly NEG_INF so they can never win a top-K slot."""
    # p: (Bq, bn, rho, k) — direct fused form, same reduction order as the
    # jnp reference so corpus-cached parity stays at float32 epsilon.
    p = pc[:, None, :, :] + q[None, :, :, :]
    term_e = _rank_reduce(p * p, e[None, None, :], acc_dtype)
    s = a_c[:, None] + a_i[None, :] + 0.5 * term_e
    return jnp.where((m != 0)[None, :], s, NEG_INF)


def _kernel_full(q_ref, a_ref, e_ref, pc_ref, ac_ref, m_ref, out_ref, *,
                 acc_dtype):
    out_ref[...] = _tile_scores(
        q_ref[...], a_ref[:, 0], e_ref[:, 0], pc_ref[...], ac_ref[:, 0],
        m_ref[:, 0], acc_dtype)


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
                 val_ref, idx_ref, *, block_n: int, index_stride: int,
                 acc_dtype):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        val_ref[...] = jnp.full_like(val_ref, NEG_INF)
        idx_ref[...] = jnp.zeros_like(idx_ref)

    scores = _tile_scores(
        q_ref[...], a_ref[:, 0], e_ref[:, 0], pc_ref[...], ac_ref[:, 0],
        m_ref[:, 0], acc_dtype)
    # row r of this tile is local slot i*block_n + r; the emitted index is
    # its caller-defined global label off + stride * local.
    labels = off_ref[0] + index_stride * (
        i * block_n + jax.lax.broadcasted_iota(jnp.int32, scores.shape, 1))
    _merge_topk(val_ref, idx_ref, scores, labels)


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
    (``'float32'`` default = historical bit-exact path; ``'bfloat16'``
    trades the reduction's precision for bandwidth — autotuner-gated,
    tolerance-bounded vs the oracle, never used on f32 slabs)."""
    n, rho, k = Q_I.shape
    Bq = P_C.shape[0]
    acc = jnp.dtype(acc_dtype)
    Q_I = Q_I.astype(jnp.float32)
    a_I = a_I.astype(jnp.float32)
    e = e.astype(jnp.float32)
    P_C = P_C.astype(jnp.float32)
    a_C = a_C.astype(jnp.float32)
    mask = (jnp.ones((n,), jnp.int32) if valid is None
            else jnp.asarray(valid).astype(jnp.int32))

    block_n = blocks.clamp_tile(block_n, n)
    pad = blocks.pad_amount(n, block_n)
    if pad:
        Q_I = jnp.pad(Q_I, ((0, pad), (0, 0), (0, 0)))
        a_I = jnp.pad(a_I, (0, pad))
        mask = jnp.pad(mask, (0, pad))      # phantom rows are dead slots
    n_pad = n + pad
    grid = blocks.grid_1d(n_pad, block_n)

    in_specs = [
        blocks.row_tiles(block_n, rho, k),
        blocks.row_tiles(block_n, 1),
        blocks.broadcast(rho, 1),
        blocks.broadcast(Bq, rho, k),
        blocks.broadcast(Bq, 1),
        blocks.row_tiles(block_n, 1),
    ]
    args = (Q_I, a_I[:, None], e[:, None], P_C, a_C[:, None], mask[:, None])

    if topk is None:
        return pl.pallas_call(
            functools.partial(_kernel_full, acc_dtype=acc),
            grid=grid,
            in_specs=in_specs,
            out_specs=blocks.col_tiles(Bq, block_n),
            out_shape=jax.ShapeDtypeStruct((Bq, n_pad), jnp.float32),
            interpret=interpret,
        )(*args)[:, :n]

    if not 0 < topk <= n:
        raise ValueError(f"topk={topk} out of range for n={n}")
    off = jnp.asarray(index_offset, jnp.int32).reshape(1)
    in_specs = in_specs + [blocks.smem()]
    args = args + (off,)
    kernel = functools.partial(_kernel_topk, block_n=block_n,
                               index_stride=index_stride, acc_dtype=acc)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=[
            # constant index map => the running (values, indices) pair
            # stays VMEM-resident across every item tile
            blocks.broadcast(Bq, topk),
            blocks.broadcast(Bq, topk),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Bq, topk), jnp.float32),
            jax.ShapeDtypeStruct((Bq, topk), jnp.int32),
        ],
        interpret=interpret,
    )(*args)


# ---------------------------------------------------------------------------
# Multi-segment mode: S tenants' micro-batches in ONE launch
# ---------------------------------------------------------------------------

def _tile_scores_multi(q, a_i, e_q, pc, a_c, m, acc_dtype=jnp.float32):
    """(SB, block_n) scores of one item tile against EVERY stacked query
    row — the per-query ``e_q`` carries each row's own segment's eigen-
    weights, so foreign rows compute garbage that the caller masks to
    NEG_INF before the merge (they can never win a slot)."""
    p = pc[:, None, :, :] + q[None, :, :, :]
    term_e = _rank_reduce(p * p, e_q[:, None, :], acc_dtype)
    s = a_c[:, None] + a_i[None, :] + 0.5 * term_e
    return jnp.where((m != 0)[None, :], s, NEG_INF)


def _kernel_multi_topk(q_ref, a_ref, m_ref, eq_ref, pc_ref, ac_ref,
                       off_ref, val_ref, idx_ref, *, seg_tiles: tuple,
                       Bq: int, block_n: int, index_stride: int,
                       acc_dtype):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        val_ref[...] = jnp.full_like(val_ref, NEG_INF)
        idx_ref[...] = jnp.zeros_like(idx_ref)

    scores = _tile_scores_multi(
        q_ref[...], a_ref[:, 0], eq_ref[...], pc_ref[...], ac_ref[:, 0],
        m_ref[:, 0], acc_dtype)
    # which segment this tile belongs to, from the static tile boundaries:
    # its stacked query rows [q_off, q_off + Bq) and its first segment-
    # LOCAL item row
    q_off, row_base = 0, 0
    for s, (first, stop) in enumerate(seg_tiles):
        in_seg = (i >= first) & (i < stop)
        q_off = jnp.where(in_seg, s * Bq, q_off)
        row_base = jnp.where(in_seg, (i - first) * block_n, row_base)
    qidx = jax.lax.broadcasted_iota(jnp.int32, scores.shape, 0)
    own = (qidx >= q_off) & (qidx < q_off + Bq)
    # a foreign row sees this tile as all-NEG_INF, so its running top-K
    # is untouched by neighbor segments' item tiles (segment isolation)
    scores = jnp.where(own, scores, NEG_INF)
    labels = off_ref[0] + index_stride * (
        row_base + jax.lax.broadcasted_iota(jnp.int32, scores.shape, 1))
    _merge_topk(val_ref, idx_ref, scores, labels)


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
    acc = jnp.dtype(acc_dtype)
    n_min = min(int(q.shape[0]) for q in Q_parts)
    if not 0 < topk <= n_min:
        raise ValueError(f"topk={topk} out of range for smallest segment "
                         f"n={n_min}")
    block_n = blocks.clamp_tile(block_n, max(int(q.shape[0])
                                             for q in Q_parts))

    q_cat, a_cat, m_cat, seg_tiles = [], [], [], []
    for s in range(S):
        q_s = Q_parts[s].astype(jnp.float32)
        a_s = a_parts[s].astype(jnp.float32)
        n_s = q_s.shape[0]
        m_s = (jnp.ones((n_s,), jnp.int32) if valid_parts[s] is None
               else jnp.asarray(valid_parts[s]).astype(jnp.int32))
        pad = blocks.pad_amount(n_s, block_n)
        if pad:
            q_s = jnp.pad(q_s, ((0, pad), (0, 0), (0, 0)))
            a_s = jnp.pad(a_s, (0, pad))
            m_s = jnp.pad(m_s, (0, pad))    # phantom rows are dead slots
        q_cat.append(q_s)
        a_cat.append(a_s)
        m_cat.append(m_s)
        first = seg_tiles[-1][1] if seg_tiles else 0
        seg_tiles.append((first, first + (n_s + pad) // block_n))
    Q_cat = jnp.concatenate(q_cat)
    a_cat = jnp.concatenate(a_cat)
    m_cat = jnp.concatenate(m_cat)
    grid = blocks.grid_1d(Q_cat.shape[0], block_n)

    e_q = jnp.repeat(e.astype(jnp.float32), Bq, axis=0)        # (SB, rho)
    pc = P_C.astype(jnp.float32).reshape(SB, rho, k)
    ac = a_C.astype(jnp.float32).reshape(SB)
    off = jnp.asarray(index_offset, jnp.int32).reshape(1)

    in_specs = [
        blocks.row_tiles(block_n, rho, k),
        blocks.row_tiles(block_n, 1),
        blocks.row_tiles(block_n, 1),
        blocks.broadcast(SB, rho),
        blocks.broadcast(SB, rho, k),
        blocks.broadcast(SB, 1),
        blocks.smem(),
    ]
    args = (Q_cat, a_cat[:, None], m_cat[:, None], e_q, pc, ac[:, None],
            off)
    kernel = functools.partial(_kernel_multi_topk, seg_tiles=tuple(seg_tiles),
                               Bq=Bq, block_n=block_n,
                               index_stride=index_stride, acc_dtype=acc)
    vals, idx = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=[
            blocks.broadcast(SB, topk),
            blocks.broadcast(SB, topk),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((SB, topk), jnp.float32),
            jax.ShapeDtypeStruct((SB, topk), jnp.int32),
        ],
        interpret=interpret,
    )(*args)
    return vals.reshape(S, Bq, topk), idx.reshape(S, Bq, topk)
