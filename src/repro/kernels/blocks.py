"""Shared Pallas tiling helpers: named tile sizes, padding, BlockSpecs.

Every kernel in this package expresses its grid and BlockSpec geometry
through these helpers instead of inline ``pl.BlockSpec``/magic-number
tile sizes — the invariant the kernel-contract linter rule
(``tools/analyze`` KRN-BLOCKSPEC / KRN-TILE) enforces.  Centralizing the
geometry buys three things: default tile sizes are NAMED (one place to
retune for a new TPU generation), index-map conventions are written once
(off-by-one in a hand-rolled ``lambda i: ...`` is the classic silent
Pallas bug), and the linter can verify "no bare tiling" purely
syntactically.

Conventions: all helpers target either a 1-D grid over tiles of axis 0
(``grid_1d`` + ``row_tiles``/``broadcast``/``col_tiles``), the
attention ``(B, KV, n_q, n_k)`` grid (``attn_tiles``), or the
scalar-prefetch gather grid (``prefetch_*``).  Tile-size defaults live
here as module constants.

Two tile-size services beyond the static defaults:

  * **clamp events** — ``clamp_tile`` no longer shrinks a tile silently:
    every clamp is recorded (trace-time Python side effect, like the
    runtime's ``trace_count``) and drainable via ``drain_clamp_events``,
    so the autotuner and benchmarks can report requested-vs-effective
    tile divergence instead of hiding it (the "no silent caps" rule).
  * **tuned-tile registry** — ``kernels/autotune.py`` registers the
    winning ``(block_n, acc_dtype)`` per parity-gated shape cell via
    ``register_tuned_tile``; ``corpus_tile`` is the lookup every call
    site that passes ``block_n=None`` resolves through (exact cell
    first, then the newest winner for the same ``(n, rho, k, dtype,
    backend)``, then ``CORPUS_TILE_N``).  Lookups happen at TRACE time
    inside the jitted callers, so tuning must run before warmup to take
    effect — a registry change never retraces an already-warm shape.
"""
from __future__ import annotations

import functools

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Named default tile sizes (retune here, not at call sites).  Values are
# VMEM-budget choices for the f32 shapes documented in each kernel.
CORPUS_TILE_N = 2048    # dplr_corpus_score: item (lane) tile of (rho, k, n)
ITEM_TILE_N = 1024      # dplr_score_items: item-axis tile of (n, mI, k)
PAIRWISE_TILE_B = 512   # fwfm_pairwise: example-axis tile of (B, m, k)
ATTN_TILE = 128         # flash_attention: q/k row tile (MXU lane width)

# Scoped-VMEM rule of the corpus scorer.  Items sit on lanes: per rank its
# (rows, k, block_n) f32 intermediate is dense, 4k = 64 bytes per (query
# row, item) pair at k = 16, a few such arrays live at once, and the
# merge's (rows, block_n) arrays pad rows to 8 sublanes.  Compiled for
# the v5e (16 MiB default scoped VMEM; top-K, K 16, rho 3, k 16, 2^20
# slots), the largest power-of-two tile is 32768 at 1 row, 16384 at 2 and
# 4, 8192 at 8 and 16, 4096 at 32, 2048 at 64, 1024 at 128: rows x
# block_n = 32768 compiles at every rows in 1..128, and 65536 runs out of
# VMEM at 1 and 2 rows.
CORPUS_VMEM_PAIRS = 32768

# Bounded log of tile clamps (requested > axis length).  Appended at
# trace time by clamp_tile; drained by the autotuner / benchmarks.
_CLAMP_EVENTS: list[dict] = []
_CLAMP_EVENTS_MAX = 256


def clamp_tile(tile: int, n: int) -> int:
    """Shrink a default tile to the axis length (tiny inputs trace a
    single-step grid instead of over-padding).  Never silent: each clamp
    is recorded for ``drain_clamp_events`` readers."""
    clamped = min(tile, n)
    if clamped != tile and len(_CLAMP_EVENTS) < _CLAMP_EVENTS_MAX:
        _CLAMP_EVENTS.append(
            {"requested": int(tile), "effective": int(clamped),
             "n": int(n)})
    return clamped


def drain_clamp_events() -> list[dict]:
    """Return and clear the recorded clamp events (bounded at
    ``_CLAMP_EVENTS_MAX``): ``{"requested", "effective", "n"}`` dicts in
    occurrence order."""
    out = list(_CLAMP_EVENTS)
    _CLAMP_EVENTS.clear()
    return out


# -- tuned-tile registry (written by kernels/autotune.py) -------------------

# exact cell (n, rho, k, Bq, K, dtype, backend) -> (block_n, acc_dtype)
_TUNED_TILES: dict[tuple, tuple[int, str]] = {}
# newest winner per shape family (n, rho, k, dtype, backend), used when a
# call's (Bq, K) cell was never tuned directly
_TUNED_FAMILY: dict[tuple, tuple[int, str]] = {}


def corpus_vmem_tile(block_n: int, rows: int) -> int:
    """The largest power-of-two corpus tile <= ``block_n`` whose
    ``rows x tile`` pairs fit ``CORPUS_VMEM_PAIRS`` (never below 128, the
    lane count: the tile is the lane axis of its blocks).  ``rows`` is
    the query rows one launch scores: Bq, or S * Bq for the multi-segment
    kernel."""
    cap = max(128, 1 << (max(CORPUS_VMEM_PAIRS // max(rows, 1), 1)
                         .bit_length() - 1))
    return min(block_n, cap)


def tile_cell(n: int, rho: int, k: int, Bq: int, K: int | None,
              dtype: str, backend: str) -> tuple:
    """The registry key of one autotuned shape cell."""
    return (int(n), int(rho), int(k), int(Bq),
            None if K is None else int(K), str(dtype), str(backend))


def register_tuned_tile(cell: tuple, block_n: int,
                        acc_dtype: str = "float32") -> None:
    """Record a parity-gated autotune winner for ``cell`` (a
    ``tile_cell`` tuple).  Only ``kernels/autotune.py`` should call this,
    and only AFTER the candidate passed its oracle parity gate — the
    KRN-TUNE analyzer rule enforces that pairing statically."""
    cell = tuple(cell)
    winner = (int(block_n), str(acc_dtype))
    _TUNED_TILES[cell] = winner
    _TUNED_FAMILY[cell[:3] + cell[5:]] = winner


def corpus_tile(n: int, rho: int, k: int, Bq: int, K: int | None,
                dtype: str, backend: str) -> tuple[int, str]:
    """Resolve the ``(block_n, acc_dtype)`` a ``block_n=None`` corpus-
    scorer call should use: the exact tuned cell if registered, else the
    newest winner of the same ``(n, rho, k, dtype, backend)`` family,
    else ``(CORPUS_TILE_N, 'float32')`` — so untuned processes behave
    exactly as before."""
    cell = tile_cell(n, rho, k, Bq, K, dtype, backend)
    hit = _TUNED_TILES.get(cell)
    if hit is None:
        hit = _TUNED_FAMILY.get(cell[:3] + cell[5:])
    return hit if hit is not None else (CORPUS_TILE_N, "float32")


def clear_tuned_tiles() -> None:
    """Drop every registered tuned tile (tests / benchmark hygiene)."""
    _TUNED_TILES.clear()
    _TUNED_FAMILY.clear()


def pad_amount(n: int, tile: int) -> int:
    """Rows of phantom padding that make ``n`` a whole number of tiles."""
    return (-n) % tile


def grid_1d(n_padded: int, tile: int) -> tuple[int]:
    """The 1-D grid over axis-0 tiles; ``n_padded`` must already be a
    tile multiple (``pad_amount`` says by how much to pad)."""
    if n_padded % tile:
        raise ValueError(f"n_padded={n_padded} not a multiple of "
                         f"tile={tile}")
    return (n_padded // tile,)


def row_tiles(tile: int, *rest: int) -> pl.BlockSpec:
    """``(tile, *rest)`` block, axis 0 tiled by the 1-D grid step, every
    trailing axis whole: grid step ``i`` sees rows ``[i*tile, (i+1)*tile)``."""
    trailing = (0,) * len(rest)
    return pl.BlockSpec((tile, *rest), lambda i: (i, *trailing))


def col_tiles(*shape: int) -> pl.BlockSpec:
    """``shape`` block, the LAST axis tiled by the 1-D grid step, every
    leading axis whole: grid step ``i`` sees columns ``[i*tile,
    (i+1)*tile)`` — a lane-dense operand or output whose tiled axis sits
    on the vreg's lanes."""
    leading = (0,) * (len(shape) - 1)
    return pl.BlockSpec(tuple(shape), lambda i: (*leading, i))


def broadcast(*shape: int) -> pl.BlockSpec:
    """A whole-array block with a constant index map: the operand stays
    VMEM-resident across every 1-D grid step (replicated operands, and
    running top-K output blocks carried across steps)."""
    zeros = (0,) * len(shape)
    return pl.BlockSpec(tuple(shape), lambda i: zeros)


def smem() -> pl.BlockSpec:
    """A whole small operand placed in SMEM (scalar memory) — runtime
    scalars a kernel reads as ``ref[j]``, such as a traced index offset.
    A (1, 1) VMEM block would be padded to a full (8, 128) tile."""
    return pl.BlockSpec(memory_space=pltpu.SMEM)


def attn_tiles(block_rows: int, head_dim: int, *, kv: bool) -> pl.BlockSpec:
    """``(1, 1, block_rows, head_dim)`` block of a ``(B, KV, S, hd)``
    operand on the attention grid ``(B, KV, n_q, n_k)``: one
    (batch, kv-head) pair per step, rows tiled by the kv grid axis when
    ``kv`` else by the q grid axis."""
    if kv:
        return pl.BlockSpec((1, 1, block_rows, head_dim),
                            lambda b, h, qi, ki: (b, h, ki, 0))
    return pl.BlockSpec((1, 1, block_rows, head_dim),
                        lambda b, h, qi, ki: (b, h, qi, 0))


def prefetch_batch(*rest: int) -> pl.BlockSpec:
    """``(1, *rest)`` block of a batch-major operand on the scalar-
    prefetch gather grid ``(B,)``: step ``i`` sees example ``i`` whole
    (the prefetch ref is part of the index-map signature but unused)."""
    trailing = (0,) * len(rest)
    return pl.BlockSpec((1, *rest), lambda i, ids_ref: (i, *trailing))


def prefetch_rows(n_slots: int, row_width: int) -> list[pl.BlockSpec]:
    """One ``(1, row_width)`` table-row view per slot on the scalar-
    prefetch grid: view ``s`` of grid step ``i`` DMAs table row
    ``ids[i, s]`` into VMEM — the data-dependent gather, driven by the
    prefetched ids."""
    return [
        pl.BlockSpec((1, row_width), functools.partial(
            lambda i, ids_ref, s=0: (ids_ref[i, s], 0), s=s))
        for s in range(n_slots)
    ]
