"""Public jit'd wrappers around the Pallas kernels.

Off the TPU the kernels execute in interpret mode (Pallas has no CPU
lowering); on TPU ``interpret=False`` compiles through Mosaic.  The
wrappers pick that automatically and expose the same signatures as the
pure-jnp references, so the serving stack can swap implementations with a
flag (cfg.use_pallas_kernels).

Corpus-scorer calls that leave ``block_n=None`` (the default) resolve
their tile geometry through ``blocks.corpus_tile`` — the registry
``kernels/autotune.py`` fills with parity-gated winners — so every call
site (single-device runtime, sharded bodies, fused multi-segment path)
inherits tuned tiles without threading a flag.  Resolution happens at
Python time in the wrapper, BEFORE the jitted kernel: same shapes +
same registry = same static args = zero retraces; tune before warmup.
"""
from __future__ import annotations

import jax

from repro.kernels import blocks
from repro.kernels import dplr_corpus_score as _corpus
from repro.kernels import dplr_score as _dplr
from repro.kernels import embedding_bag as _bag
from repro.kernels import flash_attention as _flash
from repro.kernels import fwfm_interaction as _fwfm
from repro.kernels import mind_corpus_score as _mind


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def dplr_score_items(V_I, U_I, e, d_I, P_C, s_C, *,
                     block_n: int = blocks.ITEM_TILE_N,
                     interpret: bool | None = None):
    interp = (not _on_tpu()) if interpret is None else interpret
    return _dplr.dplr_score_items(V_I, U_I, e, d_I, P_C, s_C,
                                  block_n=block_n, interpret=interp)


def _resolve_tile(n, rho, k, Bq, K, dtype, block_n, acc_dtype, rows=None):
    """Explicit ``block_n``/``acc_dtype`` win; ``None`` falls through to
    the autotuner registry (default-identical when nothing is tuned),
    capped by the scoped-VMEM rule for the ``rows`` query rows one launch
    scores (``blocks.corpus_vmem_tile``; default ``Bq``)."""
    tuned_bn, tuned_acc = blocks.corpus_tile(
        n, rho, k, Bq, K, str(dtype), jax.default_backend())
    if block_n is None:
        block_n = blocks.corpus_vmem_tile(tuned_bn, rows or Bq)
    return block_n, tuned_acc if acc_dtype is None else acc_dtype


def dplr_corpus_score(Q_I, a_I, e, P_C, a_C, valid=None, *, topk=None,
                      block_n: int | None = None,
                      interpret: bool | None = None,
                      index_offset=0, index_stride: int = 1,
                      acc_dtype: str | None = None):
    interp = (not _on_tpu()) if interpret is None else interpret
    n, rho, k = Q_I.shape
    block_n, acc_dtype = _resolve_tile(n, rho, k, P_C.shape[0], topk,
                                       Q_I.dtype, block_n, acc_dtype)
    return _corpus.dplr_corpus_score(Q_I, a_I, e, P_C, a_C, valid,
                                     topk=topk, block_n=block_n,
                                     interpret=interp,
                                     index_offset=index_offset,
                                     index_stride=index_stride,
                                     acc_dtype=acc_dtype)


def dplr_corpus_score_multi(Q_parts, a_parts, valid_parts, e, P_C, a_C, *,
                            topk: int, block_n: int | None = None,
                            interpret: bool | None = None,
                            index_offset=0, index_stride: int = 1,
                            acc_dtype: str | None = None):
    interp = (not _on_tpu()) if interpret is None else interpret
    if not Q_parts:
        raise ValueError("dplr_corpus_score_multi needs >= 1 segment")
    # the fused launch reuses the largest segment's tuned cell (its tiles
    # dominate the grid); per-segment retuning would fragment block_n
    n, rho, k = max((q.shape for q in Q_parts), key=lambda s: s[0])
    block_n, acc_dtype = _resolve_tile(n, rho, k, P_C.shape[1], topk,
                                       Q_parts[0].dtype, block_n, acc_dtype,
                                       rows=P_C.shape[0] * P_C.shape[1])
    return _corpus.dplr_corpus_score_multi(
        tuple(Q_parts), tuple(a_parts),
        valid_parts if valid_parts is None else tuple(valid_parts),
        e, P_C, a_C, topk=topk, block_n=block_n, interpret=interp,
        index_offset=index_offset, index_stride=index_stride,
        acc_dtype=acc_dtype)


def mind_corpus_score(E, U, live, valid=None, *, topk=None,
                      block_n: int | None = None,
                      interpret: bool | None = None):
    """MIND's max-over-interests scorer; ``block_n=None`` takes the
    largest tile the VMEM rule allows for this launch's query rows
    (``blocks.mind_vmem_tile``)."""
    interp = (not _on_tpu()) if interpret is None else interpret
    if block_n is None:
        rows = -(-U.shape[0] // 8) * 8
        block_n = blocks.mind_vmem_tile(blocks.MIND_TILE_N, rows)
    return _mind.mind_corpus_score(E, U, live, valid, topk=topk,
                                   block_n=block_n, interpret=interp)


def fwfm_pairwise(V, R, *, block_b: int = blocks.PAIRWISE_TILE_B,
                  interpret: bool | None = None):
    interp = (not _on_tpu()) if interpret is None else interpret
    return _fwfm.fwfm_pairwise(V, R, block_b=block_b, interpret=interp)


def embedding_bag(table, ids, weights, *, segment_ids, n_bags,
                  interpret: bool | None = None):
    interp = (not _on_tpu()) if interpret is None else interpret
    return _bag.embedding_bag(table, ids, weights,
                              segment_ids=tuple(int(s) for s in segment_ids),
                              n_bags=n_bags, interpret=interp)


def flash_attention(q, k, v, *, window=None, block_q=blocks.ATTN_TILE,
                    block_k=blocks.ATTN_TILE,
                    interpret: bool | None = None):
    interp = (not _on_tpu()) if interpret is None else interpret
    return _flash.flash_attention(q, k, v, window=window, block_q=block_q,
                                  block_k=block_k, interpret=interp)
