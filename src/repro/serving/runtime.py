"""ScorerRuntime: the corpus-independent half of the serving engine.

``ScorerRuntime`` owns everything about scoring that does NOT depend on
which corpus is being scored: the jitted/Pallas dispatch functions, the
mesh / ``shard_map`` wiring, kernel selection, and the (Bq, K,
capacity-bucket) warmup grid.  What is model-specific — the slab rows,
the query state, the score function and its kernel — comes from the
scorer the runtime picks from its config (``corpus.scorer_for``:
DPLR-FwFM, or MIND for a ``MINDConfig``; the sharded slab serves
DPLR-FwFM only).  It is keyed purely by shape+dtype — its
jit caches are a function of ``(cfg, mesh, kernel choice)`` plus the
SHAPES of the arrays that flow through them — so **T tenants share one
trace cache**: a second ``CorpusState`` whose slab capacity (and context
layout and dtype) matches an already-warm signature comes online with
ZERO retraces, and churn/refresh on any tenant never invalidates another
tenant's traces.

Layering (see docs/multitenant.md):

    ScorerRuntime   shared   jit dispatch, trace cache, mesh wiring
    CorpusState     per-tenant   slab + mask + free-lists + params snapshot
    QueryFrontend   shared   tenant-routed queues, fairness, admission

Shapes and dtypes (one runtime, any number of tenants):

    score(params, cache, ctx_ids, ctx_w)        -> (Bq, capacity) cfg.dtype
        ctx_ids (Bq, query_width) int32 query rows, ctx_w matching float
    topk(params, cache, ctx_ids, ctx_w, K=K)    -> ((Bq, K) cfg.dtype,
                                                    (Bq, K) int32)  K static
    multi_topk(params_parts, cache_parts, ctx_ids, ctx_w, K=K)
        S-tuples + (S, Bq, ...) stacked contexts -> ((S, Bq, K) x 2)
        — the fused multi-tenant dispatch: S tenants' micro-batches in
        ONE device program, per-segment results bit-exact vs S separate
        ``topk`` calls.  Keyed on the segment-count bucket S (a tuple
        length is part of the jit pytree structure), so the frontend's
        packing adds zero retraces beyond its warmed S buckets.
    build(params, slab_ids, slab_w, valid)      -> the model's slab cache
                                                   (DPLR: ItemCorpusCache)
    write_rows(params, cache, slots, ids, w)    -> slab cache (host API)
    drop_rows(cache, slots)                     -> slab cache (host API)

All device entry points are NON-blocking under JAX async dispatch (they
return device arrays; reading a result blocks).  ``write_rows`` /
``drop_rows`` are host-side conveniences that bucket-pad the Δn delta to
a power of two (so churn traces O(log capacity) times total, never once
per Δn) and, when sharded, group the delta rows per owning shard before
the ``shard_map`` scatter so each device computes and writes ONLY its
own rows (see ``repro.serving.sharded.group_deltas``).

``trace_count`` increments only when a scorer entry point actually
retraces — it is the shared, cross-tenant counter every zero-retrace
invariant in the tests, demos, and benchmarks asserts on.
"""
from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

from repro.serving.corpus import DPLRScorer, next_pow2, scorer_for


class ScorerRuntime:
    """Corpus-independent jitted scoring dispatch, shared across tenants.

    Parameters
    ----------
    cfg : FwFMConfig | MINDConfig
        Model config; picks the scorer (``corpus.scorer_for``: an FwFM
        config needs ``interaction='dplr'``).  ``cfg.dtype`` is the
        serving dtype: query weights default to it and scores carry it.
    mesh : jax.sharding.Mesh | None
        When set, caches are stored in the physical ``(capacity/D, D,
        ...)`` striped layout of ``repro.serving.sharded`` and every
        dispatch runs through ``shard_map``; ``None`` is the single-device
        D=1 layout.
    use_pallas_kernel : bool
        Score through the model's Pallas kernel
        (``kernels.ops.dplr_corpus_score`` or ``mind_corpus_score``: one
        HBM pass, fused running top-K) instead of the fused-jnp form.
    block_n : int | None
        Pallas kernel corpus-block size.  ``None`` (default) resolves
        through the autotuner registry (``kernels.blocks.corpus_tile``)
        per shape cell, falling back to ``blocks.CORPUS_TILE_N`` when
        nothing is tuned — numerically identical to the fixed default.
    """

    def __init__(self, cfg, *, mesh=None, use_pallas_kernel: bool = False,
                 block_n: int | None = None):
        self.model = scorer_for(cfg)
        if mesh is not None and not isinstance(self.model, DPLRScorer):
            raise ValueError("the sharded slab serves DPLR-FwFM only")
        self.cfg = cfg
        self.wdtype = cfg.dtype   # weights follow the serving dtype — a
        # stray f32 default here silently promotes the whole bf16 path.
        self.mesh = mesh
        self.use_pallas_kernel = use_pallas_kernel
        self.block_n = block_n
        self.trace_count = 0      # incremented only when a scorer retraces
        # keys of kernel dispatches that have run once: their executables
        # exist, so a later error there is a launch fault, not a compile
        # error (engine._kernel_dispatch)
        self.kernel_warm: set = set()
        if mesh is None:
            self._D = 1
        else:
            from repro.serving import sharded
            self._D = sharded.shard_count(mesh)
            if self._D & (self._D - 1):
                # capacity must be a power of two AND divisible by D, so a
                # non-power-of-two shard count admits NO valid capacity —
                # fail here with the real reason, not downstream
                raise ValueError(
                    f"corpus shard count must be a power of two, got a "
                    f"{self._D}-wide model axis")

        self.rows = jax.jit(self._rows_impl)
        if mesh is None:
            self.build = jax.jit(self._build_impl)
            self.score = jax.jit(self._score_impl)
            self.topk = jax.jit(self._topk_impl, static_argnames=("K",))
            self.kernel_score = jax.jit(self._kernel_score_impl,
                                        static_argnames=("K",))
            self.multi_topk = jax.jit(self._multi_topk_impl,
                                      static_argnames=("K",))
            self.kernel_multi_topk = jax.jit(self._kernel_multi_topk_impl,
                                             static_argnames=("K",))
            self._write = jax.jit(self._write_impl)
            self._drop = jax.jit(self._drop_impl)
        else:
            self._init_sharded(mesh)

    # -- identity ----------------------------------------------------------

    @property
    def n_shards(self) -> int:
        """Corpus shard count D (1 when unsharded)."""
        return self._D

    @property
    def query_width(self) -> int:
        """Slots of one query row."""
        return self.model.query_width

    @property
    def signature(self) -> tuple:
        """The shape+dtype key this runtime's trace cache is a function
        of (beyond the per-call array shapes): two ``CorpusState``s built
        on runtimes with equal signatures AND equal capacity reach the
        exact same traces."""
        return (*self.model.signature(), str(jnp.dtype(self.wdtype)),
                self._D, self.use_pallas_kernel, self.block_n)

    # -- jitted bodies (single-device) --------------------------------------

    def _build_impl(self, params, slab_ids, slab_w, valid):
        return self.model.build(params, slab_ids, slab_w, valid)

    def _rows_impl(self, params, ids, w):
        return self.model.rows(params, ids, w)

    def _write_impl(self, cache, rows, idx):
        """Scatter Δn precomputed rows (one per slab leaf before ``valid``)
        into the slab and mark them live.  ``idx`` is bucket-padded with
        ``capacity`` (out of range => dropped), so one trace serves every
        Δn in the bucket."""
        return cache._replace(
            **{f: getattr(cache, f).at[idx].set(r, mode="drop")
               for f, r in zip(cache._fields[:-1], rows)},
            valid=cache.valid.at[idx].set(True, mode="drop"))

    def _drop_impl(self, cache, idx):
        return cache._replace(valid=cache.valid.at[idx].set(False,
                                                            mode="drop"))

    def _context_impl(self, params, ctx_ids, ctx_w):
        """The model's query state for a batch of query rows (DPLR: the
        context cache P_C, s_C, lin_C)."""
        return self.model.query(params, ctx_ids, ctx_w)

    def _score_impl(self, params, cache, ctx_ids, ctx_w):
        self.trace_count += 1     # python side effect: runs at trace time only
        return self.model.scores(params, cache,
                                 self._context_impl(params, ctx_ids, ctx_w))

    def _topk_impl(self, params, cache, ctx_ids, ctx_w, *, K):
        scores = self._score_impl(params, cache, ctx_ids, ctx_w)
        return jax.lax.top_k(scores, K)

    def _multi_topk_impl(self, params_parts, cache_parts, ctx_ids, ctx_w,
                         *, K):
        """Fused multi-tenant scorer (jnp form): the segment loop runs at
        TRACE time, so the S segments' context caches, slab scores, and
        top-Ks fuse into one device program — one dispatch where the
        per-tenant path pays S.  Per-segment math is ``_topk_impl``
        verbatim, so results are bit-exact vs S separate calls."""
        self.trace_count += 1     # python side effect: runs at trace time only
        vals, idx = [], []
        for s in range(len(params_parts)):
            q = self._context_impl(params_parts[s], ctx_ids[s], ctx_w[s])
            scores = self.model.scores(params_parts[s], cache_parts[s], q)
            v, i = jax.lax.top_k(scores, K)
            vals.append(v)
            idx.append(i)
        return jnp.stack(vals), jnp.stack(idx)

    def _kernel_multi_topk_impl(self, params_parts, cache_parts, ctx_ids,
                                ctx_w, *, K):
        """Pallas fused multi-tenant scorer: every segment's query state,
        then the model's multi-segment launch (DPLR: ONE tenant-segmented
        kernel, ``kernels.dplr_corpus_score_multi``)."""
        self.trace_count += 1     # python side effect: runs at trace time only
        qs = [self._context_impl(params, ctx_ids[s], ctx_w[s])
              for s, params in enumerate(params_parts)]
        return self.model.kernel_multi(params_parts, cache_parts, qs, K,
                                       self.block_n)

    def _kernel_score_impl(self, params, cache, ctx_ids, ctx_w, *, K=None):
        """Pallas-backed scorer entry point — jitted at THIS level so
        ``trace_count`` tracks kernel-path retraces exactly like the jnp
        path (a retrace here <=> a shape/static change for the kernel),
        and so the query state (MIND: the user tower) and the kernel run
        in one program per launch."""
        self.trace_count += 1     # python side effect: runs at trace time only
        return self.model.kernel(params, cache,
                                 self._context_impl(params, ctx_ids, ctx_w),
                                 K, self.block_n)

    # -- sharded wiring -----------------------------------------------------

    def _init_sharded(self, mesh):
        """Swap the device-side ops for their shard_map versions.  Call
        signatures and semantics are identical — churn idx stay GLOBAL
        slots, score/topk outputs stay in global slot order — only the
        cache layout changes to the physical (local, D, ...) view of
        ``repro.serving.sharded``."""
        from repro.serving import sharded

        self.build = jax.jit(sharded.make_build(self.cfg, mesh))
        # churn writes: the delta is grouped per owning shard HOST-side
        # (sharded.group_deltas), so each device computes corpus rows for
        # — and scatters — only the slots it owns, never the full delta
        self._write = jax.jit(sharded.make_write_grouped(self.cfg, mesh))
        self._drop = jax.jit(sharded.make_drop(mesh))
        score = sharded.make_score(self.cfg, mesh, self._context_impl)
        topk = sharded.make_topk(self.cfg, mesh, self._context_impl)
        mtopk = sharded.make_multi_topk(self.cfg, mesh, self._context_impl)
        kscore = sharded.make_score(self.cfg, mesh, self._context_impl,
                                    use_kernel=True, block_n=self.block_n)
        ktopk = sharded.make_topk(self.cfg, mesh, self._context_impl,
                                  use_kernel=True, block_n=self.block_n)
        kmtopk = sharded.make_multi_topk(self.cfg, mesh,
                                         self._context_impl,
                                         use_kernel=True,
                                         block_n=self.block_n)

        def _score_impl(params, cache, ctx_ids, ctx_w):
            self.trace_count += 1    # python side effect: trace time only
            return score(params, cache, ctx_ids, ctx_w)

        def _topk_impl(params, cache, ctx_ids, ctx_w, *, K):
            self.trace_count += 1    # python side effect: trace time only
            return topk(params, cache, ctx_ids, ctx_w, K=K)

        def _kernel_impl(params, cache, ctx_ids, ctx_w, *, K=None):
            self.trace_count += 1
            if K is None:
                return kscore(params, cache, ctx_ids, ctx_w)
            return ktopk(params, cache, ctx_ids, ctx_w, K=K)

        def _multi_impl(params_parts, cache_parts, ctx_ids, ctx_w, *, K):
            self.trace_count += 1    # python side effect: trace time only
            return mtopk(params_parts, cache_parts, ctx_ids, ctx_w, K=K)

        def _kernel_multi_impl(params_parts, cache_parts, ctx_ids, ctx_w,
                               *, K):
            self.trace_count += 1    # python side effect: trace time only
            return kmtopk(params_parts, cache_parts, ctx_ids, ctx_w, K=K)

        self.score = jax.jit(_score_impl)
        self.topk = jax.jit(_topk_impl, static_argnames=("K",))
        self.kernel_score = jax.jit(_kernel_impl, static_argnames=("K",))
        self.multi_topk = jax.jit(_multi_impl, static_argnames=("K",))
        self.kernel_multi_topk = jax.jit(_kernel_multi_impl,
                                         static_argnames=("K",))

    # -- host-side churn helpers (bucketing + shard grouping) ---------------

    def _pad_slots(self, slots: np.ndarray, filler: int) -> np.ndarray:
        """Pad a Δn slot vector to the next power-of-two bucket so the
        jitted scatter traces O(log capacity) times total, not once per
        Δn.  Filler entries get an out-of-range index => dropped."""
        pad = next_pow2(max(len(slots), 1)) - len(slots)
        if pad:
            slots = np.concatenate([slots, np.full(pad, filler, np.int32)])
        return slots

    def write_rows(self, params, cache, slots, ids, w):
        """Scatter Δn (slot -> item row) writes into ``cache`` and mark
        them live: ONE row-compute + scatter dispatch of O(Δn rho k)
        work, bucket-padded (power-of-two Δn).  Sharded: the delta is
        grouped per owning shard host-side first, so each device
        processes only its own rows.  Non-blocking (async dispatch)."""
        if self.mesh is None:
            cap = cache.valid.shape[0]
            dn = len(slots)
            slots_p = self._pad_slots(np.asarray(slots, np.int32), cap)
            pad = len(slots_p) - dn
            if pad:
                ids = np.concatenate(
                    [ids, np.zeros((pad, ids.shape[1]), np.int32)])
                w = np.concatenate([w, np.ones((pad, w.shape[1]),
                                               np.float32)])
            rows = self.rows(params, jnp.asarray(ids),
                             jnp.asarray(w, self.wdtype))
            return self._write(cache, rows, jnp.asarray(slots_p))
        from repro.serving import sharded
        li, ids_g, w_g = sharded.group_deltas(
            np.asarray(slots, np.int32), np.asarray(ids, np.int32),
            np.asarray(w, np.float32), self._D, cache.valid.shape[0])
        return self._write(params, cache, jnp.asarray(ids_g),
                           jnp.asarray(w_g, self.wdtype), jnp.asarray(li))

    def drop_rows(self, cache, slots):
        """Invalidate slots (global ids, bucket-padded).  One scatter
        dispatch; mask-only, so no row compute.  Non-blocking."""
        cap = cache.valid.shape[0] * (1 if self.mesh is None else self._D)
        slots_p = self._pad_slots(np.asarray(slots, np.int32), cap)
        return self._drop(cache, jnp.asarray(slots_p))
