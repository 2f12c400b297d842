"""CorpusState: one tenant's mutable corpus behind a shared ScorerRuntime.

The serving stack is three layers (full design: docs/multitenant.md):

    ScorerRuntime  (repro.serving.runtime)  — SHARED: jitted/Pallas
        dispatch, mesh wiring, the trace cache.  Corpus-independent,
        keyed by shape+dtype: T tenants share ONE runtime and therefore
        one set of traces.
    CorpusState    (this module)            — PER TENANT: the capacity-
        padded slab, validity mask, free-lists, params snapshot,
        checkpoint signature, and the tenant's ``on_mutate`` writer
        barrier.  Pure host-side bookkeeping plus the device arrays it
        mirrors; every compute dispatch goes through the runtime.
    QueryFrontend  (repro.serving.frontend) — SHARED: tenant-routed
        request queues, cross-tenant fairness, admission control.

``CorpusRankingEngine`` is an alias of ``CorpusState``: the historical
single-tenant engine is exactly one CorpusState over a private runtime,
and the constructor builds that private runtime when ``runtime=`` is not
passed — existing callers are unchanged.

Scoring semantics (identical to every prior PR): a state answers
``(Bq queries x capacity candidates)`` in ONE dispatch — per query only
the context cache (P_C, s_C, lin_C) is computed, O(rho m_C k), then every
candidate costs O(rho k) against the precomputed item cache
(``repro.serving.corpus``).  ``score``/``topk`` take an already-assembled
(Bq, m_C_slots) int32 context batch (weights default to ones in
``cfg.dtype``) and are NON-blocking: they return device arrays under JAX
async dispatch — reading a result blocks.  Online traffic goes through
``QueryFrontend``, which coalesces requests into power-of-two
micro-batches and serializes churn against in-flight reads via this
state's ``on_mutate`` hook.

Mutable corpus (capacity-padded slab + validity mask)
-----------------------------------------------------
The deployed corpus churns continuously (ads enter/leave the marketplace,
Section 5.3), so the corpus lives in a slab padded to a power-of-two
``capacity`` with a ``valid`` mask and a free-list:

  * ``add_items`` / ``update_items`` / ``remove_items`` write only the
    touched slot rows — one small jitted scatter dispatch of O(Δn rho k)
    work (Δn bucketed to a power of two, out-of-range filler indices
    dropped), never a rebuild;
  * every jitted shape is a function of ``capacity`` alone, so arbitrary
    churn causes ZERO retraces; masked scoring pins dead slots to ``-inf``
    so they can never win a top-K slot;
  * slot assignments are stable: returned corpus indices keep meaning the
    same item across churn AND across model refreshes (``refresh`` rebuilds
    every slab row in place);
  * when the free-list runs dry the slab doubles (amortized O(1) per add);
    doubling is the only shape change and therefore the only operation
    after which the scorer re-traces — once per doubling (and only for
    the FIRST tenant to reach that capacity: the trace then serves every
    tenant on the shared runtime).

Model refresh (the sliding-window retrain deployment of Section 5.3) swaps
the parameter arrays and rebuilds the corpus cache WITHOUT retracing the
jitted scorer: shapes are refresh-invariant, so the swap is two dispatches
(cache rebuild + next score) — no recompilation stall in the query loop.
``maybe_refresh`` polls a ``CheckpointManager`` and performs the swap when
a newer step lands; it tracks the last *polled* step signature so a
corrupt newest checkpoint (restore falls back to an older valid step)
costs one restore attempt total, not a re-restore + cache rebuild on
every poll — while a later re-save of that step number is still picked up.

Sharded slab (capacity scales with the mesh)
--------------------------------------------
Build the runtime with ``mesh=`` (axes from ``launch/mesh.py``) and every
tenant's slab shards across the ``model`` axis: D devices each hold a
capacity/D slice of the cache.  Global slot ``g`` is owned by shard
``g % D`` at local row ``g // D`` (striped, so slab doubling never
renumbers a slot — see ``repro.serving.sharded``); churn deltas are
grouped per owning shard host-side and each device computes/scatters only
its own rows; ``topk`` merges the D device-local top-Ks with O(D·K)
traffic and is BIT-exact vs the unsharded engine, ties included.  Every
public method keeps identical semantics and slot numbering either way —
``mesh=None`` (the default) is simply D=1 on the local device.
"""
from __future__ import annotations

import heapq
import time

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.serving.corpus import next_pow2
from repro.serving.errors import NotReady, RefreshFailed
from repro.serving.runtime import ScorerRuntime
from repro.serving.sanitize import scoring_guard
from repro.serving.telemetry import OFF, span


class CorpusState:
    """One tenant's mutable, capacity-padded item corpus plus its model
    snapshot; every compute dispatch runs through a ``ScorerRuntime``
    (private by default, shared across tenants when passed in).

    Self-healing (see docs/robustness.md): mutations are DEVICE-first so
    a failed churn write leaves the host slab/validity state untouched
    (partial churn is never reader-visible); a Pallas kernel-launch
    fault degrades stickily to the jnp reference scorer
    (``kernel_degraded`` — bit-exact results, zero new traces when the
    grid was warmed), while a kernel that fails to lower or compile
    raises (see ``_kernel_dispatch``); ``maybe_refresh`` raises ``RefreshFailed`` on a
    corrupt newest checkpoint while KEEPING the last-good snapshot live.
    ``fault_injector`` arms the ``write``/``alloc``/``kernel`` chaos
    sites (``repro.serving.faults``)."""

    def __init__(self, cfg, item_ids, item_weights=None, *,
                 capacity: int | None = None, mesh=None,
                 use_pallas_kernel: bool = False,
                 block_n: int | None = None,
                 runtime: ScorerRuntime | None = None, fault_injector=None):
        if runtime is None:
            runtime = ScorerRuntime(cfg, mesh=mesh,
                                    use_pallas_kernel=use_pallas_kernel,
                                    block_n=block_n)
        else:
            if cfg is not None and cfg is not runtime.cfg:
                raise ValueError(
                    "CorpusState(cfg=..., runtime=...): the runtime was "
                    "built for a different config; pass runtime.cfg (or "
                    "cfg=None)")
            if mesh is not None and mesh is not runtime.mesh:
                raise ValueError(
                    "CorpusState(mesh=..., runtime=...): mesh is a runtime "
                    "property; build the ScorerRuntime with it instead")
        self.runtime = runtime
        self._D = runtime.n_shards

        ids = np.asarray(item_ids, np.int32)
        n0 = int(ids.shape[0])
        w = (np.ones(ids.shape, np.float32) if item_weights is None
             else np.asarray(item_weights, np.float32))
        self.capacity = max(next_pow2(max(n0, 1)), self._D) \
            if capacity is None else int(capacity)
        if self.capacity < n0:
            raise ValueError(f"capacity={self.capacity} < initial corpus "
                             f"size n={n0}")
        if self.capacity & (self.capacity - 1):
            raise ValueError(f"capacity must be a power of two, "
                             f"got {self.capacity}")
        if self.capacity % self._D:
            raise ValueError(f"capacity={self.capacity} not divisible by "
                             f"the {self._D}-way corpus shard axis")

        # host-side slab (source of truth for ids/weights/liveness), in
        # GLOBAL slot order; the device-side cache mirrors it through
        # jitted writes (physical (local, D) view when sharded).
        self._slab_ids = np.zeros((self.capacity, ids.shape[1]), np.int32)
        self._slab_w = np.ones((self.capacity, ids.shape[1]), np.float32)
        self._slab_ids[:n0] = ids
        self._slab_w[:n0] = w
        self._valid_np = np.zeros(self.capacity, bool)
        self._valid_np[:n0] = True
        # free slots as one min-heap of LOCAL rows per shard (shard of
        # slot g is g % D; D=1 degenerates to the classic single heap):
        # lowest-numbered GLOBAL slot is handed out first, O(D + log cap)
        # per op, and striping makes that order spread across shards.
        self._free = [[] for _ in range(self._D)]
        for g in range(n0, self.capacity):
            self._free[g % self._D].append(g // self._D)
        self._n_free = self.capacity - n0

        self.params: dict | None = None
        self.cache = None                  # the model's slab (NamedTuple)
        self.model_step: int | None = None
        self._last_polled_sig: tuple | None = None
        self.refresh_count = 0
        self._injector = fault_injector
        # health/degradation surface (read by QueryFrontend.health()):
        self.kernel_degraded = False      # sticky Pallas->jnp fallback
        self.last_refresh_error: str | None = None
        self.last_refresh_time: float | None = None   # time.monotonic
        # writer barrier: called before ANY corpus mutation or model
        # refresh.  A QueryFrontend installs this tenant's drain here so
        # churn is serialized against the tenant's OWN in-flight reads
        # (single-writer / many-reader) without touching other tenants —
        # see repro.serving.frontend.
        self.on_mutate = None

    # -- runtime delegation -------------------------------------------------

    @property
    def cfg(self):
        return self.runtime.cfg

    @property
    def mesh(self):
        return self.runtime.mesh

    @property
    def use_pallas_kernel(self) -> bool:
        return self.runtime.use_pallas_kernel

    @property
    def _wdtype(self):
        return self.runtime.wdtype

    @property
    def query_width(self) -> int:
        """Slots of one query row (the frontend checks submits by it)."""
        return self.runtime.query_width

    def query_vectors(self, context_ids) -> tuple[int, int]:
        """(query vectors, live ones) a launch of these query rows scans,
        counted on the host: one per row for DPLR-FwFM; K per row for
        MIND, of which the user's K'_u are live."""
        return self.runtime.model.vectors(context_ids)

    @property
    def trace_count(self) -> int:
        """Scorer traces of the UNDERLYING runtime — shared across every
        tenant on it, which is exactly what the cross-tenant zero-retrace
        invariants assert on."""
        return self.runtime.trace_count

    @property
    def fault_injector(self):
        """The attached ``FaultInjector`` (None when chaos is off).
        Settable after construction, so a driver can arm chaos against an
        engine it did not build (e.g. one assembled with a mesh/kernel
        by generic setup code)."""
        return self._injector

    @fault_injector.setter
    def fault_injector(self, injector) -> None:
        self._injector = injector

    # -- corpus introspection -----------------------------------------------

    @property
    def n_items(self) -> int:
        """Live (valid) item count — NOT the slab capacity.  O(1): the
        free-lists hold exactly the dead slots (this sits on the per-query
        top-K range check)."""
        return self.capacity - self._n_free

    @property
    def occupancy(self) -> float:
        """Live fraction of the slab, ``n_items / capacity`` — i.e.
        1 − free-list fraction.  The autoscaling signal: a slab near 1.0
        is one ``add_items`` burst away from a reactive mid-call grow."""
        return 1.0 - self._n_free / self.capacity

    def maybe_autoscale(self, high: float) -> bool:
        """Proactively double the slab once ``occupancy >= high`` —
        the same ``_grow`` path ``add_items`` falls back on, behind the
        same writer barrier (in-flight reads drain first), but paid at a
        scheduled tick instead of inside an unlucky hot-path insert.
        Costs one trace per NEW capacity on the (shared) runtime; a
        no-op before the first ``refresh`` (nothing to re-pad) or below
        the mark.  Returns True when it grew."""
        if not 0.0 < high <= 1.0:
            raise ValueError(f"high={high} outside (0, 1]")
        if self.cache is None or self.occupancy < high:
            return False
        self._begin_write()
        self._grow(1)                  # doubles: new = max(2*old, ...)
        return True

    @property
    def n_shards(self) -> int:
        """Corpus shard count D (1 when unsharded)."""
        return self._D

    @property
    def local_capacity(self) -> int:
        """Slots per shard: each device holds capacity/D cache rows."""
        return self.capacity // self._D

    def shard_of(self, slots) -> np.ndarray:
        """Owning shard of each global slot id (striped: ``g % D``)."""
        return np.asarray(slots, np.int64) % self._D

    @property
    def valid_slots(self) -> np.ndarray:
        """(n_items,) ascending corpus indices of the live slots."""
        return np.flatnonzero(self._valid_np)

    def is_live(self, indices) -> np.ndarray:
        """Elementwise liveness of corpus slot indices (out-of-range =>
        False) — the public check callers should use on returned top-K
        indices across churn."""
        idx = np.asarray(indices, np.int64).reshape(-1)
        ok = (0 <= idx) & (idx < self.capacity)
        out = np.zeros(idx.shape, bool)
        out[ok] = self._valid_np[idx[ok]]
        return out.reshape(np.shape(indices))

    # -- corpus mutation (the churn path) -----------------------------------

    def _begin_write(self) -> None:
        """Run the writer barrier (if installed) before mutating the
        corpus or swapping the model.  With a ``QueryFrontend`` attached
        this drains THIS tenant's queued and in-flight micro-batches
        first, so no reader ever observes a half-applied write and every
        reply is delivered against the snapshot its batch was dispatched
        on — other tenants' reads are untouched."""
        if self.on_mutate is not None:
            self.on_mutate()

    def _alloc_slot(self) -> int:
        """Pop the lowest-numbered free GLOBAL slot across the per-shard
        heaps.  The order is identical to a single global heap (striping:
        shard s's heap head l encodes global l*D + s), so the sharded and
        unsharded engines assign the same slots for the same op sequence."""
        best_s, best_g = -1, -1
        for s, heap in enumerate(self._free):
            if heap:
                g = heap[0] * self._D + s
                if best_g < 0 or g < best_g:
                    best_s, best_g = s, g
        heapq.heappop(self._free[best_s])
        self._n_free -= 1
        return best_g

    def _free_slot(self, g: int) -> None:
        heapq.heappush(self._free[g % self._D], g // self._D)
        self._n_free += 1

    def _scatter_rows(self, slots, ids, w):
        # DEVICE write first, host mirror second: if the scatter dispatch
        # fails (or an armed 'write' fault fires), the host slab /
        # validity mask / liveness counts are untouched — a mid-flight
        # mutation failure leaves readers on the exact pre-churn
        # snapshot, never a half-applied one (tests/test_faults.py).
        if self._injector is not None:
            self._injector.check("write")
        self.cache = self.runtime.write_rows(self.params, self.cache,
                                             slots, ids, w)
        self._slab_ids[slots] = ids
        self._slab_w[slots] = w
        self._valid_np[slots] = True

    def _payload(self, ids, weights, op, n_expected=None):
        """Normalize + validate a (Δn, n_item_slots) ids/weights payload;
        a short payload must raise, not silently numpy-broadcast one row
        into every targeted slot."""
        ids = np.atleast_2d(np.asarray(ids, np.int32))
        if n_expected is not None and ids.shape[0] != n_expected:
            raise ValueError(
                f"{op}: {n_expected} slots but {ids.shape[0]} item rows")
        w = (np.ones(ids.shape, np.float32) if weights is None
             else np.atleast_2d(np.asarray(weights, np.float32)))
        if w.shape != ids.shape:
            raise ValueError(f"{op}: weights shape {w.shape} != ids shape "
                             f"{ids.shape}")
        return ids, w

    def add_items(self, ids, weights=None) -> np.ndarray:
        """Insert Δn items; returns their (Δn,) corpus slot indices (stable
        until removed).  O(Δn rho k) — one row-compute + one scatter
        dispatch; doubles the slab first if the free-list runs dry.
        Blocking behavior: returns after the scatter is *dispatched* (not
        complete); runs the writer barrier first (see ``_begin_write``)."""
        self._require_ready()
        self._begin_write()
        with span("engine.write", op="add"):
            ids, w = self._payload(ids, weights, "add_items")
            dn = ids.shape[0]
            if dn > self._n_free:
                self._grow(dn - self._n_free)
            slots = np.asarray([self._alloc_slot() for _ in range(dn)],
                               np.int32)
            try:
                self._scatter_rows(slots, ids, w)
            except Exception:
                # roll the allocation back: the rows were never written,
                # so n_items must not count them and the slots must stay
                # free — the failed add is invisible (retryable) to every
                # reader
                for g in slots:
                    self._free_slot(int(g))
                raise
        return slots

    def update_items(self, indices, ids, weights=None) -> None:
        """Rewrite the items at the given live slots in place (same cost
        shape as ``add_items``); slot assignments are unchanged."""
        self._require_ready()
        self._begin_write()
        with span("engine.write", op="update"):
            slots = np.asarray(indices, np.int32).reshape(-1)
            self._check_live(slots, "update_items")
            ids, w = self._payload(ids, weights, "update_items",
                                   n_expected=slots.size)
            self._scatter_rows(slots, ids, w)

    def remove_items(self, indices) -> None:
        """Invalidate the given live slots (their rows become free; masked
        scoring pins them to -inf immediately).  One scatter dispatch."""
        self._require_ready()
        self._begin_write()
        with span("engine.write", op="remove"):
            slots = np.asarray(indices, np.int32).reshape(-1)
            self._check_live(slots, "remove_items")
            # device-first, like _scatter_rows: a failed drop leaves the
            # host mask/free-lists untouched (the remove simply didn't
            # happen)
            if self._injector is not None:
                self._injector.check("write")
            self.cache = self.runtime.drop_rows(self.cache, slots)
            self._valid_np[slots] = False
            for s in slots:
                self._free_slot(int(s))

    def _check_live(self, slots, op):
        if len(np.unique(slots)) != len(slots):
            raise ValueError(f"{op}: duplicate slot indices")
        if slots.size and not (
                (0 <= slots).all() and (slots < self.capacity).all()
                and self._valid_np[slots].all()):
            raise ValueError(f"{op}: slot indices must be live corpus slots")

    def _grow(self, min_extra: int) -> None:
        """Double the slab (at least) so >= min_extra slots are free.  The
        ONLY shape-changing operation: the next score/build traces once for
        the new capacity (once per capacity on the SHARED runtime — a
        second tenant reaching the same capacity retraces nothing),
        amortized O(1) per added item.

        Sharded: growth pads the LOCAL axis of every shard's cache slice —
        striped ownership means the new global slots [old, new) are exactly
        the new local rows [old/D, new/D) on each shard, and every live
        slot keeps its (shard, local) address (ids never renumber)."""
        # the 'alloc' fault site: an armed injector models the slab-growth
        # allocation failing (device OOM).  Checked before ANY state is
        # touched, so a failed grow is a clean no-op and the add_items
        # that wanted it raises with the corpus unchanged.
        if self._injector is not None:
            self._injector.check("alloc")
        old = self.capacity
        new = max(old * 2, next_pow2(old + min_extra))
        extra = new - old
        self._slab_ids = np.pad(self._slab_ids, ((0, extra), (0, 0)))
        self._slab_w = np.pad(self._slab_w, ((0, extra), (0, 0)),
                              constant_values=1.0)
        self._valid_np = np.pad(self._valid_np, (0, extra))
        # every new local row is > every existing free row of its shard,
        # so a plain append preserves each per-shard min-heap invariant
        for g in range(old, new):
            self._free[g % self._D].append(g // self._D)
        self._n_free += extra
        self.capacity = new
        if self.cache is not None:
            # every slab leaf has the (local) slot axis first: pad it
            ex = extra // self._D        # per-shard local rows added
            self.cache = jax.tree.map(
                lambda a: jnp.pad(a, ((0, ex),) + ((0, 0),) * (a.ndim - 1)),
                self.cache)

    # -- corpus/model lifecycle --------------------------------------------

    def refresh(self, params: dict, step: int | None = None) -> None:
        """Install a model snapshot: rebuild every slab row IN PLACE (one
        jitted dispatch, slot assignments preserved), keep the runtime's
        jit cache intact.  Sharded: each device rebuilds only its own
        capacity/D rows (the global-order host slab reshapes to the
        physical (local, D) view for free, because ownership is striped)."""
        self._begin_write()
        with span("engine.refresh", op="refresh"):
            if self.mesh is not None:
                # one replica per shard, placed once per snapshot: left on
                # one device, the whole model (embedding arena included)
                # would be copied to every shard on every sharded dispatch
                params = jax.device_put(params,
                                        NamedSharding(self.mesh, P()))
            self.params = params
            if self.mesh is None:
                self.cache = self.runtime.build(
                    params, jnp.asarray(self._slab_ids),
                    jnp.asarray(self._slab_w, self._wdtype),
                    jnp.asarray(self._valid_np))
            else:
                lc = self.local_capacity
                ids = self._slab_ids.reshape(lc, self._D, -1)
                w = self._slab_w.reshape(lc, self._D, -1)
                self.cache = self.runtime.build(
                    params, jnp.asarray(ids), jnp.asarray(w, self._wdtype),
                    jnp.asarray(self._valid_np.reshape(lc, self._D)))
        self.model_step = step
        self.refresh_count += 1
        self.last_refresh_time = time.monotonic()

    def maybe_refresh(self, manager, template, select=lambda t: t) -> bool:
        """CheckpointManager invalidation hook: if a newer checkpoint step
        exists, restore it and rebuild the corpus cache.  ``template`` is
        the pytree structure passed to ``manager.restore``; ``select``
        extracts the model params from the restored tree.

        Returns True on a swap, False when there is nothing newer (or the
        newest landing was a backward step — skipped, as ever).  A newest
        step that FAILS VALIDATION (corrupt/torn payload, nothing newer
        restorable) raises ``RefreshFailed`` with the offending step and
        its poll signature attached — the engine KEEPS SERVING its
        last-good snapshot; the error reports the bad push, it does not
        interrupt service.  A corrupt newest with a valid intermediate
        step (older than newest, newer than installed) installs the
        intermediate and returns True, recording the bad push in
        ``last_refresh_error``.

        Poison-safe: the newest step's SIGNATURE (step + manifest mtime) is
        recorded BEFORE restoring, and a poll that finds the same corrupt
        signature again returns False silently — so a poisoned checkpoint
        costs one restore attempt and raises ONCE, not a restore + error
        per poll, while a later RE-SAVE of the same step number (new
        mtime) is still picked up.
        """
        # cheap name-only poll: no checksum pass over retained checkpoints
        # in the serving loop; restore() below validates what it loads.
        step = manager.latest_step(validate=False)
        if step is None or step == self.model_step:
            return False
        sig = manager.step_signature(step)
        if sig == self._last_polled_sig:
            return False
        self._last_polled_sig = sig
        restored, rstep = manager.restore(template)
        if restored is None or (self.model_step is not None
                                and rstep is not None
                                and rstep <= self.model_step):
            # the newest step is unrestorable and nothing NEWER than the
            # installed snapshot validated: surface the failed push (the
            # last-good snapshot stays live and keeps serving)
            self.last_refresh_error = (
                f"checkpoint step {step} failed validation; serving "
                f"last-good step {self.model_step}")
            raise RefreshFailed(self.last_refresh_error, step=step,
                                signature=sig)
        if rstep is not None and rstep < step:
            # newest failed validation but an intermediate step validated:
            # forward progress (install it) + a recorded bad push
            self.last_refresh_error = (
                f"checkpoint step {step} failed validation; installed "
                f"fallback step {rstep}")
        else:
            self.last_refresh_error = None
        self.refresh(select(restored), step=rstep)
        return True

    # -- public scoring API -------------------------------------------------

    def _require_ready(self):
        if self.cache is None:
            raise NotReady("engine has no model: call refresh() first")

    def _ctx_arrays(self, context_ids, context_weights):
        ids = jnp.asarray(context_ids)
        w = (jnp.ones(ids.shape, self._wdtype) if context_weights is None
             else jnp.asarray(context_weights, self._wdtype))
        return ids, w

    def score(self, context_ids, context_weights=None) -> jax.Array:
        """(Bq, capacity) scores for a batch of query contexts; dead slots
        score exactly ``NEG_INF``.

        ``context_ids``: (Bq, m_C_slots) int32 local context slot ids;
        ``context_weights``: matching float (defaults to ones in
        ``cfg.dtype``).  Output dtype follows ``cfg.dtype``.  Non-
        blocking: returns a device array under JAX async dispatch —
        ``np.asarray``/``block_until_ready`` is where the wait happens."""
        self._require_ready()
        ids, w = self._ctx_arrays(context_ids, context_weights)
        if self.use_pallas_kernel and not self.kernel_degraded:
            out = _kernel_dispatch(
                (self,), (None, ids.shape),
                lambda: self.runtime.kernel_score(self.params, self.cache,
                                                  ids, w))
            if out is not None:
                return out
        with scoring_guard():
            return self.runtime.score(self.params, self.cache, ids, w)

    def topk(self, context_ids, K: int, context_weights=None, *,
             batch: int | None = None):
        """((Bq, K) scores, (Bq, K) int32 corpus slot indices) — only the
        winners leave the scorer, not the (Bq, capacity) logit matrix.
        Masked: a dead slot can never be returned (K is checked against the
        LIVE item count, not the slab capacity).

        Rows are sorted best-first with ``lax.top_k`` tie-breaking
        (lowest slot id wins — preserved bit-exactly by the sharded
        merge), so truncating a top-``K`` result to any ``K' < K`` IS the
        top-``K'`` result — the property the frontend's one-max-K-
        dispatch-per-batch design rests on.  Non-blocking, like
        ``score``.  K is static under jit: each distinct K traces once
        on the shared runtime (the frontend quantizes K to power-of-two
        buckets for exactly this reason).  ``batch`` (the frontend's
        dispatch number) labels the model's launch span, where it has
        one (MIND: ``engine.mind_launch``)."""
        self._require_ready()
        if not 0 < K <= self.n_items:
            raise ValueError(
                f"topk K={K} out of range for corpus of {self.n_items} "
                f"live items")
        name = self.runtime.model.span
        with span(name, batch=batch) if name else OFF:
            ids, w = self._ctx_arrays(context_ids, context_weights)
            if self.use_pallas_kernel and not self.kernel_degraded:
                out = _kernel_dispatch(
                    (self,), (K, ids.shape),
                    lambda: self.runtime.kernel_score(
                        self.params, self.cache, ids, w, K=K))
                if out is not None:
                    return out
            with scoring_guard():
                return self.runtime.topk(self.params, self.cache, ids, w,
                                         K=K)

    def warmup_grid(self, context_ids, context_weights=None, *,
                    max_batch: int = 16, max_k: int = 16) -> int:
        """Trace the reachable (Bq bucket x K bucket) grid for THIS
        state's capacity with a representative context; returns the
        number of dispatches.  On a SHARED runtime the grid is warm for
        every tenant with the same capacity: warming a second such tenant
        dispatches the same grid but adds zero traces (the cross-tenant
        aha the multi-tenant benchmark asserts).  Call after
        ``refresh``."""
        ctx = np.asarray(context_ids, np.int32).reshape(-1)
        w = (np.ones(ctx.shape, np.float32) if context_weights is None
             else np.asarray(context_weights, np.float32).reshape(-1))
        n = 0
        bq = 1
        while bq <= max_batch:
            ids_b = np.broadcast_to(ctx, (bq, ctx.shape[0]))
            w_b = np.broadcast_to(w, (bq, w.shape[0]))
            k = 1
            while k <= min(next_pow2(max_k), self.n_items):
                self.topk(ids_b, k, w_b)
                n += 1
                if self.use_pallas_kernel and not self.kernel_degraded:
                    # warm the jnp reference path at the same shape: the
                    # sticky kernel-degradation fallback must cost ZERO
                    # mid-serve traces when it fires
                    jids, jw = self._ctx_arrays(ids_b, w_b)
                    self.runtime.topk(self.params, self.cache, jids, jw,
                                      K=k)
                    n += 1
                k *= 2
            bq *= 2
        return n

    def score_query(self, query: dict) -> jax.Array:
        """Convenience for ``rank_items``-style query dicts (item tensors,
        if present, are ignored — the corpus is the engine's)."""
        return self.score(query["context_ids"], query.get("context_weights"))


def _kernel_dispatch(states, key, launch):
    """Run one Pallas dispatch for ``states`` (one shared runtime) and
    return its result, or None after degrading every state STICKILY to
    the jnp reference path on a launch fault: the armed ``kernel`` fault
    site, or an error from an executable this runtime has already run at
    ``key`` (with the states' capacities added to it).  The first dispatch
    at a key traces, lowers and compiles the kernel, so its error — a
    Mosaic lowering error, VMEM or HBM exhausted at compile — propagates:
    a kernel that cannot compile stops the server at warmup instead of
    hiding behind the jnp path."""
    rt = states[0].runtime
    key = key + (tuple(st.capacity for st in states),)
    warm = True                 # an injected fault degrades unconditionally
    try:
        for st in states:
            if st._injector is not None:
                st._injector.check("kernel")
        warm = key in rt.kernel_warm
        with scoring_guard():
            out = launch()
    except Exception:                     # noqa: BLE001 — see docstring
        if not warm:
            raise
        for st in states:
            st.kernel_degraded = True
        return None
    rt.kernel_warm.add(key)
    return out


def fused_topk(states, context_ids, K: int, context_weights=None):
    """ONE device dispatch answering S tenants' micro-batches: returns
    ``((S, Bq, K) scores, (S, Bq, K) int32 slot indices)`` where row
    ``[s]`` is bit-exact ``states[s].topk(context_ids[s], K)`` — the
    fused multi-tenant path the ``QueryFrontend`` packs same-runtime
    tenants through (``pack=True``).

    ``states`` must share one ``ScorerRuntime`` (that is what makes the
    fusion a single trace) and each must be ready with ``K <= n_items``.
    ``context_ids``: (S, Bq, m_C_slots) stacked micro-batches — one
    common Bq, because the frontend buckets to a common power of two
    before packing.  On a mesh, all states must also share one capacity
    (the frontend's pack key guarantees both).

    Kernel selection and self-healing mirror ``CorpusState.topk``: the
    Pallas path runs only while NO packed state is kernel-degraded, each
    state's armed ``kernel`` fault site is checked, and a launch fault
    stickily degrades every packed state to the (bit-exact) jnp fused
    path — a poisoned kernel never splits the pack's fate."""
    states = tuple(states)
    if not states:
        raise ValueError("fused_topk needs at least one state")
    rt = states[0].runtime
    for st in states:
        if st.runtime is not rt:
            raise ValueError(
                "fused_topk states must share one ScorerRuntime (tenants "
                "on different runtimes cannot pack into one dispatch)")
        st._require_ready()
        if not 0 < K <= st.n_items:
            raise ValueError(
                f"fused_topk K={K} out of range for a corpus of "
                f"{st.n_items} live items")
    if rt.mesh is not None and len(
            {st.local_capacity for st in states}) != 1:
        raise ValueError("fused mesh top-K needs equal capacities; the "
                         "frontend's pack key guarantees this")
    ids = jnp.asarray(context_ids)
    if ids.ndim != 3 or ids.shape[0] != len(states):
        raise ValueError(f"context_ids must stack to (S={len(states)}, "
                         f"Bq, m_C_slots), got {ids.shape}")
    w = (jnp.ones(ids.shape, rt.wdtype) if context_weights is None
         else jnp.asarray(context_weights, rt.wdtype))
    params_parts = tuple(st.params for st in states)
    cache_parts = tuple(st.cache for st in states)
    if rt.use_pallas_kernel and not any(st.kernel_degraded
                                        for st in states):
        out = _kernel_dispatch(
            states, (K, ids.shape),
            lambda: rt.kernel_multi_topk(params_parts, cache_parts, ids, w,
                                         K=K))
        if out is not None:
            return out
    with scoring_guard():
        return rt.multi_topk(params_parts, cache_parts, ids, w, K=K)


# The historical single-tenant name: one CorpusState over a private
# runtime.  Kept as a true alias so isinstance checks and imports from
# every prior PR keep working.
CorpusRankingEngine = CorpusState
