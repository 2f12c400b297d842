"""Tenant-routed async micro-batching query frontend (the online path).

A ``CorpusState`` scores a *batch* of query contexts for ONE corpus in
one jitted dispatch, but an online service receives queries one at a
time — each with its own K, deadline, and (in a real ad deployment)
**tenant**: the per-advertiser / per-market / per-surface corpus it
ranks against.  ``QueryFrontend`` is the layer in between: it keeps one
request queue per tenant, coalesces each tenant's requests into
power-of-two padded micro-batches, round-robins the non-empty tenant
queues into a SHARED in-flight dispatch window, and sheds load it cannot
serve in time with a fast ``Overloaded`` error instead of queueing it.

Request lifecycle (see docs/multitenant.md for the full walkthrough):

    submit ──► admission ──► per-tenant queue (EDF order)
                  │                 │   round-robin across tenants
              Overloaded            ▼
                         [bucket Bq, pad] ──► dispatch (async) ──► in-flight
                                                                      │
    reply  ◄── truncate to per-query K ◄── resolve (block) ◄──────────┘

A reply is ``((k,) scores, (k,) int32 corpus slot ids)`` in the
engine's dtypes, best first — bit-exact vs a lone ``engine.topk(ctx, k)``
call on that request's tenant.

Tenants
-------
Construct with one engine (single-tenant, exactly the historical API) or
a ``{name: CorpusState}`` dict; ``add_tenant``/``remove_tenant`` manage
the set live.  Each tenant keeps its own queue, stats, and writer
barrier; they share the dispatch window, the (Bq, K) bucket grid, and —
when their states sit on one ``ScorerRuntime`` — the trace cache, so a
new tenant with an already-warm shape signature serves with ZERO
retraces.  A micro-batch never mixes tenants' *rows* (different
corpora), but batches from different tenants overlap freely in the
in-flight window — and with ``pack=True`` they can share one LAUNCH
(below).

Fused multi-tenant dispatch (``pack=True``)
-------------------------------------------
At high tenant counts with small per-tenant micro-batches (16 tenants x
Bq<=4 is the regime the multitenant benchmark gates), per-dispatch
overhead dominates: each launch pays the Python->jit boundary, transfer,
and kernel-launch cost for a handful of rows.  With ``pack=True`` the
scheduler opportunistically FUSES ready same-shape tenants into one
``engine.fused_topk`` launch: whenever a SWRR turn picks a lane, up to
``pack_max - 1`` further turns are granted to other eligible lanes with
the same **pack key** — ``(runtime identity, slab capacity, context
width)`` — and the group dispatches as ONE device call whose kernel
scores every tenant's segment against its own corpus slab (segmented
top-K: a reply can never receive a neighbour segment's slot).  Each
tenant's rows stay bit-exact vs its own unpacked ``engine.topk``.

The retrace invariant survives packing because every packed axis is
bucketed: one common Bq bucket (max over the group), one common K bucket
(max over the group), and the SEGMENT COUNT pads up to a power of two
``<= pack_max`` by repeating the last tenant's segment (phantom
segments are scored and discarded, like padding rows).  The reachable
fused shape set is thus (S buckets x Bq buckets x K buckets) per
capacity — ``warmup_packed`` traces it once.  Groups degrade gracefully:
a group whose common K bucket exceeds some member's live corpus unpacks
into per-tenant dispatches, a single-lane "group" short-circuits to the
classic path, and EDF order within every lane plus SWRR fairness across
lanes are preserved (each packed lane pays a real scheduler turn).
``stats["fused_dispatches"]``/``stats["fused_segments"]`` count the
wins; ``health()["packing"]`` reports the running mean group size.

Coalescing and the retrace invariant
------------------------------------
A jitted scorer retraces on every new (Bq, K) shape, so the frontend
quantizes both:

  * **Bq buckets** — a micro-batch of q queries pads up to the next power
    of two ``<= max_batch`` by repeating a real context row (padding rows
    are scored and discarded; per-row scores are independent, so real
    rows are bit-identical to a lone dispatch of the same context);
  * **K buckets**  — one dispatch serves every K in the batch: the engine
    runs top-``K_pad`` where ``K_pad = next_pow2(max K)``, and each reply
    is the host-side truncation to its own K (exact: ``lax.top_k`` output
    is sorted, so the first K of top-``K_pad`` IS top-K).

The reachable shape set is therefore the fixed grid (Bq buckets x K
buckets x tenant capacities): ``warmup()`` traces it once per DISTINCT
capacity, and after that arbitrary arrival patterns, batch sizes,
per-query Ks, and tenant mixes cause ZERO retraces (asserted by
``tests/test_frontend.py``, ``tests/test_multitenant.py``, and the
``--frontend``/``--tenant-demo`` drivers).

Dispatch order: EDF within a tenant, weighted fairness across tenants
---------------------------------------------------------------------
Within a tenant's queue, requests that carry deadlines pop
earliest-deadline-first; deadline-less requests keep FIFO order (and
sort after any deadlined request) — a tight-deadline late arrival
overtakes a slack early one (tested).  Across tenants, ``pump`` and
``flush`` run smooth weighted round-robin (SWRR) over the eligible
lanes: every turn each candidate lane earns ``weight`` credit, the
richest lane wins the turn and pays back the sum of the candidates'
weights, so over any window each tenant's share of dispatch turns
converges to its weight share — with equal weights (the default) this
IS plain round-robin, turn for turn.  At most one micro-batch is taken
per turn, so one tenant's backlog can never starve another's traffic
out of the shared window, and removing a tenant mid-stream cannot skew
the schedule (credits live on the lanes, not in a cursor).

On top of the weights, an optional per-tenant **QPS quota** (requests
per second, token bucket with burst capacity ``max_batch``) bounds how
fast the *scheduler* serves a lane: a lane with no tokens is skipped by
``pump`` until its bucket refills (``lane_stats``'s
``quota_deferred``).  Quotas shape scheduling only — explicit blocking
paths (``PendingQuery.result``, ``drain``, ``close``, the writer
barrier) bypass them, so an accepted request can ALWAYS be resolved and
a quota-starved tenant never wedges its own drain, let alone another
tenant's traffic.  Weights and quotas are set at ``add_tenant`` time
and re-tunable live via ``set_tenant_policy``.

Capacity autoscaling (the occupancy signal)
-------------------------------------------
With ``autoscale_high=f`` the pump tick watches each tenant's slab
occupancy (``n_items / capacity``, i.e. 1 − free-list fraction) and
proactively doubles a slab that crossed the high-water mark via
``CorpusState.maybe_autoscale`` — the same ``_grow`` path churn uses,
behind the same writer barrier.  The trade: growth costs ONE trace per
new capacity on the shared runtime, paid at a scheduled pump tick
instead of inside some unlucky ``add_items`` call on the hot path.
Off by default (``None``); ``stats["autoscales"]`` counts grows.

Admission control (load shedding)
---------------------------------
Two signals, both OFF by default (pass the knob to enable):

  * ``admit_depth`` — a tenant whose queue already holds this many
    requests sheds new submits with ``Overloaded`` immediately: under
    sustained overload the queue stays bounded and every accepted
    request is served, instead of every request timing out.
  * ``admit_deadlines`` — a deadlined submit whose predicted completion
    ``now + max_wait + (queued batches + in-flight + 1) · EWMA(batch
    service time)`` already exceeds its deadline sheds with
    ``Overloaded`` at submit — a fast reject, not a ``DeadlineExceeded``
    after the deadline burned in the queue.

Shedding raises from ``submit`` before the request is queued; it never
affects already-accepted requests (counted in ``stats["shed"]``).

Overlapped dispatch (the async window)
--------------------------------------
``engine.topk`` returns device arrays immediately (JAX async dispatch);
nothing blocks until a result is *read*.  The frontend exploits that
with a depth-``inflight`` window (default 2, i.e. double buffering)
SHARED across tenants: batch N's replies are materialized (one blocking
host sync) only when the window is full, the caller asks for a result,
or a drain runs — by which time batch N+1's assembly and context
transfer already happened *under* batch N's device time.

Churn vs in-flight reads (per-tenant writer barrier)
----------------------------------------------------
Corpus mutations and model refreshes are serialized against in-flight
queries PER TENANT: registering tenant T installs ``T.on_mutate =
drain(T)``, so any writer entry point on T's state (``add_items`` /
``remove_items`` / ``update_items`` / ``refresh``) first flushes T's
queued requests and resolves T's in-flight batches — and ONLY T's:
tenant-A churn never drains tenant-B's in-flight reads (tested).  Every
reply is computed — and delivered — against the corpus snapshot that was
live when its batch was dispatched, and a returned slot id is live at
reply time.

The per-tenant hook alone makes this airtight when reads and writes
share one thread (the event-loop discipline).  A SEPARATE writer thread
must mutate through the frontend's own ``add_items`` / ``remove_items``
/ ``update_items`` / ``refresh`` wrappers (``tenant=`` selects the
lane), which hold the frontend lock across the barrier AND the state
write — otherwise a submit could dispatch between the drain and the mask
update and deliver slots the in-progress churn is about to kill.

Deadlines
---------
A request may carry an absolute ``deadline`` (frontend-clock seconds).
A request still queued past its deadline is failed with
``DeadlineExceeded`` at the next dispatch — a clean error, never a score
computed against a stale corpus.  Once dispatched, a request is always
answered (the answer is correct; lateness is the caller's policy).

Self-healing (failures are typed, bounded, and recovered from)
---------------------------------------------------------------
Every failure the frontend hands a caller is a ``repro.serving.errors.
ServingError`` subclass, and every ACCEPTED request resolves — with a
result or a typed error, never silently dropped — under every fault the
chaos suite injects (docs/robustness.md):

  * **retry/backoff** — a failed micro-batch dispatch re-dispatches the
    SAME assembled batch (identical ctx/weights/K bucket, so a reply
    that eventually succeeds is bit-exact with a fault-free run) up to
    ``retries`` times with exponential backoff + seeded jitter; only
    then does the batch fail with ``DispatchFailed``.
  * **circuit breaker** — ``breaker_threshold`` consecutive exhausted
    dispatches trip the TENANT's breaker: submits shed fast with
    ``Degraded`` (no queueing behind a dead backend) until
    ``breaker_cooldown`` elapses, then the breaker half-opens and the
    next accepted request is the probe — its dispatch success closes the
    breaker, failure re-opens it.  Other tenants' lanes are untouched
    (their queues, their in-flight batches, their breakers).
  * **pressure-K clamp** — under sustained queue pressure
    (``pressure_depth``) dispatches clamp each request's served K to
    ``pressure_k``: smaller top-K buckets, less device work per batch.
    A clamped reply is the EXACT top-``pressure_k`` prefix of the full
    answer (top-K rows are sorted) and is flagged ``degraded`` on its
    ``PendingQuery`` — degraded-but-exact, never wrong.
  * **pump watchdog** — ``start_pump`` runs the pump on a background
    thread plus a watchdog that detects a stalled heartbeat and restarts
    the pump loop (``stats["pump_restarts"]``); a stalled generation
    exits harmlessly when it wakes.
  * **health probe** — ``health()`` reports per-tenant breaker state,
    queue depths, last-refresh age, and degradation flags; ``close()``
    shuts down gracefully (in-flight batches resolve to real results,
    queued requests fail with typed ``Unservable``).

Telemetry
---------
``frontend.telemetry`` (a ``repro.serving.telemetry.Stages``) holds the
latency histograms of where requests wait: ``queue`` (submit -> the
launch holding the request returns) and ``inflight`` (launch returns ->
finish), observed at finish; ``read_block`` per resolved batch; and the
``write.lock`` / ``write.barrier`` / ``write.apply`` stages of the writer
wrappers.  An ``RpcServer`` adds ``rpc`` and ``server``.  The profiler
spans ``frontend.dispatch``, ``frontend.resolve`` and
``frontend.barrier`` mark the same boundaries in a trace;
``health()["stages"]`` reports the histograms' quantiles.

The frontend is an event-loop-style coalescer, not a thread pool: one
thread calls ``submit``/``pump``/``result``; a separate churn thread is
supported via the frontend's writer wrappers (above).  All public entry
points are non-blocking except ``PendingQuery.result``, ``drain``,
``close``, and the writer wrappers.
"""
from __future__ import annotations

import collections
import heapq
import math
import threading
import time
from functools import partial

import numpy as np

from repro.serving.corpus import next_pow2
from repro.serving.engine import fused_topk
from repro.serving.errors import (Degraded, DeadlineExceeded, DispatchFailed,
                                  Overloaded, ServingError, Unservable)
from repro.serving.telemetry import Stages, span


class PendingQuery:
    """Future-like handle for one submitted ranking request.

    ``result()`` returns ``(scores, slots)`` — ``(K,) float`` scores and
    ``(K,) int32`` corpus slot indices, best first — blocking until the
    request's micro-batch resolves (and forcing a flush if it is still
    queued).  ``done()`` never blocks.  ``submit_time``/``done_time`` are
    frontend-clock stamps for latency accounting; ``tenant`` names the
    lane that served it.

    Degradation: under sustained pressure the frontend may clamp the
    served K below the requested ``k`` (``pressure_k``); the reply is
    then the exact top-``served_k`` prefix of the full answer and
    ``degraded`` is True.  Healthy replies have ``served_k == k``.

    Telemetry: ``batch`` is the frontend's dispatch sequence number of
    the micro-batch that answered the request; ``t_submit_ns`` and
    ``t_finish_ns`` are ``time.perf_counter_ns`` stamps of submit entry
    and of the finish (stage boundaries of ``repro.serving.telemetry``).
    """

    __slots__ = ("k", "served_k", "degraded", "deadline", "submit_time",
                 "done_time", "tenant", "batch", "t_submit_ns",
                 "t_finish_ns", "_frontend", "_ctx", "_w",
                 "_scores", "_slots", "_error", "_taken")

    def __init__(self, frontend, tenant, ctx, w, k, deadline, submit_time,
                 t_submit_ns):
        self.k = k
        self.served_k = k            # lowered only by the pressure clamp
        self.degraded = False
        self.deadline = deadline
        self.submit_time = submit_time
        self.done_time = None
        self.tenant = tenant
        self.batch = None
        self.t_submit_ns = t_submit_ns
        self.t_finish_ns = None
        self._frontend = frontend
        self._ctx = ctx
        self._w = w
        self._scores = None
        self._slots = None
        self._error = None
        self._taken = False          # popped from its lane's queue

    def done(self) -> bool:
        return self.done_time is not None

    def result(self):
        """((K,) scores, (K,) int32 slot ids).  Blocks: flushes the queue
        if needed, then resolves in-flight batches up to this one.  Raises
        ``DeadlineExceeded``/``FrontendError`` if the request failed."""
        # snapshot BEFORE the done() check: a concurrent writer-wrapper
        # drain may finish this request (clearing _frontend) between the
        # check and the call; _resolve_until re-checks under the lock
        fe = self._frontend
        if not self.done() and fe is not None:
            fe._resolve_until(self)
        if self._error is not None:
            raise self._error
        return self._scores, self._slots

    def _finish(self, scores, slots, now, batch, t_finish_ns):
        self._scores, self._slots = scores, slots
        # stamped before done() turns True: a reader on another thread
        # that sees done() sees them
        self.batch, self.t_finish_ns = batch, t_finish_ns
        self.done_time = now
        self._frontend = self._ctx = self._w = None

    def _fail(self, err, now):
        self._error = err
        self.done_time = now
        self._frontend = self._ctx = self._w = None


class _InFlight:
    """One dispatched-but-unresolved micro-batch: the device arrays plus
    the requests (in row order) awaiting truncation, the tenant it was
    scored against, and the ASSEMBLED batch (ctx/w/k_pad) so a failure
    surfacing at resolve time can re-dispatch the identical batch
    (bit-exact recovery).

    A batch that rode a fused multi-tenant launch carries ``launch``
    (the shared ``_PackedLaunch``) and its segment row ``seg`` instead
    of per-batch device arrays; its ``ctx``/``w`` still hold THIS
    tenant's assembled rows, so the resolve-time recovery path can
    re-dispatch just this segment as a classic single-tenant batch
    (bit-exact: the fused kernel's per-segment rows equal the unpacked
    dispatch).  ``batch`` is the dispatch sequence number and
    ``t_launch_ns`` the ``perf_counter_ns`` stamp at which the launch
    returned."""

    __slots__ = ("requests", "vals", "idx", "tenant", "ctx", "w", "k_pad",
                 "batch", "t_launch_ns", "launch", "seg")

    def __init__(self, requests, vals, idx, tenant, ctx, w, k_pad, batch,
                 t_launch_ns, launch=None, seg=None):
        self.requests = requests
        self.vals = vals
        self.idx = idx
        self.tenant = tenant
        self.ctx = ctx
        self.w = w
        self.k_pad = k_pad
        self.batch = batch
        self.t_launch_ns = t_launch_ns
        self.launch = launch
        self.seg = seg


class _PackedLaunch:
    """The shared result of ONE fused multi-tenant dispatch: the (S, Bq,
    K) device arrays plus a one-shot host materialization every member
    segment's resolve reuses — the first resolve pays the blocking read,
    the rest slice for free.  A read failure is remembered so every
    segment takes its own single-tenant recovery path instead of
    re-raising from a half-dead launch."""

    __slots__ = ("vals", "idx", "np_vals", "np_idx", "error")

    def __init__(self, vals, idx):
        self.vals = vals
        self.idx = idx
        self.np_vals = None
        self.np_idx = None
        self.error = None

    def read(self):
        """((S, Bq, K) scores, (S, Bq, K) indices) as host arrays;
        blocks on the device exactly once."""
        if self.error is not None:
            raise self.error
        if self.np_vals is None:
            try:
                self.np_vals = np.asarray(self.vals)
                self.np_idx = np.asarray(self.idx)
            except Exception as e:        # noqa: BLE001 — deferred device
                self.error = e
                raise
        return self.np_vals, self.np_idx


class _TenantLane:
    """Per-tenant frontend state: the engine (CorpusState), the EDF
    request queue, per-tenant counters, the tenant's circuit breaker
    (``closed`` -> ``open`` on consecutive dispatch failures ->
    ``half_open`` after cooldown -> ``closed`` on probe success), and
    the tenant's share of the cross-tenant scheduler — its SWRR
    ``weight``/``credit`` pair and, when a QPS ``quota`` is set, a token
    bucket (``tokens`` refilled at ``quota``/s from the ``tok_t``
    stamp, burst-capped at the frontend's ``max_batch``)."""

    __slots__ = ("name", "engine", "heap", "arrivals", "n_ctx", "stats",
                 "breaker", "fails", "opened_at", "weight", "quota",
                 "tokens", "tok_t", "credit")

    def __init__(self, name, engine, weight=1.0, quota=None):
        self.name = name
        self.engine = engine
        self.heap: list = []                      # (deadline|inf, seq, req)
        self.arrivals: collections.deque = collections.deque()  # FIFO view
        self.n_ctx = engine.query_width
        self.stats = {"submitted": 0, "completed": 0, "shed": 0,
                      "failed": 0, "trips": 0, "quota_deferred": 0}
        self.breaker = "closed"                   # closed|open|half_open
        self.fails = 0                            # consecutive exhausted
        self.opened_at = None                     # frontend-clock stamp
        self.weight = float(weight)               # SWRR share
        self.quota = None if quota is None else float(quota)
        self.tokens = 0.0                         # earned from tok_t on
        self.tok_t = None                         # last refill stamp
        self.credit = 0.0                         # SWRR running credit


class QueryFrontend:
    """Coalesces individual ranking requests into micro-batched, overlap-
    dispatched ``engine.topk`` calls, routed per tenant.

    Parameters
    ----------
    engines : CorpusState | dict[str, CorpusState]
        One scoring state (single-tenant; lane name ``"default"``) or a
        dict of tenant name -> state.  Each state may be single-device or
        mesh-sharded; states sharing one ``ScorerRuntime`` share the
        trace cache.  The frontend installs itself as each state's
        ``on_mutate``, so corpus churn and model refresh drain THAT
        tenant's in-flight queries first (one frontend per state).
    max_batch : int
        Largest micro-batch (power of two).  Bq buckets are
        ``1, 2, 4, …, max_batch``; a full bucket dispatches immediately.
    max_k : int
        Largest accepted per-request K.  K buckets are the powers of two
        up to ``next_pow2(max_k)``.
    max_wait : float
        Seconds a queued request may age before its lane's partial tail
        is force-dispatched at the next ``pump`` — the latency/occupancy
        knob.
    inflight : int
        Depth of the unresolved-dispatch window, shared across tenants
        (2 = double buffering).  Dispatching past the window resolves the
        oldest batch first.
    admit_depth : int | None
        Per-tenant queue-depth admission bound: a submit finding this
        many requests already queued on its lane sheds with
        ``Overloaded``.  ``None`` (default) disables depth shedding.
    admit_deadlines : bool
        Shed deadlined submits whose predicted completion already
        exceeds their deadline (EWMA of batch service time; see module
        docstring).  Default off.
    auto_pump : bool
        Run ``pump`` from inside ``submit`` (default).  Event-loop
        servers that pump on their own tick — and tests that need
        queues to actually build up — pass ``False``.
    clock : callable
        Time source (seconds).  Injectable for deterministic tests and
        trace-replay simulation; defaults to ``time.perf_counter``.
    retries : int
        Bounded re-dispatch attempts after a failed micro-batch dispatch
        (the SAME assembled batch, so recovered replies are bit-exact);
        0 fails fast.  Default 2.
    retry_backoff : float
        Base backoff (seconds) between dispatch retries; attempt i waits
        ``retry_backoff * 2**i`` scaled by seeded jitter in [0.5, 1.5).
    breaker_threshold : int | None
        Consecutive exhausted dispatches that trip a tenant's circuit
        breaker (submits then shed fast with ``Degraded``).  ``None``
        (default) disables the breaker.
    breaker_cooldown : float
        Seconds an open breaker sheds before half-opening; the next
        accepted request is the probe (success closes, failure
        re-opens).
    pressure_depth : int | None
        Queue depth (post-batch, per tenant) at which dispatches clamp
        served K to ``pressure_k`` — degraded-but-exact replies under
        sustained pressure.  ``None`` (default) disables the clamp.
    pressure_k : int | None
        The clamped K (required with ``pressure_depth``; must be
        ``<= max_k`` so the clamped bucket is already warm).
    autoscale_high : float | None
        Slab-occupancy high-water mark in (0, 1]: each pump tick asks
        every lane's state to ``maybe_autoscale`` (proactive double via
        the churn ``_grow`` path) once ``n_items / capacity`` reaches
        it.  Costs one trace per NEW capacity — paid at a pump tick,
        not inside a hot-path ``add_items``.  ``None`` (default)
        disables autoscaling.
    pack : bool
        Fuse ready same-pack-key tenants into one ``fused_topk`` launch
        per scheduler round (see the module docstring's fused-dispatch
        section).  Default off — single-tenant and low-tenant-count
        deployments keep the classic one-dispatch-per-tenant path.
    pack_max : int
        Largest tenant count per fused launch (power of two >= 2;
        default 8).  The dispatched segment count pads up to a power of
        two <= ``pack_max``, so the fused trace grid stays the fixed
        (S buckets x Bq buckets x K buckets) set ``warmup_packed``
        covers.
    fault_injector : FaultInjector | None
        Chaos hook: an armed injector's ``dispatch``/``resolve``/``pump``
        sites fire inside this frontend (see ``repro.serving.faults``).
        ``None`` (default) = zero-overhead no-op.
    """

    def __init__(self, engines, *, max_batch: int = 16, max_k: int = 16,
                 max_wait: float = 2e-3, inflight: int = 2,
                 admit_depth: int | None = None,
                 admit_deadlines: bool = False, auto_pump: bool = True,
                 clock=time.perf_counter, retries: int = 2,
                 retry_backoff: float = 1e-3,
                 breaker_threshold: int | None = None,
                 breaker_cooldown: float = 0.05,
                 pressure_depth: int | None = None,
                 pressure_k: int | None = None,
                 autoscale_high: float | None = None,
                 pack: bool = False, pack_max: int = 8,
                 fault_injector=None):
        if max_batch < 1 or max_batch & (max_batch - 1):
            raise ValueError(f"max_batch must be a power of two, "
                             f"got {max_batch}")
        if max_k < 1:
            raise ValueError(f"max_k must be >= 1, got {max_k}")
        if inflight < 1:
            raise ValueError(f"inflight depth must be >= 1, got {inflight}")
        if admit_depth is not None and admit_depth < 1:
            raise ValueError(f"admit_depth must be >= 1, got {admit_depth}")
        if retries < 0:
            raise ValueError(f"retries must be >= 0, got {retries}")
        if breaker_threshold is not None and breaker_threshold < 1:
            raise ValueError(f"breaker_threshold must be >= 1, "
                             f"got {breaker_threshold}")
        if (pressure_depth is None) != (pressure_k is None):
            raise ValueError("pressure_depth and pressure_k come together")
        if pressure_k is not None and not 1 <= pressure_k <= max_k:
            raise ValueError(f"pressure_k={pressure_k} outside "
                             f"[1, max_k={max_k}]")
        if autoscale_high is not None and not 0.0 < autoscale_high <= 1.0:
            raise ValueError(f"autoscale_high={autoscale_high} outside "
                             f"(0, 1]")
        if pack_max < 2 or pack_max & (pack_max - 1):
            raise ValueError(f"pack_max must be a power of two >= 2, "
                             f"got {pack_max}")
        self.pack = bool(pack)
        self.pack_max = pack_max
        self.max_batch = max_batch
        self.max_k = max_k
        self.max_wait = float(max_wait)
        self.inflight = inflight
        self.admit_depth = admit_depth
        self.admit_deadlines = admit_deadlines
        self.auto_pump = auto_pump
        self.clock = clock
        self.retries = retries
        self.retry_backoff = float(retry_backoff)
        self.breaker_threshold = breaker_threshold
        self.breaker_cooldown = float(breaker_cooldown)
        self.pressure_depth = pressure_depth
        self.pressure_k = pressure_k
        self.autoscale_high = autoscale_high
        self._injector = fault_injector
        self._rng = np.random.default_rng(0)     # retry jitter (seeded)
        self._closed = False
        self._lanes: dict[str, _TenantLane] = {}
        self._seq = 0                # global FIFO tie-break for EDF
        self._svc = None             # EWMA batch service time (seconds)
        self._batches = 0            # dispatch sequence number
        self._barrier_ns = 0         # drain time inside the current write
        self.telemetry = Stages()
        self._window: collections.deque[_InFlight] = collections.deque()
        self._lock = threading.RLock()
        # retry backoff waits on a Condition bound to the frontend lock:
        # Condition.wait releases the (re-entrant) lock at EVERY recursion
        # depth for the duration of the pause, so submits/pump ticks keep
        # flowing while a faulted dispatch backs off (never time.sleep
        # while holding self._lock)
        self._retry_wait = threading.Condition(self._lock)
        # background pump + watchdog state (start_pump): the generation
        # token lets the watchdog orphan a stalled pump thread — a stale
        # generation exits harmlessly when it finally wakes
        self._pump_run = False
        self._pump_gen = 0
        self._pump_beat = 0.0        # time.monotonic heartbeat
        self._pump_interval = 1e-3
        self._watchdog_timeout = None
        self._pump_thread = None
        self._watchdog_thread = None
        self.stats = {"submitted": 0, "completed": 0, "expired": 0,
                      "failed": 0, "shed": 0, "dispatches": 0,
                      "dispatched_rows": 0, "padded_rows": 0, "drains": 0,
                      "retries": 0, "degraded": 0, "clamped": 0,
                      "pump_restarts": 0, "pump_errors": 0,
                      "autoscales": 0, "fused_dispatches": 0,
                      "fused_segments": 0, "scan_vectors": 0,
                      "live_vectors": 0}
        self.last_pump_error: BaseException | None = None
        if hasattr(engines, "topk"):         # single engine, classic API
            engines = {"default": engines}
        for name, engine in engines.items():
            self.add_tenant(name, engine)

    # -- tenant management --------------------------------------------------

    def add_tenant(self, name: str, engine, *, weight: float = 1.0,
                   quota: float | None = None) -> None:
        """Register a tenant lane and install its writer barrier
        (``engine.on_mutate`` -> drain THIS tenant only).  The new tenant
        serves with zero retraces if its state's shape signature —
        runtime + capacity — is already warm.

        ``weight`` is the lane's SWRR share of cross-tenant dispatch
        turns (default 1.0 = equal); ``quota`` is an optional QPS cap
        (token bucket, burst ``max_batch``) the pump scheduler honors —
        a fresh lane starts with an empty bucket and earns tokens from
        registration time on."""
        if weight <= 0.0:
            raise ValueError(f"weight must be > 0, got {weight}")
        if quota is not None and quota <= 0.0:
            raise ValueError(f"quota must be > 0 requests/s, got {quota}")
        with self._lock:
            if name in self._lanes:
                raise ValueError(f"tenant {name!r} already registered")
            lane = _TenantLane(name, engine, weight, quota)
            lane.tok_t = self.clock()    # an empty bucket earns from here
            self._lanes[name] = lane
            # the per-tenant writer barrier: any mutation of THIS state
            # drains THIS lane before touching the corpus — other
            # tenants' queues and in-flight batches are untouched
            engine.on_mutate = partial(self._drain_tenant, name)

    def set_tenant_policy(self, name: str, *, weight: float | None = None,
                          quota: float | None = None) -> None:
        """Re-tune a live lane's scheduler share: ``weight`` replaces its
        SWRR weight, ``quota`` its QPS cap (pass ``math.inf`` to lift a
        cap — ``None`` means "leave unchanged" here).  Takes effect on
        the next pump turn; queued requests are untouched."""
        with self._lock:
            lane = self._lane(name)
            if weight is not None:
                if weight <= 0.0:
                    raise ValueError(f"weight must be > 0, got {weight}")
                lane.weight = float(weight)
            if quota is not None:
                if quota <= 0.0:
                    raise ValueError(f"quota must be > 0 requests/s, "
                                     f"got {quota}")
                lane.quota = None if math.isinf(quota) else float(quota)
                lane.tokens = min(lane.tokens, float(self.max_batch))

    def remove_tenant(self, name: str) -> None:
        """Drain and deregister a tenant (its queued + in-flight requests
        are answered first; the state's writer barrier is detached).
        SWRR credits live on the lanes, so removal cannot skew the
        surviving tenants' schedule."""
        with self._lock:
            self._drain_tenant(name)
            lane = self._lanes.pop(name)
            lane.engine.on_mutate = None

    @property
    def tenants(self) -> tuple:
        return tuple(self._lanes)

    def lane_stats(self, tenant: str | None = None) -> dict:
        """Per-tenant counters: submitted / completed / shed / queued."""
        lane = self._lane(tenant)
        return dict(lane.stats, queued=len(lane.heap))

    def _lane(self, tenant: str | None) -> _TenantLane:
        if tenant is None:
            if len(self._lanes) != 1:
                raise ValueError(
                    f"tenant= required: frontend routes "
                    f"{len(self._lanes)} tenants {tuple(self._lanes)}")
            return next(iter(self._lanes.values()))
        try:
            return self._lanes[tenant]
        except KeyError:
            raise ValueError(f"unknown tenant {tenant!r}; registered: "
                             f"{tuple(self._lanes)}") from None

    # -- request ingress ----------------------------------------------------

    def submit(self, context_ids, context_weights=None, *, k: int = 10,
               deadline: float | None = None,
               tenant: str | None = None) -> PendingQuery:
        """Enqueue one ranking request; returns its ``PendingQuery``.

        ``context_ids``: (n_context_slots,) int — ONE query's context
        (a leading unit axis is squeezed).  ``k``: winners wanted,
        ``1 <= k <= max_k``.  ``deadline``: absolute frontend-clock time
        after which the request must fail rather than be served late.
        ``tenant``: the lane to rank against (optional when exactly one
        tenant is registered).  Non-blocking; raises ``Overloaded``
        instead of queueing when admission control sheds (see module
        docstring), ``Degraded`` while the tenant's circuit breaker is
        open, and ``Unservable`` after ``close()``.  With ``auto_pump``
        a full bucket dispatches at once.
        """
        t_submit = time.perf_counter_ns()
        with self._lock:
            if self._closed:
                raise Unservable("frontend is closed", tenant=tenant)
            lane = self._lane(tenant)
            ctx = np.asarray(context_ids, np.int32).reshape(-1)
            if ctx.shape[0] != lane.n_ctx:
                raise ValueError(f"context has {ctx.shape[0]} slots, "
                                 f"layout expects {lane.n_ctx}")
            w = (np.ones(ctx.shape, np.float32) if context_weights is None
                 else np.asarray(context_weights, np.float32).reshape(-1))
            if w.shape != ctx.shape:
                raise ValueError(f"context_weights shape {w.shape} != "
                                 f"context shape {ctx.shape}")
            if not 1 <= k <= self.max_k:
                raise ValueError(f"k={k} outside [1, max_k={self.max_k}]")
            now = self.clock()
            if not self._breaker_allows(lane, now):
                lane.stats["shed"] += 1
                self.stats["degraded"] += 1
                raise Degraded(
                    f"tenant {lane.name!r} circuit breaker open after "
                    f"{lane.fails} consecutive dispatch failures",
                    tenant=lane.name)
            self._admit(lane, deadline, now)
            req = PendingQuery(self, lane.name, ctx, w, int(k), deadline,
                               now, t_submit)
            heapq.heappush(lane.heap,
                           (math.inf if deadline is None else deadline,
                            self._seq, req))
            self._seq += 1
            lane.arrivals.append(req)
            lane.stats["submitted"] += 1
            self.stats["submitted"] += 1
            if self.auto_pump:
                self.pump(now)
        return req

    def _admit(self, lane, deadline, now) -> None:
        """Admission control: shed (raise ``Overloaded``) instead of
        queueing a request the frontend cannot serve in time."""
        if (self.admit_depth is not None
                and len(lane.heap) >= self.admit_depth):
            lane.stats["shed"] += 1
            self.stats["shed"] += 1
            raise Overloaded(
                f"tenant {lane.name!r} queue depth {len(lane.heap)} >= "
                f"admit_depth {self.admit_depth}", tenant=lane.name)
        if (self.admit_deadlines and deadline is not None
                and self._svc is not None):
            backlog = (len(lane.heap) // self.max_batch
                       + len(self._window) + 1)
            eta = now + self.max_wait + backlog * self._svc
            if eta > deadline:
                lane.stats["shed"] += 1
                self.stats["shed"] += 1
                raise Overloaded(
                    f"tenant {lane.name!r}: predicted completion "
                    f"{eta - now:.4f}s out exceeds deadline "
                    f"{deadline - now:.4f}s out", tenant=lane.name)

    # -- self-healing: circuit breaker + bounded retry ----------------------

    def _breaker_allows(self, lane, now) -> bool:
        """Breaker gate for SUBMITS only: already-queued requests still
        dispatch (accepted => resolved, even against a sick backend).
        An open breaker half-opens after the cooldown; the next accepted
        request is the probe."""
        if lane.breaker == "open":
            if now - lane.opened_at >= self.breaker_cooldown:
                lane.breaker = "half_open"
                return True
            return False
        return True                       # closed or half_open (probing)

    def _breaker_failure(self, lane, now) -> None:
        """An exhausted dispatch on this lane: trip at the threshold, and
        re-open immediately if the half-open probe just failed."""
        if self.breaker_threshold is None:
            return
        lane.fails += 1
        if (lane.breaker == "half_open"
                or lane.fails >= self.breaker_threshold):
            if lane.breaker != "open":
                lane.stats["trips"] += 1
            lane.breaker = "open"
            lane.opened_at = now

    def _breaker_success(self, lane) -> None:
        lane.fails = 0
        if lane.breaker != "closed":
            lane.breaker = "closed"
            lane.opened_at = None

    def _launch(self, lane, ctx, w, k_pad, batch=None):
        """Dispatch ONE assembled micro-batch with bounded retry: every
        attempt re-dispatches the identical (ctx, w, k_pad) — same shape
        bucket (no retrace), same rows (a reply that eventually succeeds
        is bit-exact with a fault-free run).  Exponential backoff with
        seeded jitter between attempts; raises ``DispatchFailed`` once
        ``retries`` re-dispatches are exhausted."""
        attempts = self.retries + 1
        for i in range(attempts):
            try:
                if self._injector is not None:
                    self._injector.check("dispatch")
                return lane.engine.topk(ctx, k_pad, w, batch=batch)
            except Exception as e:            # noqa: BLE001 — typed below
                if i + 1 >= attempts:
                    raise DispatchFailed(
                        f"tenant {lane.name!r}: micro-batch dispatch "
                        f"failed after {attempts} attempts: {e}",
                        tenant=lane.name, attempts=attempts) from e
                self.stats["retries"] += 1
                pause = self.retry_backoff * (2.0 ** i)
                pause *= 0.5 + self._rng.random()     # jitter in [.5, 1.5)
                if pause > 0.0:
                    # Condition.wait, NOT time.sleep: _launch runs with
                    # self._lock held, and wait() releases the RLock at
                    # all depths for the pause — submits, pump ticks and
                    # the watchdog keep flowing while this batch backs
                    # off.  Nobody notifies; the timeout IS the backoff.
                    self._retry_wait.wait(timeout=pause)

    # -- batching policy ----------------------------------------------------

    def _has_quota(self, lane, now) -> bool:
        """Refill the lane's token bucket to ``now`` (at ``quota``
        tokens/s, burst-capped at ``max_batch``) and report whether it
        can afford a scheduler turn.  No quota => always eligible."""
        if lane.quota is None:
            return True
        if lane.tok_t is None:
            lane.tok_t = now
        dt = now - lane.tok_t
        if dt > 0.0:
            lane.tokens = min(float(self.max_batch),
                              lane.tokens + dt * lane.quota)
            lane.tok_t = now
        return lane.tokens >= 1.0

    def _consume_quota(self, lane, n: int) -> None:
        """Pay ``n`` dispatched requests out of the bucket.  The balance
        may go negative (a turn is granted on >= 1 token but a batch
        carries up to ``max_batch`` requests); the deficit is clamped at
        ``-max_batch`` so one burst never mortgages the lane forever."""
        if lane.quota is not None:
            lane.tokens = max(lane.tokens - n, -float(self.max_batch))

    def _pick(self, pred, now, *,
              respect_quota: bool = True) -> _TenantLane | None:
        """One smooth-weighted-round-robin turn over the lanes passing
        ``pred`` (and, on the scheduler path, holding quota tokens):
        every candidate earns its ``weight`` in credit, the richest lane
        wins the turn and pays back the candidates' combined weight, so
        dispatch turns converge to the weight shares over any window —
        with equal weights this is exactly round-robin, turn for turn.
        Ties break by registration order.  Returns None when no lane is
        eligible; credits persist on the lanes, so tenant removal cannot
        skew the surviving schedule."""
        eligible = []
        for lane in self._lanes.values():
            if not pred(lane):
                continue
            if respect_quota and not self._has_quota(lane, now):
                lane.stats["quota_deferred"] += 1
                continue
            eligible.append(lane)
        if not eligible:
            return None
        total = 0.0
        for lane in eligible:
            lane.credit += lane.weight
            total += lane.weight
        best = max(eligible, key=lambda ln: ln.credit)
        best.credit -= total
        return best

    def _pack_key(self, lane):
        """Fused-dispatch compatibility key: lanes with equal keys can
        share one ``fused_topk`` launch with zero retraces — same
        runtime (same trace cache, same mesh), same slab capacity (same
        cache shapes; on a mesh this also equalizes ``local_capacity``),
        same context width.  ``None`` = unpackable (not ready)."""
        eng = lane.engine
        if getattr(eng, "cache", None) is None:
            return None
        return (id(eng.runtime), int(eng.capacity), lane.n_ctx)

    def _collect_group(self, first, pred, now, *,
                       respect_quota: bool = True) -> list[_TenantLane]:
        """Grow a fused-dispatch group around the lane a scheduler turn
        just picked: grant up to ``pack_max - 1`` FURTHER SWRR turns,
        each restricted to lanes that pass ``pred`` and share ``first``'s
        pack key.  Every member pays a real turn, so packing preserves
        the weighted fairness schedule exactly; with ``pack=False`` (or
        nobody compatible) the group is just ``[first]``."""
        group = [first]
        if not self.pack or len(self._lanes) < 2:
            return group
        key = self._pack_key(first)
        if key is None:
            return group
        names = {first.name}
        while len(group) < self.pack_max:
            mate = self._pick(
                lambda ln: (ln.name not in names and pred(ln)
                            and self._pack_key(ln) == key),
                now, respect_quota=respect_quota)
            if mate is None:
                break
            names.add(mate.name)
            group.append(mate)
        return group

    def _oldest_age(self, lane, now) -> float | None:
        """Age of the lane's oldest still-queued request (arrival order —
        independent of the EDF dispatch order)."""
        while lane.arrivals and lane.arrivals[0]._taken:
            lane.arrivals.popleft()
        if not lane.arrivals:
            return None
        return now - lane.arrivals[0].submit_time

    def pump(self, now: float | None = None) -> int:
        """Advance the frontend: dispatch every full ``max_batch`` bucket
        (weighted SWRR turns across tenants, quota-gated), plus each
        lane's partial tail once its oldest request has aged past
        ``max_wait``.  With ``autoscale_high`` set, first give every
        lane's slab its occupancy check.  Call this from the serving
        loop on every arrival (and on ticks while idle); non-blocking
        unless the in-flight window must evict.  Returns the number of
        batches dispatched."""
        with self._lock:
            if now is None:
                now = self.clock()
            if self.autoscale_high is not None:
                for lane in self._lanes.values():
                    if lane.engine.maybe_autoscale(self.autoscale_high):
                        self.stats["autoscales"] += 1
            n = 0
            full = lambda ln: len(ln.heap) >= self.max_batch  # noqa: E731
            while True:
                lane = self._pick(full, now)
                if lane is None:
                    break
                group = self._collect_group(lane, full, now)
                if len(group) == 1:
                    self._dispatch(lane, self._take(lane, self.max_batch),
                                   now)
                else:
                    self._dispatch_group(
                        [(ln, self._take(ln, self.max_batch))
                         for ln in group], now)
                n += 1
            aged = lambda ln: (self._oldest_age(ln, now)  # noqa: E731
                               or -1.0) >= self.max_wait
            for lane in list(self._lanes.values()):
                age = self._oldest_age(lane, now)
                if age is not None and age >= self.max_wait:
                    if not self._has_quota(lane, now):
                        lane.stats["quota_deferred"] += 1
                        continue
                    group = self._collect_group(lane, aged, now)
                    if len(group) == 1:
                        self._dispatch(lane,
                                       self._take(lane, len(lane.heap)),
                                       now)
                    else:
                        self._dispatch_group(
                            [(ln, self._take(
                                ln, min(len(ln.heap), self.max_batch)))
                             for ln in group], now)
                    n += 1
            return n

    def flush(self) -> int:
        """Dispatch everything queued on every tenant regardless of age,
        one micro-batch per tenant per SWRR turn (still async — does not
        resolve).  QUOTAS ARE BYPASSED: flush backs the blocking paths
        (``result``/``drain``/``close``), where liveness beats pacing —
        an accepted request can always be resolved.  Returns the number
        of batches dispatched."""
        with self._lock:
            now = self.clock()
            n = 0
            queued = lambda ln: len(ln.heap) > 0  # noqa: E731
            while True:
                lane = self._pick(queued, now, respect_quota=False)
                if lane is None:
                    break
                group = self._collect_group(lane, queued, now,
                                            respect_quota=False)
                if len(group) == 1:
                    self._dispatch(
                        lane,
                        self._take(lane,
                                   min(len(lane.heap), self.max_batch)),
                        now)
                else:
                    self._dispatch_group(
                        [(ln, self._take(
                            ln, min(len(ln.heap), self.max_batch)))
                         for ln in group], now)
                n += 1
            return n

    def drain(self) -> None:
        """Flush and resolve EVERY tenant's queued and in-flight batches
        (blocking) — the full-stop barrier, e.g. before shutdown."""
        with self._lock:
            for name in list(self._lanes):
                self._drain_tenant(name)

    def _drain_tenant(self, name: str) -> None:
        """The per-tenant writer barrier: flush THIS lane's queue and
        resolve THIS lane's in-flight batches (blocking).  The state
        calls it (via ``on_mutate``) before any corpus mutation or model
        refresh; other tenants' queues and windows are untouched."""
        with self._lock, span("frontend.barrier", tenant=name):
            t0 = time.perf_counter_ns()
            self.stats["drains"] += 1
            lane = self._lanes[name]
            now = self.clock()
            while lane.heap:
                self._dispatch(
                    lane,
                    self._take(lane, min(len(lane.heap), self.max_batch)),
                    now)
            keep = collections.deque()
            while self._window:
                fl = self._window.popleft()
                if fl.tenant == name:
                    self._resolve(fl)
                else:
                    keep.append(fl)
            self._window = keep
            self._barrier_ns += time.perf_counter_ns() - t0

    # -- writer entry points (atomic barrier + mutation) --------------------
    #
    # Calling a state's mutators directly still drains its lane first
    # (the on_mutate hook), which fully serializes churn in the
    # single-threaded event-loop discipline.  A SEPARATE writer thread
    # must mutate through these wrappers instead: they hold the frontend
    # lock across barrier AND mutation, so no submit can slip a dispatch
    # in between drain and the mask update (which could deliver slots the
    # in-progress churn is about to kill).

    def _write(self, tenant, apply):
        """``apply(engine)`` on the tenant's state under the frontend
        lock, observing the ``write.lock``, ``write.barrier`` and
        ``write.apply`` stages (the barrier runs inside the engine's
        writer, through ``on_mutate``; ``_drain_tenant`` adds its time
        to ``_barrier_ns``)."""
        t0 = time.perf_counter_ns()
        with self._lock:
            t1 = time.perf_counter_ns()
            self._barrier_ns = 0
            out = apply(self._lane(tenant).engine)
            t2 = time.perf_counter_ns()
            observe = self.telemetry.observe
            observe("write.lock", (t1 - t0) * 1e-9)
            observe("write.barrier", self._barrier_ns * 1e-9)
            observe("write.apply", (t2 - t1 - self._barrier_ns) * 1e-9)
        return out

    def add_items(self, ids, weights=None, *, tenant: str | None = None):
        """``engine.add_items`` on the tenant's state under the frontend
        lock (drain + write atomic vs concurrent submits); returns the
        new slot indices."""
        return self._write(tenant, lambda eng: eng.add_items(ids, weights))

    def remove_items(self, indices, *, tenant: str | None = None) -> None:
        """``engine.remove_items`` under the frontend lock."""
        self._write(tenant, lambda eng: eng.remove_items(indices))

    def update_items(self, indices, ids, weights=None, *,
                     tenant: str | None = None) -> None:
        """``engine.update_items`` under the frontend lock."""
        self._write(tenant,
                    lambda eng: eng.update_items(indices, ids, weights))

    def refresh(self, params, step=None, *,
                tenant: str | None = None) -> None:
        """``engine.refresh`` (model hot-swap) under the frontend lock."""
        self._write(tenant, lambda eng: eng.refresh(params, step=step))

    def maybe_refresh(self, manager, template, select=lambda t: t, *,
                      tenant: str | None = None) -> bool:
        """``engine.maybe_refresh`` under the frontend lock."""
        with self._lock:
            return self._lane(tenant).engine.maybe_refresh(
                manager, template, select=select)

    def _take(self, lane, m: int) -> list[PendingQuery]:
        out = []
        for _ in range(m):
            _, _, req = heapq.heappop(lane.heap)
            req._taken = True
            out.append(req)
        return out

    # -- dispatch (async) ---------------------------------------------------

    def _k_dispatch(self, lane, reqs) -> int:
        """Bucketed dispatch K: next_pow2(max SERVED K), lowered only
        if the lane's live item count sits below the bucket (rare; may
        trace).  Callers guarantee every request's k <= the live count."""
        k_max = max(r.served_k for r in reqs)
        k_pad = next_pow2(k_max)
        n_live = lane.engine.n_items
        while k_pad > n_live:
            k_pad //= 2
        return max(k_pad, k_max)

    def _filter_live(self, lane, reqs: list[PendingQuery],
                     now: float) -> list[PendingQuery]:
        """Pre-scoring request triage for one tenant's taken requests:
        fail past-deadline ones with ``DeadlineExceeded`` and ones whose
        k exceeds the lane's live corpus (churn shrank it since submit)
        with ``Unservable`` — individually; neither poisons its
        batchmates — then apply the pressure-K clamp to the survivors
        (with the lane's queue still deep AFTER this batch was taken,
        serve the exact top-``pressure_k`` prefix instead of the full K:
        smaller, already-warm K bucket, less device work per batch,
        replies flagged degraded but never wrong)."""
        n_live_items = lane.engine.n_items
        live = []
        for r in reqs:
            if r.deadline is not None and now > r.deadline:
                self.stats["expired"] += 1
                r._fail(DeadlineExceeded(
                    f"deadline exceeded after "
                    f"{(now - r.submit_time) * 1e3:.2f} ms in queue",
                    tenant=lane.name), now)
            elif r.k > n_live_items:
                self.stats["failed"] += 1
                lane.stats["failed"] += 1
                r._fail(Unservable(
                    f"k={r.k} exceeds tenant {lane.name!r}'s live corpus "
                    f"({n_live_items} items)", tenant=lane.name), now)
            else:
                live.append(r)
        if (live and self.pressure_depth is not None
                and len(lane.heap) >= self.pressure_depth):
            for r in live:
                if r.served_k > self.pressure_k:
                    r.served_k = self.pressure_k
                    r.degraded = True
                    self.stats["clamped"] += 1
        return live

    @staticmethod
    def _assemble(live: list[PendingQuery], bq: int):
        """Stack one tenant's live rows to the ``bq`` bucket.  Pads with
        a REAL context row: per-row scoring is independent, so real rows
        stay bit-identical and the filler rows cost no trace."""
        pad = bq - len(live)
        ctx = np.stack([r._ctx for r in live] + [live[0]._ctx] * pad)
        w = np.stack([r._w for r in live] + [live[0]._w] * pad)
        return ctx, w

    def _dispatch(self, lane, reqs: list[PendingQuery], now: float) -> None:
        """Assemble one micro-batch for ONE tenant and launch it (async).
        A dispatch that fails all its bounded retries fails the whole
        batch with ``DispatchFailed`` and feeds the lane's circuit
        breaker; see ``_filter_live`` for the per-request triage."""
        self._consume_quota(lane, len(reqs))
        live = self._filter_live(lane, reqs, now)
        if not live:
            return
        self._dispatch_live(lane, live, now)

    def _dispatch_live(self, lane, live: list[PendingQuery],
                       now: float) -> None:
        bq = min(next_pow2(len(live)), self.max_batch)
        k_pad = self._k_dispatch(lane, live)
        self._batches += 1
        batch = self._batches
        try:
            with span("frontend.dispatch", batch=batch, rows=len(live),
                      k=k_pad):
                ctx, w = self._assemble(live, bq)
                # async dispatch: engine.topk returns device arrays
                # without blocking — the device scores while the host
                # assembles the next micro-batch (the overlap this
                # frontend exists for)
                vals, idx = self._launch(lane, ctx, w, k_pad, batch)
        except DispatchFailed as e:
            for r in live:
                self.stats["failed"] += 1
                lane.stats["failed"] += 1
                r._fail(e, now)
            self._breaker_failure(lane, now)
            return
        t_launch = time.perf_counter_ns()
        self._breaker_success(lane)
        self._count_launch(lane, ctx, bq, len(live))
        self._window.append(_InFlight(live, vals, idx, lane.name,
                                      ctx, w, k_pad, batch, t_launch))
        while len(self._window) > self.inflight:
            self._resolve_oldest()

    def _count_launch(self, lane, ctx, bq: int, n_live: int) -> None:
        """Count one launched micro-batch: its rows, and the query
        vectors its scan carries (``CorpusState.query_vectors``)."""
        self.stats["dispatches"] += 1
        self.stats["dispatched_rows"] += bq
        self.stats["padded_rows"] += bq - n_live
        scan, live = lane.engine.query_vectors(ctx)
        self.stats["scan_vectors"] += scan
        self.stats["live_vectors"] += live

    def _dispatch_group(self, pairs, now: float) -> None:
        """Launch a ``_collect_group`` group as ONE fused dispatch:
        triage each lane's requests, bucket the group to a common Bq
        (max over lanes) and a common K bucket (max over lanes), pad the
        segment count to a power of two <= ``pack_max`` by repeating the
        last segment, and hand the stack to ``engine.fused_topk``.  Each
        member batch enters the in-flight window as its own ``_InFlight``
        slice of the shared ``_PackedLaunch``.  Degrades safely: one
        surviving lane takes the classic path, and a common K bucket
        exceeding some member's live corpus unpacks the group into
        per-tenant dispatches (rare; churn between collect and launch)."""
        live_pairs = []
        for lane, reqs in pairs:
            self._consume_quota(lane, len(reqs))
            live = self._filter_live(lane, reqs, now)
            if live:
                live_pairs.append((lane, live))
        if not live_pairs:
            return
        if len(live_pairs) == 1:
            self._dispatch_live(*live_pairs[0], now)
            return
        bq = min(max(next_pow2(len(live)) for _, live in live_pairs),
                 self.max_batch)
        k_pad = max(self._k_dispatch(lane, live)
                    for lane, live in live_pairs)
        if any(k_pad > lane.engine.n_items for lane, _ in live_pairs):
            for lane, live in live_pairs:
                self._dispatch_live(lane, live, now)
            return
        self._batches += 1
        batch = self._batches
        try:
            with span("frontend.dispatch", batch=batch,
                      rows=sum(len(live) for _, live in live_pairs),
                      k=k_pad):
                rows = [self._assemble(live, bq) for _, live in live_pairs]
                states = [lane.engine for lane, _ in live_pairs]
                # pad the SEGMENT count to its power-of-two bucket
                # (phantom segments repeat the last tenant's slab + rows
                # and are simply never read back): the fused trace grid
                # stays the fixed (S buckets x Bq buckets x K buckets)
                # set warmup_packed warms
                s_pad = next_pow2(len(live_pairs))
                ctx = np.stack([c for c, _ in rows]
                               + [rows[-1][0]] * (s_pad - len(rows)))
                w = np.stack([wt for _, wt in rows]
                             + [rows[-1][1]] * (s_pad - len(rows)))
                states = tuple(states
                               + [states[-1]] * (s_pad - len(states)))
                launch = self._launch_group(live_pairs, states, ctx, w,
                                            k_pad)
        except DispatchFailed as e:
            for lane, live in live_pairs:
                for r in live:
                    self.stats["failed"] += 1
                    lane.stats["failed"] += 1
                    r._fail(e, now)
                self._breaker_failure(lane, now)
            return
        t_launch = time.perf_counter_ns()
        self.stats["fused_dispatches"] += 1
        self.stats["fused_segments"] += len(live_pairs)
        for seg, (lane, live) in enumerate(live_pairs):
            self._breaker_success(lane)
            self._count_launch(lane, rows[seg][0], bq, len(live))
            self._window.append(_InFlight(live, None, None, lane.name,
                                          rows[seg][0], rows[seg][1],
                                          k_pad, batch, t_launch,
                                          launch=launch, seg=seg))
        while len(self._window) > self.inflight:
            self._resolve_oldest()

    def _launch_group(self, live_pairs, states, ctx, w, k_pad):
        """``_launch``'s fused twin: dispatch ONE packed batch with the
        same bounded-retry/backoff discipline, re-dispatching the
        identical (states, ctx, w, k_pad) stack every attempt."""
        attempts = self.retries + 1
        for i in range(attempts):
            try:
                if self._injector is not None:
                    self._injector.check("dispatch")
                vals, idx = fused_topk(states, ctx, k_pad, w)
                return _PackedLaunch(vals, idx)
            except Exception as e:        # noqa: BLE001 — typed below
                if i + 1 >= attempts:
                    names = tuple(lane.name for lane, _ in live_pairs)
                    raise DispatchFailed(
                        f"fused dispatch for tenants {names} failed "
                        f"after {attempts} attempts: {e}",
                        tenant=names[0], attempts=attempts) from e
                self.stats["retries"] += 1
                pause = self.retry_backoff * (2.0 ** i)
                pause *= 0.5 + self._rng.random()     # jitter in [.5, 1.5)
                if pause > 0.0:
                    self._retry_wait.wait(timeout=pause)

    # -- resolution (the only blocking step) --------------------------------

    def _resolve(self, fl: _InFlight) -> None:
        with span("frontend.resolve", batch=fl.batch):
            t_read = self.clock()
            lane = self._lanes.get(fl.tenant)
            try:
                if self._injector is not None:
                    self._injector.check("resolve")
                if fl.launch is not None:
                    # fused batch: the first member segment pays the one
                    # blocking read of the shared (S, Bq, K) launch; the
                    # rest slice the cached host arrays for free
                    all_vals, all_idx = fl.launch.read()
                    vals, idx = all_vals[fl.seg], all_idx[fl.seg]
                else:
                    vals = np.asarray(fl.vals)  # blocks until device done
                    idx = np.asarray(fl.idx)
            except Exception:               # noqa: BLE001 — deferred device
                # failure surfaced at materialization: re-dispatch the
                # SAME assembled batch (fl.ctx/fl.w/fl.k_pad — bit-exact)
                # and read it synchronously; only exhausted retries fail
                # the requests
                now = self.clock()
                try:
                    if lane is None:
                        raise DispatchFailed(
                            f"tenant {fl.tenant!r} removed with batch in "
                            f"flight", tenant=fl.tenant)
                    vals, idx = self._launch(lane, fl.ctx, fl.w, fl.k_pad)
                    vals = np.asarray(vals)
                    idx = np.asarray(idx)
                except DispatchFailed as e:
                    for r in fl.requests:
                        self.stats["failed"] += 1
                        if lane is not None:
                            lane.stats["failed"] += 1
                        r._fail(e, now)
                    if lane is not None:
                        self._breaker_failure(lane, now)
                    return
                if lane is not None:
                    self._breaker_success(lane)
            now = self.clock()
            # Admission-control service-time sample: the time this read
            # spent BLOCKED on the device, not wall time since dispatch —
            # a batch that sat resolved in a lazy window for 100 ms did
            # not take 100 ms of service.  Under light load samples are
            # ~0 (device idle => any sane deadline is feasible); under
            # overload the window evicts into genuinely-blocking reads
            # and the EWMA tracks the real per-batch cost — exactly the
            # regime shedding matters.
            dt = now - t_read
            self._svc = (dt if self._svc is None
                         else 0.3 * dt + 0.7 * self._svc)
            t_finish = time.perf_counter_ns()
            observe = self.telemetry.observe
            observe("read_block", dt)
            # one launch stamp per batch: every request's inflight is
            # the same
            observe("inflight", (t_finish - fl.t_launch_ns) * 1e-9,
                    len(fl.requests))
            for row, r in enumerate(fl.requests):
                # host-side truncation: top-k_pad is sorted best-first,
                # so its first served_k entries ARE the top-served_k
                # (bit-exact; served_k == k unless the pressure clamp
                # lowered it)
                r._finish(vals[row, :r.served_k], idx[row, :r.served_k],
                          now, fl.batch, t_finish)
                observe("queue", (fl.t_launch_ns - r.t_submit_ns) * 1e-9)
                self.stats["completed"] += 1
                if lane is not None:
                    lane.stats["completed"] += 1

    def resolve(self, max_batches: int | None = None) -> int:
        """Resolve up to ``max_batches`` of the OLDEST in-flight
        micro-batches (all of them when ``None``), blocking on their
        device reads.  The event-loop server's tick calls this right
        after ``pump`` so replies materialize on the tick instead of in
        some caller's ``result()``.  Returns the number resolved."""
        with self._lock:
            n = 0
            while self._window and (max_batches is None
                                    or n < max_batches):
                self._resolve_oldest()
                n += 1
            return n

    def _resolve_oldest(self) -> None:
        self._resolve(self._window.popleft())

    def _resolve_until(self, req: PendingQuery) -> None:
        with self._lock:
            if not req.done():
                self.flush()
            while not req.done() and self._window:
                self._resolve_oldest()
            if not req.done():
                raise Unservable("request neither queued nor in flight",
                                 tenant=req.tenant)

    # -- warmup -------------------------------------------------------------

    def warmup(self, context_ids, context_weights=None,
               tenant: str | None = None) -> int:
        """Trace the full reachable (Bq bucket x K bucket) grid once for
        one tenant's capacity with a representative context, so
        steady-state traffic — any arrival pattern, any mix of Ks —
        retraces NOTHING.  Tenants sharing a runtime AND a capacity are
        warm after any one of them warms (re-warming adds zero traces).
        Returns the number of warmup dispatches.  Call after the state's
        ``refresh``."""
        lane = self._lane(tenant)
        return lane.engine.warmup_grid(context_ids, context_weights,
                                       max_batch=self.max_batch,
                                       max_k=self.max_k)

    def warmup_packed(self, context_ids, context_weights=None,
                      tenant: str | None = None, *,
                      s_counts=None, batch_sizes=None, ks=None) -> int:
        """Trace the FUSED (S bucket x Bq bucket x K bucket) grid once
        for one tenant's pack key, so packed steady-state traffic — any
        group size up to ``pack_max``, any Bq/K mix — retraces NOTHING
        (``_dispatch_group`` pads every axis to these buckets).  The
        representative tenant's state is repeated S times per cell,
        which hits the exact trace a mixed-tenant group of the same pack
        key lands on (the jit key is the cache pytree STRUCTURE, not the
        member identities).  Lanes sharing a pack key are warm after any
        one of them warms.

        ``s_counts``/``batch_sizes``/``ks`` override the swept buckets
        (each a subset of the reachable powers of two) when the caller
        knows its traffic shape — e.g. a benchmark priming exactly one
        cell.  Returns the number of warmup dispatches.  Call after the
        state's ``refresh`` (and after kernel autotuning, which must
        precede the first trace to take effect)."""
        lane = self._lane(tenant)
        eng = lane.engine
        ctx = np.asarray(context_ids, np.int32).reshape(-1)
        w = (np.ones(ctx.shape, np.float32) if context_weights is None
             else np.asarray(context_weights, np.float32).reshape(-1))
        if s_counts is None:
            s_counts = [s for s in (2, 4, 8, 16, 32, 64)
                        if s <= self.pack_max]
        if batch_sizes is None:
            batch_sizes = []
            bq = 1
            while bq <= self.max_batch:
                batch_sizes.append(bq)
                bq *= 2
        if ks is None:
            ks = []
            k = 1
            while k <= min(next_pow2(self.max_k), eng.n_items):
                ks.append(k)
                k *= 2
        n = 0
        for S in s_counts:
            states = (eng,) * S
            for bq in batch_sizes:
                ids_b = np.broadcast_to(ctx, (S, bq, ctx.shape[0]))
                w_b = np.broadcast_to(w, (S, bq, w.shape[0]))
                for k in ks:
                    fused_topk(states, ids_b, k, w_b)
                    n += 1
                    if eng.use_pallas_kernel and not eng.kernel_degraded:
                        # warm the jnp fused fallback at the same shape:
                        # sticky kernel degradation must cost ZERO
                        # mid-serve traces when it fires (same contract
                        # as warmup_grid)
                        eng.runtime.multi_topk(
                            (eng.params,) * S, (eng.cache,) * S,
                            np.ascontiguousarray(ids_b),
                            np.ascontiguousarray(w_b).astype(
                                eng.runtime.wdtype), K=k)
                        n += 1
        return n

    # -- background pump + watchdog -----------------------------------------

    def start_pump(self, interval: float = 1e-3, *,
                   watchdog: float | None = None) -> None:
        """Run ``pump`` on a daemon thread every ``interval`` seconds —
        the idle tick that force-dispatches aged partial batches without
        a serving-loop caller.  With ``watchdog=t`` a second daemon
        thread monitors the pump heartbeat and, after ``t`` seconds of
        silence (a stalled hook, GC pause, hung I/O), orphans the stalled
        generation and starts a fresh pump thread
        (``stats["pump_restarts"]``); the stalled thread exits harmlessly
        when it wakes and finds its generation stale.  Idempotent while
        running."""
        with self._lock:
            if self._closed:
                raise Unservable("frontend is closed")
            if self._pump_run:
                return
            self._pump_run = True
            self._pump_interval = float(interval)
            self._watchdog_timeout = watchdog
            self._pump_gen += 1
            self._spawn_pump(self._pump_gen)
            if watchdog is not None:
                t = threading.Thread(target=self._watchdog_loop,
                                     daemon=True, name="frontend-watchdog")
                self._watchdog_thread = t
                t.start()

    def stop_pump(self) -> None:
        """Stop the background pump (and watchdog); joins briefly.  Safe
        when never started; queued work is NOT flushed (use ``drain``
        or ``close``)."""
        with self._lock:
            self._pump_run = False
            self._pump_gen += 1          # orphan any live generation
            threads = [self._pump_thread, self._watchdog_thread]
            self._pump_thread = self._watchdog_thread = None
        me = threading.current_thread()
        for t in threads:
            if t is not None and t is not me and t.is_alive():
                t.join(timeout=1.0)

    def _spawn_pump(self, gen: int) -> None:
        self._pump_beat = time.monotonic()
        t = threading.Thread(target=self._pump_loop, args=(gen,),
                             daemon=True, name=f"frontend-pump-{gen}")
        self._pump_thread = t
        t.start()

    def _pump_loop(self, gen: int) -> None:
        while True:
            with self._lock:
                if not self._pump_run or gen != self._pump_gen:
                    return               # stopped, or watchdog moved on
            self._pump_beat = time.monotonic()
            try:
                # the stall probe sits OUTSIDE the frontend lock: a
                # stalled (sleeping) pump must not block submits or the
                # watchdog that is about to replace it
                if self._injector is not None:
                    self._injector.check("pump")
                self.pump()
            except Exception as e:       # noqa: BLE001 — tick lost, loop on
                # a lost tick is survivable (the next tick force-
                # dispatches the same aged work) but never silent: the
                # error is counted and kept for health()/debugging
                self.stats["pump_errors"] += 1
                self.last_pump_error = e
            time.sleep(self._pump_interval)

    def _watchdog_loop(self) -> None:
        timeout = self._watchdog_timeout
        while True:
            time.sleep(timeout / 2)
            with self._lock:
                if not self._pump_run:
                    return
                if time.monotonic() - self._pump_beat >= timeout:
                    self._pump_gen += 1
                    self.stats["pump_restarts"] += 1
                    self._spawn_pump(self._pump_gen)

    # -- health + graceful shutdown -----------------------------------------

    def health(self) -> dict:
        """Readiness/health probe (cheap; safe to poll).

        Top level: ``ready`` (accepting submits), ``closed``, ``degraded``
        (any lane breaker not closed, any engine on its fallback kernel,
        or a recorded refresh failure), ``queue_depth``,
        ``inflight_depth``, ``pump`` (running / restarts),
        ``packing`` (fused-dispatch counters + mean group size), and
        ``stages``: per stage of ``repro.serving.telemetry``, the count
        and the p50 / p90 / p99 in seconds since the frontend started.  Per
        tenant: breaker state and consecutive-failure count, queue depth,
        live item count, model step, seconds since the last model
        refresh, the last refresh error (if any), and whether the engine
        degraded to the jnp reference kernel."""
        with self._lock:
            # refresh stamps are time.monotonic (engine-side), NOT the
            # injectable frontend clock — age them on the same basis
            now = time.monotonic()
            lanes = {}
            degraded = False
            for name, lane in self._lanes.items():
                eng = lane.engine
                rt = getattr(eng, "last_refresh_time", None)
                info = {
                    "breaker": lane.breaker,
                    "consecutive_failures": lane.fails,
                    "trips": lane.stats["trips"],
                    "weight": lane.weight,
                    "quota": lane.quota,
                    "quota_deferred": lane.stats["quota_deferred"],
                    "queued": len(lane.heap),
                    "n_items": eng.n_items,
                    "model_step": getattr(eng, "model_step", None),
                    "refresh_age": None if rt is None else now - rt,
                    "last_refresh_error":
                        getattr(eng, "last_refresh_error", None),
                    "kernel_degraded":
                        bool(getattr(eng, "kernel_degraded", False)),
                }
                if (info["breaker"] != "closed" or info["kernel_degraded"]
                        or info["last_refresh_error"] is not None):
                    degraded = True
                lanes[name] = info
            pump = self._pump_thread
            fused = self.stats["fused_dispatches"]
            return {
                "ready": not self._closed,
                "closed": self._closed,
                "degraded": degraded,
                "queue_depth": self.queue_depth,
                "inflight_depth": len(self._window),
                "pump": {"running": pump is not None and pump.is_alive(),
                         "restarts": self.stats["pump_restarts"]},
                "packing": {
                    "enabled": self.pack,
                    "pack_max": self.pack_max,
                    "fused_dispatches": fused,
                    "fused_segments": self.stats["fused_segments"],
                    "mean_group":
                        self.stats["fused_segments"] / fused if fused
                        else 0.0,
                },
                "tenants": lanes,
                "stages": self.telemetry.summary(),
            }

    def close(self) -> None:
        """Graceful shutdown: stop the pump/watchdog threads, resolve
        every in-flight batch to its REAL result, fail every still-queued
        request with ``Unservable`` (typed, never silently dropped), and
        detach every tenant's writer barrier.  Subsequent submits raise
        ``Unservable``; idempotent."""
        self.stop_pump()
        with self._lock:
            if self._closed:
                return
            self._closed = True
            now = self.clock()
            for lane in self._lanes.values():
                while lane.heap:
                    _, _, req = heapq.heappop(lane.heap)
                    req._taken = True
                    self.stats["failed"] += 1
                    lane.stats["failed"] += 1
                    req._fail(Unservable(
                        "frontend closed with request still queued",
                        tenant=lane.name), now)
                lane.arrivals.clear()
            while self._window:
                self._resolve_oldest()
            for lane in self._lanes.values():
                lane.engine.on_mutate = None

    # -- convenience --------------------------------------------------------

    @property
    def queue_depth(self) -> int:
        """Total queued requests across every tenant lane."""
        return sum(len(lane.heap) for lane in self._lanes.values())

    @property
    def inflight_depth(self) -> int:
        return len(self._window)

    @property
    def occupancy(self) -> float:
        """Real-request fraction of dispatched micro-batch rows (1.0 =
        every dispatched row was a live query, no bucket padding)."""
        rows = self.stats["dispatched_rows"]
        return 1.0 if rows == 0 else 1.0 - self.stats["padded_rows"] / rows
