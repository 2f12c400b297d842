"""Corpus-precomputation serving subsystem for DPLR-FwFM.

Extends the paper's context-side caching (Algorithm 1) to the item side:
rank-space projections of the candidate corpus are precomputed once and
every query costs O(rho k) per item.  The stack is three layers — shared
compute, per-tenant state, shared request routing:

  * ``ScorerRuntime`` (SHARED) owns everything corpus-independent: the
    jitted/Pallas dispatch, mesh/``shard_map`` wiring, kernel selection,
    and the trace cache.  Keyed purely by shape+dtype, so T tenants
    share one runtime and a new tenant with an already-warm shape
    signature comes online with zero retraces.
  * ``CorpusState`` (PER TENANT) is the mutable corpus: a capacity-padded
    slab with a validity mask, free-lists, the params snapshot, and the
    tenant's ``on_mutate`` writer barrier.  Catalog churn is absorbed by
    O(Δn rho k) in-place row writes (shard-grouped when meshed) — no
    rebuilds, no shape changes, zero retraces — and a model refresh
    rebuilds the slab in place with slot assignments preserved.  With a
    meshed runtime the slab shards across the ``model`` axis: D devices
    each hold capacity/D slots and top-K merges D device-local top-Ks
    with O(D·K) traffic.  ``CorpusRankingEngine`` (the historical
    single-tenant name) is an alias: one CorpusState over a private
    runtime.
  * ``QueryFrontend`` (SHARED) is the online request path: per-tenant
    EDF queues coalescing into power-of-two padded micro-batches,
    weighted (SWRR + QPS-quota) fairness across tenants into one
    double-buffered
    in-flight window (host assembly overlaps device scoring), admission
    control that sheds with ``Overloaded`` instead of queueing doomed
    requests, and a per-tenant writer barrier — tenant-A churn never
    drains tenant-B's in-flight reads.

    corpus.py   - the serving contract a model supplies (slab rows, query
                  state, score function; ``scorer_for`` picks it from
                  the config): DPLRScorer (ItemCorpusCache +
                  build_corpus_cache + corpus_rows + masked_slab_scores,
                  slab/mask invariants documented here) and MINDScorer
                  (MINDSlab, the user tower, max-over-interests scores)
    runtime.py  - ScorerRuntime (shared jitted dispatch + trace cache,
                  warmup grid, host-side churn bucketing/grouping)
    engine.py   - CorpusState / CorpusRankingEngine (per-tenant slab,
                  masked scoring, fused top-K, add/remove/update_items,
                  slab doubling, checkpoint-refresh invalidation)
    sharded.py  - shard_map implementations of build/write/score/topk
                  (striped slot ownership, shard-grouped churn deltas,
                  bit-exact candidate merge)
    frontend.py - QueryFrontend (tenant routing, request coalescing,
                  bucketed Bq/K, EDF + weighted-SWRR dispatch with QPS
                  quotas, admission control, overlapped dispatch,
                  deadlines, per-tenant churn/read serialization,
                  retry/backoff + circuit breakers + pressure clamp +
                  occupancy autoscaling + pump watchdog + health)
    rpc.py      - RpcServer/RpcClient (asyncio length-prefixed binary
                  protocol over the frontend: typed error frames from
                  the ServingError taxonomy, per-connection
                  backpressure, graceful SIGTERM drain) — see
                  docs/network.md
    errors.py   - the typed ServingError hierarchy (one base, one
                  subclass per failure domain; FrontendError is a
                  compatibility alias of the base)
    faults.py   - FaultInjector (deterministic, seeded chaos: armable
                  fault sites threaded through the stack) — see
                  docs/robustness.md
"""
from repro.serving.corpus import (ItemCorpusCache, build_corpus_cache,
                                  corpus_rows, masked_slab_scores)
from repro.serving.engine import CorpusRankingEngine, CorpusState
from repro.serving.errors import (Degraded, DeadlineExceeded, DispatchFailed,
                                  FrontendError, NotReady, Overloaded,
                                  RefreshFailed, ServingError, Unservable)
from repro.serving.faults import FaultInjector, InjectedFault
from repro.serving.frontend import PendingQuery, QueryFrontend
from repro.serving.rpc import (RpcClient, RpcDisconnected,
                               RpcProtocolError, RpcServer,
                               serve_in_thread)
from repro.serving.runtime import ScorerRuntime
from repro.serving.sanitize import (assert_no_retrace, check_scores,
                                    sanitize_enabled, scoring_guard)

__all__ = ["ItemCorpusCache", "build_corpus_cache", "corpus_rows",
           "masked_slab_scores", "ScorerRuntime", "CorpusState",
           "CorpusRankingEngine", "QueryFrontend", "PendingQuery",
           "ServingError", "Overloaded", "DeadlineExceeded", "Unservable",
           "DispatchFailed", "RefreshFailed", "Degraded", "NotReady",
           "FrontendError", "FaultInjector", "InjectedFault",
           "RpcServer", "RpcClient", "RpcProtocolError", "RpcDisconnected",
           "serve_in_thread",
           "assert_no_retrace", "check_scores", "sanitize_enabled",
           "scoring_guard"]
