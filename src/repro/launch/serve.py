"""Serving driver: the paper's deployment — a ranking service answering
"score these N candidates for this context" queries.

    PYTHONPATH=src python -m repro.launch.serve --arch dplr-fwfm \
        [--engine corpus|percall] [--items 512] [--queries 100] \
        [--topk 10] [--mp] [--bf16]

Serving engine
--------------
The default ``--engine corpus`` path serves through
``repro.serving.CorpusRankingEngine``: the item side (``Q_I = U_I V_I``,
``t_I``, ``lin_I``) is context-independent, so it is precomputed ONCE per
(corpus, model) — per-row deltas absorb catalog churn — and each query
costs

    O(rho m_C k)            context cache (once per query)
    O(rho k) per item       combine with the precomputed Q_I

versus Algorithm 1's per-query O(rho m_I k + m_I k) per item (``--engine
percall``, kept as the baseline: it re-gathers and re-projects every
candidate on every query).  With ``--topk K`` only the (Bq, K) winners
leave the scorer instead of (Bq, n) logits.

Model refresh: with ``--ckpt-dir`` the engine polls the CheckpointManager
every ``--refresh-every`` queries and, when a newer step lands (the
sliding-window retrain mode of Section 5.3), rebuilds the corpus cache
WITHOUT retracing the jitted scorer — ``--refresh-demo`` exercises the
round-trip in-process by writing a perturbed checkpoint mid-stream.

Catalog churn: the corpus is a capacity-padded mutable slab
(``--capacity``), so items can be added/removed/updated between queries
with O(Δn rho k) in-place writes.  ``--churn-demo`` interleaves
``--churn-ops`` add/remove/update/score operations on a live engine and
asserts the jitted scorer NEVER retraces (the recompilation stall the slab
design removes) and that masked top-K never surfaces a dead slot.

Online frontend: ``--frontend`` replays a Poisson arrival trace (rate
``--arrival-rate``, 0 = auto-calibrated to ~2x the sync per-query
capacity) of single-query requests with mixed per-query K through
``repro.serving.QueryFrontend`` — power-of-two micro-batch coalescing
(``--fe-batch``, ``--max-wait-ms``) with a depth-``--inflight`` window of
overlapped async dispatches — AND through sync per-query serving, then
prints p50/p95/p99 latency + QPS for both.  Asserts zero scorer retraces
across the mixed workload (including mid-stream churn bursts through the
writer barrier) and bit-exact reply parity vs one-by-one engine calls.
Composes with ``--mesh``: the same trace runs against the sharded engine.

Multi-tenant serving: ``--tenant-demo`` stands up ``--tenants`` N
per-tenant corpora (one ``CorpusState`` each — the paper's
many-corpora-behind-one-model ad deployment) on ONE shared
``ScorerRuntime`` and routes mixed tenant traffic through the
tenant-routed ``QueryFrontend``.  Asserts the tentpole invariants: after
warming ONE tenant's (Bq, K) grid, every other tenant serves with ZERO
retraces (shared trace cache); replies are bit-exact vs a dedicated
single-tenant engine; churn bursts on tenant 0 never drain other
tenants' in-flight reads (per-tenant writer barrier); and a 5x
admission-control burst sheds with fast ``Overloaded`` replies while
every accepted request is served.  Composes with ``--mesh`` (the tenant
slabs all shard over the same mesh) and ``--use-pallas``.

Sharded corpus: ``--mesh host`` shards the slab over every local device's
model axis (CI runs this under
``XLA_FLAGS=--xla_force_host_platform_device_count=4``), so corpus
capacity scales with the device count; churn deltas route to their owning
shard and top-K merges the device-local winners (bit-exact vs unsharded).
``--mesh prod`` / ``--mesh prod-mp`` build the production (16, 16) /
(2, 16, 16) mesh shapes — usable under a dry-run-style forced device
count.  All other flags compose: churn/refresh demos, --topk,
--use-pallas all run sharded.

Self-healing: ``--chaos-demo`` runs a scripted fault storm through the
frontend's recovery machinery — a transient dispatch fault retried
bit-exactly (the SAME assembled batch re-dispatches), a sustained outage
tripping the per-tenant circuit breaker (fast ``Degraded`` shedding,
half-open probe, close), a corrupt model push rejected with a typed
``RefreshFailed`` while the last-good snapshot keeps serving, a failed
churn write that leaves the corpus untouched, a stalled background pump
restarted by its watchdog, and a seeded random fault storm in which
every request resolves with a result or a typed error.  Asserts zero
scorer retraces across ALL recovery paths.  Composes with ``--mesh``
and ``--use-pallas`` (which adds the sticky kernel->jnp fallback leg).

Network serving: ``--rpc`` puts the multi-tenant frontend behind the
length-prefixed binary RPC protocol (``repro.serving.rpc``, spec in
docs/network.md) on a real TCP socket (``--port``, 0 = ephemeral) and
replays a mixed-tenant pipelined client trace against it.  The frontend
runs with ``auto_pump=False`` — the server's event loop owns the pump —
and the demo asserts the wire contract: every socket reply is BIT-EXACT
vs direct in-process ``QueryFrontend`` submission, protocol and serving
errors come back as typed error frames that reconstruct the
``ServingError`` taxonomy client-side, zero scorer retraces across the
replay, and ``server.stop()`` drains gracefully.

``--mp`` switches to the model-parallel DPLR scorer (EXPERIMENTS.md §Perf
cell 3) — on this 1-device container it exercises the same shard_map code
path the production mesh runs; ``--bf16`` serves bf16 tables.
"""
from __future__ import annotations

import argparse
import time

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.checkpoint import CheckpointManager
from repro.configs import REGISTRY
from repro.data.synthetic_ctr import SyntheticCTR
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh, make_production_mesh
from repro.models.recsys import fwfm
from repro.serving import (CorpusRankingEngine, RefreshFailed,
                           assert_no_retrace)


def _corpus_mesh(kind: str):
    """Mesh carrying the corpus slab.  ``host`` spans every local device
    (1 on a plain CPU run; N under a forced host-platform device count);
    ``prod``/``prod-mp`` are the production shapes from launch/mesh.py and
    need the matching (dry-run-forced) device count."""
    if kind == "none":
        return None
    if kind == "host":
        return make_host_mesh(model=jax.device_count())
    return make_production_mesh(multi_pod=(kind == "prod-mp"))


def _report(tag: str, lat: np.ndarray, queries: int, items: int) -> None:
    if lat.size == 0:   # fewer queries than the 2 warmup/compile drops
        print(f"{queries} queries x {items} items ({tag}): "
              f"too few queries for latency percentiles")
        return
    print(f"{queries} queries x {items} items ({tag}): "
          f"avg {lat.mean():.2f} ms  P95 {np.percentile(lat, 95):.2f} ms  "
          f"P99 {np.percentile(lat, 99):.2f} ms")


def _frontend_demo(args, engine, data) -> None:
    """Drive a Poisson arrival trace through the micro-batching frontend
    and through sync per-query serving, and compare latency percentiles
    and throughput.  Asserts the frontend's contract on the way: zero
    scorer retraces after warmup (mixed Bq AND mixed K), every reply's
    slots live at reply time, and bit-exact parity with a one-by-one
    engine call for a sample of requests."""
    from repro.serving import QueryFrontend
    from repro.serving.corpus import next_pow2

    rng = np.random.default_rng(args.seed)
    max_k = max(args.topk or 10, 1)
    fe = QueryFrontend(engine, max_batch=args.fe_batch, max_k=max_k,
                       max_wait=args.max_wait_ms * 1e-3,
                       inflight=args.inflight)
    ctx0 = data.context_query(0)["context_ids"]
    fe.warmup(ctx0)
    traced = engine.trace_count

    # the zero-retrace block closes BEFORE the parity calls below, which
    # use exact (unbucketed) Ks on purpose and add baseline traces
    with assert_no_retrace(engine, label="frontend coalesced run"):
        # sync per-query service time -> auto arrival rate (~2x sync
        # capacity, where coalescing visibly wins and sync visibly queues)
        k_bucket = next_pow2(max_k)
        for _ in range(3):
            jax.block_until_ready(engine.topk(ctx0, k_bucket)[0])
        t0 = time.perf_counter()
        for _ in range(10):
            jax.block_until_ready(engine.topk(ctx0, k_bucket)[0])
        s1 = (time.perf_counter() - t0) / 10
        rate = args.arrival_rate or 2.0 / s1

        # one fixed trace served by both paths: Poisson arrivals, mixed K,
        # a small update-churn burst every 25 requests (through the
        # ENGINE, to exercise the on_mutate writer barrier mid-stream)
        n = args.queries
        arrivals = np.cumsum(rng.exponential(1.0 / rate, n))
        ks = rng.integers(1, max_k + 1, n)
        ctxs = [data.context_query(s)["context_ids"] for s in range(n)]
        churn_at = set(range(25, n, 25))

        def churn(s):
            upd = data.ranking_query(2, 50_000 + s)
            fe_slots = rng.choice(engine.valid_slots, 2, replace=False)
            engine.update_items(fe_slots, upd["item_ids"][0],
                                upd["item_weights"][0])

        # warm the churn path too (row-compute + scatter trace once), so
        # the first timed run doesn't pay compilation the second run gets
        # for free
        churn(-1)

        # -- coalesced (frontend) ------------------------------------------
        pend = []
        t0 = time.perf_counter()
        for s in range(n):
            now = time.perf_counter() - t0
            if arrivals[s] > now:
                time.sleep(arrivals[s] - now)
            if s in churn_at:
                churn(s)
            pend.append(fe.submit(ctxs[s], k=int(ks[s])))
        fe.drain()
        end = time.perf_counter() - t0
        # completion minus SCHEDULED arrival — symmetric with the sync
        # loop below, and charges any submit-loop backlog as queueing
        lat_fe = np.asarray([(p.done_time - t0 - arrivals[s]) * 1e3
                             for s, p in enumerate(pend)])
        qps_fe = n / max(end, 1e-9)
    for s in range(n):
        assert engine.is_live(pend[s].result()[1]).all(), \
            "frontend surfaced a dead slot"
    # bit-exact parity vs a fresh one-by-one call is checkable for the
    # requests scored against the FINAL corpus state, i.e. those
    # submitted after the last churn burst (earlier replies were
    # correctly computed on the pre-churn snapshot their batch saw)
    for s in range((max(churn_at) + 1) if churn_at else 0, n):
        sc, sl = pend[s].result()
        wv, wi = engine.topk(np.asarray(ctxs[s]).reshape(1, -1), int(ks[s]))
        assert np.array_equal(sc, np.asarray(wv)[0]) and \
            np.array_equal(sl, np.asarray(wi)[0]), \
            "coalesced reply != one-by-one engine call (must be bit-exact)"

    # -- sync per-query baseline (same trace, no coalescing) ---------------
    lat_sync = np.empty(n)
    t0 = time.perf_counter()
    for s in range(n):
        now = time.perf_counter() - t0
        if arrivals[s] > now:
            time.sleep(arrivals[s] - now)
        if s in churn_at:
            churn(s)
        jax.block_until_ready(
            engine.topk(ctxs[s], int(next_pow2(int(ks[s]))))[0])
        lat_sync[s] = (time.perf_counter() - t0 - arrivals[s]) * 1e3
    qps_sync = n / max(time.perf_counter() - t0, 1e-9)

    def pct(a):
        return (np.percentile(a, 50), np.percentile(a, 95),
                np.percentile(a, 99))

    print(f"frontend demo: {n} requests, Poisson {rate:.0f} qps, "
          f"K in 1..{max_k}, bucket<= {args.fe_batch}, "
          f"max-wait {args.max_wait_ms:.1f} ms, inflight {args.inflight}, "
          f"{len(churn_at)} churn bursts")
    p50, p95, p99 = pct(lat_fe)
    print(f"  coalesced : p50 {p50:7.2f}  p95 {p95:7.2f}  "
          f"p99 {p99:7.2f} ms   {qps_fe:7.0f} qps   "
          f"occupancy {fe.occupancy:.2f} "
          f"({fe.stats['dispatches']} dispatches)")
    p50, p95, p99 = pct(lat_sync)
    print(f"  sync      : p50 {p50:7.2f}  p95 {p95:7.2f}  "
          f"p99 {p99:7.2f} ms   {qps_sync:7.0f} qps")
    print(f"  zero-retrace OK ({traced} traces, incl. warmup), replies "
          f"bit-exact vs one-by-one, all returned slots live")


def _tenant_demo(args, cfg, params, data) -> None:
    """Serve ``--tenants`` N corpora behind ONE ScorerRuntime through the
    tenant-routed frontend, and assert the multi-tenant contract: zero
    retraces after one tenant warms the grid, bit-exact per-tenant
    replies, churn isolation, and fast admission-control shedding."""
    from repro.serving import (CorpusRankingEngine, CorpusState, Overloaded,
                               QueryFrontend, ScorerRuntime)
    from repro.serving.corpus import next_pow2

    rng = np.random.default_rng(args.seed)
    T = max(args.tenants, 2)
    corpus_mesh = _corpus_mesh(args.mesh)
    n_shards = 1 if corpus_mesh is None else int(corpus_mesh.shape["model"])
    runtime = ScorerRuntime(cfg, mesh=corpus_mesh,
                            use_pallas_kernel=args.use_pallas)
    capacity = max(args.capacity or next_pow2(2 * args.items), n_shards)
    names = [f"t{i}" for i in range(T)]
    corpora = {n: data.ranking_query(args.items, 1000 + i)
               for i, n in enumerate(names)}
    states = {}
    for name in names:
        c = corpora[name]
        states[name] = CorpusState(cfg, c["item_ids"][0],
                                   c["item_weights"][0],
                                   capacity=capacity, runtime=runtime)
        states[name].refresh(params, step=0)
    max_k = max(args.topk or 10, 1)
    fe = QueryFrontend(states, max_batch=args.fe_batch, max_k=max_k,
                       max_wait=args.max_wait_ms * 1e-3,
                       inflight=args.inflight)

    # ONE tenant warms the (Bq x K) grid; the shared runtime makes every
    # same-capacity tenant warm with it — the zero-retrace onboarding aha
    warm_dispatches = fe.warmup(data.context_query(0)["context_ids"],
                                tenant="t0")
    traced = runtime.trace_count

    n = args.queries
    ctxs = [data.context_query(s)["context_ids"] for s in range(n)]
    ks = rng.integers(1, max_k + 1, n)
    lanes = [names[int(rng.integers(T))] for _ in range(n)]
    churn_at = set(range(10, n, 20))         # churn bursts, tenant t0 only
    pend = []
    t0 = time.perf_counter()
    last_churn = -1
    # mixed-tenant traffic + t0 churn must add ZERO traces to the shared
    # runtime — the cross-tenant isolation contract
    with assert_no_retrace(runtime, label="mixed-tenant traffic"):
        for s in range(n):
            if s in churn_at:
                upd = data.ranking_query(2, 50_000 + s)
                fe.update_items(
                    rng.choice(states["t0"].valid_slots, 2, replace=False),
                    upd["item_ids"][0], upd["item_weights"][0], tenant="t0")
                last_churn = s
            pend.append(fe.submit(ctxs[s], k=int(ks[s]), tenant=lanes[s]))
        fe.drain()
        wall = time.perf_counter() - t0
    # every reply live at delivery; bit-exact vs the tenant's own state
    # for requests scored against its FINAL corpus (non-t0 tenants never
    # churned, t0 after its last burst)
    checked = 0
    for s, p in enumerate(pend):
        sc, sl = p.result()
        assert states[lanes[s]].is_live(sl).all(), \
            f"tenant {lanes[s]} reply surfaced a dead slot"
        if lanes[s] != "t0" or s > last_churn:
            wv, wi = states[lanes[s]].topk(
                np.asarray(ctxs[s]).reshape(1, -1), int(ks[s]))
            assert np.array_equal(sc, np.asarray(wv)[0]) and \
                np.array_equal(sl, np.asarray(wi)[0]), \
                "tenant reply != one-by-one state call (must be bit-exact)"
            checked += 1
    # cross-checking one tenant against a DEDICATED single-tenant engine
    # proves sharing the runtime changed nothing
    c = corpora["t1"]
    dedicated = CorpusRankingEngine(cfg, c["item_ids"][0],
                                    c["item_weights"][0],
                                    capacity=capacity, mesh=corpus_mesh,
                                    use_pallas_kernel=args.use_pallas)
    dedicated.refresh(params, step=0)
    for s in range(0, n, max(n // 8, 1)):
        gv, gi = states["t1"].topk(np.asarray(ctxs[s]).reshape(1, -1),
                                   max_k)
        wv, wi = dedicated.topk(np.asarray(ctxs[s]).reshape(1, -1), max_k)
        assert np.array_equal(np.asarray(gv), np.asarray(wv)) and \
            np.array_equal(np.asarray(gi), np.asarray(wi)), \
            "shared-runtime tenant != dedicated engine (must be bit-exact)"

    # admission control under a 5x burst: bounded queue, fast sheds, and
    # every ACCEPTED request still answered
    fe.auto_pump = False
    fe.admit_depth = max(args.fe_batch, 4)
    sheds = accepted = 0
    for s in range(5 * fe.admit_depth):
        try:
            fe.submit(ctxs[s % n], k=int(ks[s % n]), tenant="t1")
            accepted += 1
        except Overloaded:
            sheds += 1
    fe.drain()
    assert accepted == fe.admit_depth and sheds == 4 * fe.admit_depth, \
        f"admission control off: {accepted} accepted, {sheds} shed"
    assert fe.stats["expired"] == 0
    fe.auto_pump, fe.admit_depth = True, None

    lat = np.asarray([(p.done_time - p.submit_time) * 1e3 for p in pend])
    per_tenant = {t: fe.lane_stats(t)["completed"] for t in names}
    print(f"tenant demo: {T} tenants x {args.items} items "
          f"(capacity {capacity}"
          f"{f', {n_shards} shards' if n_shards > 1 else ''}) on ONE "
          f"ScorerRuntime; {n} mixed requests in {wall * 1e3:.0f} ms, "
          f"{len(churn_at)} t0 churn bursts")
    print(f"  traces    : {traced} total ({warm_dispatches} grid warmup "
          f"dispatches on t0 alone) — 0 added by {T - 1} more tenants + "
          f"traffic")
    print(f"  replies   : p50 {np.percentile(lat, 50):.2f}  "
          f"p95 {np.percentile(lat, 95):.2f} ms; {checked} checked "
          f"bit-exact (incl. vs a dedicated engine); per-tenant "
          f"{per_tenant}")
    print(f"  admission : 5x burst -> {accepted} accepted / {sheds} shed "
          f"fast (Overloaded), 0 deadline expiries")


def _rpc_demo(args, cfg, params, data) -> None:
    """Serve ``--tenants`` corpora over the binary RPC protocol on a real
    socket and replay a pipelined mixed-tenant client trace, asserting
    the wire contract: socket replies bit-exact vs direct frontend
    submission, typed error frames, zero retraces, graceful drain."""
    from repro.serving import (CorpusState, DeadlineExceeded, QueryFrontend,
                               RpcClient, ScorerRuntime, serve_in_thread)
    from repro.serving.corpus import next_pow2

    rng = np.random.default_rng(args.seed)
    T = max(args.tenants, 2)
    corpus_mesh = _corpus_mesh(args.mesh)
    n_shards = 1 if corpus_mesh is None else int(corpus_mesh.shape["model"])
    runtime = ScorerRuntime(cfg, mesh=corpus_mesh,
                            use_pallas_kernel=args.use_pallas)
    capacity = max(args.capacity or next_pow2(2 * args.items), n_shards)
    names = [f"t{i}" for i in range(T)]
    states = {}
    for i, name in enumerate(names):
        c = data.ranking_query(args.items, 1000 + i)
        states[name] = CorpusState(cfg, c["item_ids"][0],
                                   c["item_weights"][0],
                                   capacity=capacity, runtime=runtime)
        states[name].refresh(params, step=0)
    max_k = max(args.topk or 10, 1)
    # auto_pump=False: the RPC server's event loop owns pump/resolve
    fe = QueryFrontend(states, max_batch=args.fe_batch, max_k=max_k,
                       max_wait=args.max_wait_ms * 1e-3,
                       inflight=args.inflight, auto_pump=False)
    fe.warmup(data.context_query(0)["context_ids"], tenant="t0")
    traced = runtime.trace_count

    server = serve_in_thread(fe, port=args.port)
    print(f"rpc: {T} tenants x {args.items} items (capacity {capacity}"
          f"{f', {n_shards} shards' if n_shards > 1 else ''}) on ONE "
          f"ScorerRuntime, listening on 127.0.0.1:{server.port}")

    n = args.queries
    ctxs = [data.context_query(s)["context_ids"] for s in range(n)]
    ks = rng.integers(1, max_k + 1, n)
    lanes = [names[int(rng.integers(T))] for _ in range(n)]
    window = 16                       # pipelined in-flight frames per burst
    lat, replies = [], {}
    with assert_no_retrace(runtime, label="rpc traffic"):
        with RpcClient("127.0.0.1", server.port) as cli:
            t_start = time.perf_counter()
            sent = []
            for s in range(n):
                sent.append((s, cli.send_rank(ctxs[s], k=int(ks[s]),
                                              tenant=lanes[s]),
                             time.perf_counter()))
                if len(sent) >= window or s == n - 1:
                    for si, rid, ti in sent:
                        reply = cli.recv_for(rid)
                        reply.raise_for_status()
                        replies[si] = reply
                        lat.append((time.perf_counter() - ti) * 1e3)
                    sent = []
            wall = time.perf_counter() - t_start

            # typed error frames reconstruct the taxonomy client-side
            bad_k = cli.recv_for(cli.send_rank(ctxs[0], k=max_k + 90,
                                               tenant="t0"))
            assert isinstance(bad_k.error, ValueError), bad_k.error
            expired = cli.recv_for(cli.send_rank(ctxs[0], k=1, tenant="t0",
                                                 deadline_rel=1e-9))
            assert isinstance(expired.error, DeadlineExceeded), expired.error
            assert expired.error.tenant == "t0"

        # socket replies must be BIT-EXACT vs direct frontend submission
        # (the server keeps pumping; submit() from here rides its ticks)
        check = list(range(0, n, max(n // 16, 1)))
        pend = [(s, fe.submit(ctxs[s], k=int(ks[s]), tenant=lanes[s]))
                for s in check]
        for s, p in pend:
            sc, sl = p.result()
            assert np.array_equal(replies[s].scores, np.asarray(sc)) and \
                np.array_equal(replies[s].slots, np.asarray(sl)), \
                f"socket reply {s} != direct frontend submission"

    server.stop()                     # graceful drain, then loop teardown
    st = server.stats
    lat_a = np.asarray(lat)
    print(f"  traces    : {traced} total — 0 added by {n} socket requests "
          f"across {T} tenants")
    print(f"  replies   : p50 {np.percentile(lat_a, 50):.2f}  "
          f"p95 {np.percentile(lat_a, 95):.2f}  "
          f"p99 {np.percentile(lat_a, 99):.2f} ms over the wire "
          f"({n / wall:.0f} rps pipelined x{window}); {len(check)} checked "
          f"bit-exact vs in-process submission")
    print(f"  wire      : {st['requests']} requests, {st['replies']} ok, "
          f"{st['errors']} typed error frames, "
          f"{st['protocol_errors']} protocol errors; graceful drain ok")
    fe.close()


def _churn_demo(args, engine, data) -> None:
    """Interleave add/remove/update/score on the LIVE engine and prove the
    slab absorbs arbitrary catalog churn with zero scorer retraces."""
    rng = np.random.default_rng(args.seed)
    K = args.topk or 10

    def one_score(s):
        q = data.context_query(s)
        ctx = jnp.asarray(q["context_ids"])
        ctx_w = jnp.asarray(q["context_weights"])
        t0 = time.perf_counter()
        vals, idx = jax.block_until_ready(engine.topk(ctx, K, ctx_w))
        dt = (time.perf_counter() - t0) * 1e3
        idx = np.asarray(idx).ravel()
        assert engine.is_live(idx).all(), \
            f"masked top-K surfaced a dead slot: {idx}"
        return dt

    # warmup: trace the scorer once for the slab capacity
    one_score(0)
    traced, cap0 = engine.trace_count, engine.capacity
    lat, counts = [], {"add": 0, "remove": 0, "update": 0, "score": 0}
    with assert_no_retrace(engine, label="catalog churn"):
        for s in range(args.churn_ops):
            kind = ("score" if s % 2 else
                    rng.choice(["add", "remove", "update"]))
            live = engine.valid_slots
            if kind == "add":
                dn = int(rng.integers(1, 9))
                if engine.n_items + dn > engine.capacity:
                    kind = "remove"  # stay inside the slab: no mid-demo grow
                else:
                    fresh = data.ranking_query(dn, 10_000 + s)
                    engine.add_items(fresh["item_ids"][0],
                                     fresh["item_weights"][0])
            if kind == "remove":
                dn = int(rng.integers(1, 9))
                if engine.n_items - dn < max(K, args.items // 2):
                    kind = "update"  # keep enough live items for top-K
                else:
                    engine.remove_items(rng.choice(live, dn, replace=False))
            if kind == "update":
                dn = int(rng.integers(1, 9))
                fresh = data.ranking_query(dn, 20_000 + s)
                engine.update_items(rng.choice(live, dn, replace=False),
                                    fresh["item_ids"][0],
                                    fresh["item_weights"][0])
            if kind == "score":
                lat.append(one_score(s))
            counts[kind] += 1
        jax.block_until_ready(engine.cache.Q_I)

    assert engine.capacity == cap0, "slab doubled mid-demo"
    print(f"churn demo: {args.churn_ops} interleaved ops "
          f"({counts['add']} add / {counts['remove']} remove / "
          f"{counts['update']} update / {counts['score']} score), "
          f"{engine.n_items}/{engine.capacity} live slots at exit")
    _report(f"churn, top{K}", np.asarray(lat), counts["score"], args.items)
    print(f"zero-retrace OK: scorer traced {traced}x during warmup, "
          f"{engine.trace_count}x after {args.churn_ops} churn ops")


def _chaos_demo(args, engine, data, params) -> None:
    """Scripted fault storm against the self-healing serving stack: a
    transient dispatch fault retried bit-exactly, a retry-exhaustion
    outage that trips the per-tenant circuit breaker (fast ``Degraded``
    shedding, half-open probe, close), a corrupt model push rejected
    typed while the last-good snapshot keeps serving (then a good push
    installing cleanly), a failed churn write that leaves the corpus
    untouched, a stalled pump restarted by the watchdog, and a seeded
    random fault storm where every request still resolves.  Asserts
    bit-exact replies on every success and ZERO scorer retraces across
    all recovery paths."""
    import tempfile

    from repro.serving import (Degraded, DispatchFailed, FaultInjector,
                               QueryFrontend, RefreshFailed, ServingError)
    from repro.serving.corpus import next_pow2

    inj = FaultInjector(seed=args.seed)
    engine.fault_injector = inj
    fe = QueryFrontend(engine, max_batch=8, max_k=16,
                       max_wait=args.max_wait_ms * 1e-3,
                       retries=2, retry_backoff=1e-4,
                       breaker_threshold=2, breaker_cooldown=0.05,
                       fault_injector=inj)
    ctx0 = data.context_query(0)["context_ids"]
    fe.warmup(ctx0)
    traced = engine.trace_count
    fe.start_pump(interval=1e-3, watchdog=0.25)

    k = 8
    ctxs = [data.context_query(s)["context_ids"] for s in range(8)]
    oracle = [tuple(np.asarray(a) for a in
                    engine.topk(np.asarray(c).reshape(1, -1), k))
              for c in ctxs]

    def serve(s):
        got_v, got_i = fe.submit(ctxs[s], k=k).result()
        ov, oi = oracle[s]
        assert np.array_equal(got_v, ov[0]) \
            and np.array_equal(got_i, oi[0]), \
            f"reply {s} not bit-exact vs the fault-free oracle"

    # 1. transient dispatch fault: bounded retry re-dispatches the SAME
    #    assembled batch, so the reply is bit-exact — not re-queued
    inj.arm("dispatch", count=1)
    serve(0)
    assert fe.stats["retries"] >= 1
    print(f"chaos 1: transient dispatch fault retried "
          f"({fe.stats['retries']} retry), reply bit-exact")

    # 2. sustained outage: two exhausted retry budgets trip the breaker;
    #    an open breaker sheds SUBMITS fast; the half-open probe closes it
    for i in (1, 2):
        inj.arm("dispatch", count=fe.retries + 1)
        try:
            fe.submit(ctxs[i], k=k).result()
            raise AssertionError("outage dispatch unexpectedly succeeded")
        except DispatchFailed:
            pass
    try:
        fe.submit(ctxs[3], k=k)
        raise AssertionError("open breaker accepted a submit")
    except Degraded:
        print("chaos 2: breaker OPEN after 2 exhausted retry budgets -> "
              "fast Degraded shed")
    time.sleep(fe.breaker_cooldown)
    serve(3)
    print("chaos 2: half-open probe served -> breaker CLOSED, "
          "reply bit-exact")

    # 3. corrupt model push: rejected typed ONCE, last-good keeps
    #    serving; a good push at the next step installs cleanly
    def to_ckpt(tree):
        return jax.tree.map(
            lambda a: np.asarray(a, np.float32)
            if jnp.asarray(a).dtype == jnp.bfloat16 else np.asarray(a),
            tree)

    def to_serving(tree):
        if not args.bf16:
            return tree["params"]
        return jax.tree.map(
            lambda a: jnp.asarray(a).astype(jnp.bfloat16)
            if jnp.asarray(a).dtype == jnp.float32 else jnp.asarray(a),
            tree["params"])

    mgr = CheckpointManager(tempfile.mkdtemp(prefix="serve_chaos_"))
    step0 = engine.model_step          # None when serving unversioned
    push = (step0 or 0) + 1
    mgr.save({"params": to_ckpt(params)}, step=push, blocking=True)
    inj.corrupt_checkpoint(mgr.directory)
    try:
        fe.maybe_refresh(mgr, {"params": to_ckpt(params)},
                         select=to_serving)
        raise AssertionError("corrupt push was not rejected")
    except RefreshFailed as e:
        assert engine.model_step == step0
        print(f"chaos 3: corrupt push REJECTED typed ({e}); still "
              f"serving step {step0}")
    serve(4)
    mgr.save({"params": to_ckpt(params)}, step=push + 1, blocking=True)
    assert fe.maybe_refresh(mgr, {"params": to_ckpt(params)},
                            select=to_serving)
    print(f"chaos 3: good push installed (step {engine.model_step}), "
          f"replies bit-exact throughout")

    # 4. failed churn write: device write faults BEFORE any host state
    #    moves, so the corpus stays exactly as it was
    upd = data.ranking_query(2, 70_000)
    inj.arm("write", count=1)
    landed = True
    try:
        fe.update_items(engine.valid_slots[:2], upd["item_ids"][0],
                        upd["item_weights"][0])
    except Exception:            # InjectedFault from the armed site
        landed = False
    assert not landed, "faulted churn write unexpectedly landed"
    serve(5)
    print("chaos 4: churn write faulted mid-flight -> corpus untouched, "
          "reply bit-exact")

    # 5. stalled pump: the watchdog orphans the silent generation and
    #    restarts; queued work drains on the fresh thread
    inj.arm("pump", count=1, delay=0.6)
    p = fe.submit(ctxs[6], k=k)
    deadline = time.perf_counter() + 10.0
    while fe.stats["pump_restarts"] < 1 and time.perf_counter() < deadline:
        time.sleep(0.01)
    assert fe.stats["pump_restarts"] >= 1, "watchdog never restarted pump"
    got_v, got_i = p.result()
    assert np.array_equal(got_v, oracle[6][0][0]) \
        and np.array_equal(got_i, oracle[6][1][0])
    print(f"chaos 5: pump stalled 0.6 s -> watchdog restarted it "
          f"({fe.stats['pump_restarts']} restart), reply bit-exact")

    # 6. Pallas launch failure: sticky fallback to the jnp reference
    #    scorer — bit-exact, and zero new traces (warmup warmed BOTH)
    if args.use_pallas:
        inj.arm("kernel", count=1)
        serve(7)
        assert engine.kernel_degraded
        print("chaos 6: kernel launch fault -> sticky jnp fallback, "
              "reply bit-exact")

    # 7. seeded random storm: every submitted request resolves with a
    #    result or a typed ServingError — zero silent drops
    inj.clear()
    inj.arm("dispatch", rate=0.2)
    rng = np.random.default_rng(args.seed)
    pend, shed = [], 0
    for s in range(args.queries):
        kq = int(next_pow2(int(rng.integers(1, 17))))
        try:
            pend.append(fe.submit(data.context_query(100 + s)
                                  ["context_ids"], k=kq))
        except Degraded:
            shed += 1            # breaker open mid-storm: fast failure
            time.sleep(fe.breaker_cooldown)
    fe.drain()
    inj.clear()
    ok = failed = 0
    for p in pend:
        assert p.done(), "storm request never resolved"
        try:
            p.result()
            ok += 1
        except ServingError:
            failed += 1
    print(f"chaos 7: storm of {args.queries} requests at fault rate 0.2 "
          f"-> {ok} served / {failed} typed failures / {shed} shed, "
          f"0 dropped")

    h = fe.health()
    assert h["ready"] and not h["closed"]
    fe.close()
    assert engine.trace_count == traced, \
        (f"recovery paths retraced the scorer: "
         f"{engine.trace_count} != {traced}")
    print(f"chaos demo OK: all recovery paths exercised, zero retraces "
          f"({traced} traces incl. warmup)")


def main(argv=None):
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="dplr-fwfm")
    ap.add_argument("--config", default="smoke", choices=["smoke", "full"])
    ap.add_argument("--engine", default=None, choices=["corpus", "percall"],
                    help="corpus = precomputed item cache (default for "
                         "dplr); percall = Algorithm 1 per-query baseline")
    ap.add_argument("--items", type=int, default=512)
    ap.add_argument("--queries", type=int, default=100)
    ap.add_argument("--topk", type=int, default=0,
                    help="fused top-K: only (Bq, K) leaves the scorer")
    ap.add_argument("--use-pallas", action="store_true",
                    help="corpus engine scores through the Pallas kernel")
    ap.add_argument("--mp", action="store_true",
                    help="model-parallel DPLR scoring (shard_map)")
    ap.add_argument("--bf16", action="store_true", help="bf16 serving tables")
    ap.add_argument("--ckpt-dir", default=None,
                    help="load params from the newest checkpoint")
    ap.add_argument("--refresh-every", type=int, default=25,
                    help="poll --ckpt-dir for a newer step every N queries")
    ap.add_argument("--refresh-demo", action="store_true",
                    help="write a perturbed checkpoint mid-stream and "
                         "verify the corpus engine hot-swaps it")
    ap.add_argument("--mesh", default="none",
                    choices=["none", "host", "prod", "prod-mp"],
                    help="shard the corpus slab over this mesh's model "
                         "axis (host = all local devices; prod[-mp] = the "
                         "production shapes, dry-run device counts only)")
    ap.add_argument("--capacity", type=int, default=0,
                    help="corpus slab capacity (power of two; 0 = auto: "
                         "items rounded up, 2x items under --churn-demo)")
    ap.add_argument("--churn-demo", action="store_true",
                    help="interleave add/remove/update/score ops on the "
                         "live corpus and assert zero scorer retraces")
    ap.add_argument("--churn-ops", type=int, default=1000,
                    help="number of interleaved churn/score operations")
    ap.add_argument("--frontend", action="store_true",
                    help="drive a Poisson arrival trace through the "
                         "micro-batching query frontend vs sync per-query "
                         "serving (p50/p95/p99 + QPS; asserts zero "
                         "retraces and bit-exact replies)")
    ap.add_argument("--chaos-demo", action="store_true",
                    help="run a scripted fault storm through the "
                         "self-healing frontend: retried dispatch "
                         "faults, breaker trip/close, corrupt-push "
                         "rejection, failed churn write, pump-watchdog "
                         "restart (asserts bit-exact replies and zero "
                         "retraces on every recovery path)")
    ap.add_argument("--tenant-demo", action="store_true",
                    help="serve --tenants per-tenant corpora on ONE "
                         "shared ScorerRuntime through the tenant-routed "
                         "frontend (asserts zero cross-tenant retraces, "
                         "bit-exact replies, churn isolation, admission "
                         "shedding)")
    ap.add_argument("--tenants", type=int, default=4,
                    help="tenant count for --tenant-demo/--rpc (min 2)")
    ap.add_argument("--rpc", action="store_true",
                    help="serve the tenant-routed frontend over the "
                         "length-prefixed binary RPC protocol on a real "
                         "socket and replay a pipelined mixed-tenant "
                         "client trace (asserts bit-exact replies vs "
                         "direct frontend submission, typed error "
                         "frames, zero retraces, graceful drain; see "
                         "docs/network.md)")
    ap.add_argument("--port", type=int, default=0,
                    help="--rpc listen port (0 = ephemeral)")
    ap.add_argument("--arrival-rate", type=float, default=0.0,
                    help="frontend demo offered load in qps "
                         "(0 = auto: ~2x the sync per-query capacity)")
    ap.add_argument("--max-wait-ms", type=float, default=2.0,
                    help="frontend coalescing window: max ms a queued "
                         "request waits before a partial batch dispatches")
    ap.add_argument("--fe-batch", type=int, default=16,
                    help="frontend max micro-batch size (power of two)")
    ap.add_argument("--inflight", type=int, default=2,
                    help="frontend in-flight dispatch window depth "
                         "(2 = double buffering)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    spec = REGISTRY[args.arch]
    assert spec.family == "recsys", "serve.py ranks recsys candidates"
    cfg = spec.make_smoke() if args.config == "smoke" else spec.make_config()
    mod = fwfm if args.arch == "dplr-fwfm" else None
    if mod is None:
        from repro.launch.steps import _recsys_module
        mod = _recsys_module(args.arch)

    is_dplr = getattr(cfg, "interaction", None) == "dplr"
    engine_kind = args.engine or ("corpus" if is_dplr and not args.mp
                                  else "percall")
    if engine_kind == "corpus":
        if not is_dplr or args.mp:
            ap.error("--engine corpus requires a dplr model (and not --mp)")
    elif (args.topk or args.refresh_demo or args.use_pallas
          or args.churn_demo or args.frontend or args.tenant_demo
          or args.rpc or args.chaos_demo or args.mesh != "none"):
        ap.error("--topk/--refresh-demo/--use-pallas/--churn-demo/"
                 "--frontend/--tenant-demo/--rpc/--chaos-demo/--mesh "
                 "require --engine corpus")

    params = mod.init(jax.random.PRNGKey(args.seed), cfg)
    mgr = None
    if args.ckpt_dir or args.refresh_demo:
        ckpt_dir = args.ckpt_dir
        if ckpt_dir is None:           # demo mode: self-contained tmp dir
            import tempfile
            ckpt_dir = tempfile.mkdtemp(prefix="serve_refresh_demo_")
        mgr = CheckpointManager(ckpt_dir)
        restored, step = mgr.restore({"params": params})
        if restored:
            params = restored["params"]
            print(f"serving checkpoint step {step}")
    if args.bf16:
        params = jax.tree.map(
            lambda a: a.astype(jnp.bfloat16) if a.dtype == jnp.float32 else a,
            params)

    data = SyntheticCTR(cfg.layout, embed_dim=4, seed=args.seed)
    mesh = make_host_mesh()

    if engine_kind == "corpus":
        # checkpoints store f32 (npz can't round-trip bf16); restored params
        # are cast back to the serving dtype so the scorer never retraces.
        def to_serving_dtype(tree):
            if not args.bf16:
                return tree
            return jax.tree.map(
                lambda a: jnp.asarray(a).astype(jnp.bfloat16)
                if jnp.asarray(a).dtype == jnp.float32 else jnp.asarray(a),
                tree)

        def to_checkpoint_dtype(tree):
            return jax.tree.map(
                lambda a: np.asarray(a, np.float32)
                if jnp.asarray(a).dtype == jnp.bfloat16 else np.asarray(a),
                tree)

        if args.tenant_demo:
            return _tenant_demo(args, cfg, params, data)
        if args.rpc:
            return _rpc_demo(args, cfg, params, data)

        # initial candidate corpus: the item side of a fixed ranking query,
        # living in a capacity-padded slab so the catalog can churn.
        from repro.serving.corpus import next_pow2
        corpus_mesh = _corpus_mesh(args.mesh)
        n_shards = 1 if corpus_mesh is None \
            else int(corpus_mesh.shape["model"])
        capacity = args.capacity or next_pow2(
            2 * args.items if args.churn_demo else args.items)
        capacity = max(capacity, n_shards)
        corpus = data.ranking_query(args.items, 0)
        engine = CorpusRankingEngine(
            cfg, corpus["item_ids"][0], corpus["item_weights"][0],
            capacity=capacity, mesh=corpus_mesh,
            use_pallas_kernel=args.use_pallas)
        if corpus_mesh is not None:
            print(f"corpus sharded {n_shards}-way: "
                  f"{engine.local_capacity}/{engine.capacity} slots per "
                  f"device")
        engine.refresh(params, step=(mgr.latest_step() if mgr else None))

        if args.chaos_demo:
            return _chaos_demo(args, engine, data, params)
        if args.frontend:
            return _frontend_demo(args, engine, data)
        if args.churn_demo:
            return _churn_demo(args, engine, data)

        lat, refreshes = [], 0
        demo_pending = False
        for s in range(args.queries):
            if args.refresh_demo and s == args.queries // 2:
                bumped = jax.tree.map(lambda a: a, params)
                bumped["bias"] = params["bias"] + 1.0
                mgr.save({"params": to_checkpoint_dtype(bumped)},
                         step=(engine.model_step or 0) + 1, blocking=True)
                demo_pending = True   # poll immediately, whatever the cadence
            if mgr is not None and (demo_pending
                                    or (s and s % args.refresh_every == 0)):
                try:
                    swapped = engine.maybe_refresh(
                        mgr, {"params": to_checkpoint_dtype(params)},
                        select=lambda t: to_serving_dtype(t["params"]))
                except RefreshFailed as e:
                    # a bad model push: keep serving the last-good
                    # snapshot, report once (the signature gate keeps
                    # later polls silent until the push changes)
                    swapped = False
                    print(f"query {s}: refresh REJECTED ({e}); serving "
                          f"step {engine.model_step}")
                if swapped:
                    refreshes += 1
                    demo_pending = False
                    print(f"query {s}: refreshed to checkpoint step "
                          f"{engine.model_step} (corpus cache rebuilt)")
            qn = data.context_query(s)
            ctx = jnp.asarray(qn["context_ids"])
            ctx_w = jnp.asarray(qn["context_weights"])
            t0 = time.perf_counter()
            if args.topk:
                out = jax.block_until_ready(engine.topk(ctx, args.topk,
                                                        ctx_w))
                scores = out[0]
            else:
                scores = jax.block_until_ready(engine.score(ctx, ctx_w))
            lat.append((time.perf_counter() - t0) * 1e3)
            if s == 0:
                if args.topk:
                    print(f"query 0: fused top-{args.topk} of {args.items} "
                          f"candidates -> {np.asarray(out[1][0][:3])}")
                else:
                    top = np.argsort(-np.asarray(scores[0]))[:3]
                    print(f"query 0: top-3 of {args.items} candidates -> {top}")
        tag = (f"corpus{', pallas' if args.use_pallas else ''}"
               f"{' (degraded to jnp)' if engine.kernel_degraded else ''}"
               f"{f', top{args.topk}' if args.topk else ''}"
               f"{f', {n_shards} shards' if n_shards > 1 else ''}"
               f"{', bf16' if args.bf16 else ''}")
        _report(tag, np.asarray(lat[2:]), args.queries, args.items)
        if args.refresh_demo:
            assert refreshes >= 1, "refresh demo never saw the new checkpoint"
            assert engine.trace_count <= 1, \
                f"scorer retraced across refresh ({engine.trace_count})"
            print(f"refresh round-trip OK: {refreshes} refresh(es), "
                  f"scorer traced {engine.trace_count}x (no restart)")
        return

    if args.mp:
        assert args.arch == "dplr-fwfm" and cfg.interaction == "dplr"
        scorer = jax.jit(lambda p, q: fwfm.rank_items_mp(
            p, cfg, q, mesh=mesh, item_spec=P(None, None, None)))
    else:
        scorer = jax.jit(lambda p, q: mod.rank_items(p, cfg, q))

    lat = []
    for s in range(args.queries):
        q = {k: jnp.asarray(v) for k, v in
             data.ranking_query(args.items, s).items()}
        t0 = time.perf_counter()
        scores = jax.block_until_ready(scorer(params, q))
        lat.append((time.perf_counter() - t0) * 1e3)
        if s == 0:
            top = np.argsort(-np.asarray(scores[0]))[:3]
            print(f"query 0: top-3 of {args.items} candidates -> {top}")
    tag = (f"percall, {'mp' if args.mp else 'spmd'}"
           f"{', bf16' if args.bf16 else ''}")
    _report(tag, np.asarray(lat[2:]), args.queries, args.items)


if __name__ == "__main__":
    main()
