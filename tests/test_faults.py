"""Chaos suite: the serving stack under injected faults.

Every scenario scripts a deterministic ``FaultInjector`` schedule and
asserts the self-healing invariants of docs/robustness.md:

  * every ACCEPTED request resolves — a result or a typed
    ``ServingError``, never a silent drop;
  * every reply that succeeds is BIT-exact vs the fault-free oracle
    (retries re-dispatch the same assembled batch; the pressure clamp
    serves an exact prefix);
  * failure domains stay isolated — tenant A's open breaker never
    touches tenant B's serving or churn, a failed mutation is never
    partially visible, a corrupt model push never interrupts serving;
  * no recovery path retraces the scorer (warm grid stays warm).

Timing-sensitive pieces (watchdog) use generous margins; everything
else runs on fake clocks and count/rate fault schedules from a seeded
stream, so a failure here reproduces exactly under ``pytest -x``.
"""
import time

import numpy as np
import pytest

import jax

from repro.checkpoint import CheckpointManager
from repro.core.fields import uniform_layout
from repro.data.synthetic_ctr import SyntheticCTR
from repro.models.recsys import fwfm
from repro.serving import (CorpusRankingEngine, DeadlineExceeded, Degraded,
                           DispatchFailed, FaultInjector, InjectedFault,
                           QueryFrontend, RefreshFailed, ServingError,
                           Unservable)


def _setup(nC=5, nI=4, vocab=50, k=8, rho=2, n=37, seed=0, **engine_kw):
    layout = uniform_layout(nC, nI, vocab)
    cfg = fwfm.FwFMConfig(layout=layout, embed_dim=k, interaction="dplr",
                          rank=rho)
    params = fwfm.init(jax.random.PRNGKey(seed), cfg)
    data = SyntheticCTR(layout, embed_dim=4, seed=seed)
    q = data.ranking_query(n, seed)
    engine = CorpusRankingEngine(cfg, q["item_ids"][0], q["item_weights"][0],
                                 **engine_kw)
    engine.refresh(params, step=0)
    return cfg, params, data, engine


class FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self) -> float:
        return self.t


def _ctx(data, s):
    return data.context_query(s)["context_ids"]


def _oracle(engine, data, s, k):
    v, i = engine.topk(np.asarray(_ctx(data, s)).reshape(1, -1), k)
    return np.asarray(v)[0], np.asarray(i)[0]


# ---------------------------------------------------------------------------
# retry/backoff: transient dispatch faults are absorbed, replies bit-exact
# ---------------------------------------------------------------------------

def test_transient_dispatch_fault_retried_bitexact():
    _, _, data, engine = _setup()
    inj = FaultInjector()
    fe = QueryFrontend(engine, max_batch=4, max_k=8, max_wait=1e9,
                       retries=2, retry_backoff=0.0, fault_injector=inj)
    fe.warmup(_ctx(data, 0))
    traced = engine.trace_count
    inj.arm("dispatch", count=1)          # fail exactly the next dispatch
    p = fe.submit(_ctx(data, 3), k=5)
    fe.drain()
    scores, slots = p.result()            # retry absorbed the fault
    assert engine.trace_count == traced   # recovery retraced nothing
    wv, wi = _oracle(engine, data, 3, 5)  # (the exact-K oracle may trace)
    np.testing.assert_array_equal(scores, wv)
    np.testing.assert_array_equal(slots, wi)
    assert fe.stats["retries"] == 1 and fe.stats["failed"] == 0
    assert inj.fired("dispatch") == 1


def test_exhausted_retries_fail_typed():
    _, _, data, engine = _setup()
    inj = FaultInjector()
    fe = QueryFrontend(engine, max_batch=2, max_k=4, max_wait=1e9,
                       retries=1, retry_backoff=0.0, fault_injector=inj)
    inj.arm("dispatch")                   # every dispatch fails
    p = fe.submit(_ctx(data, 0), k=2)
    fe.drain()
    assert p.done()
    with pytest.raises(DispatchFailed) as ei:
        p.result()
    assert ei.value.attempts == 2         # first try + 1 retry
    assert ei.value.tenant == "default"
    assert fe.stats["failed"] == 1


# ---------------------------------------------------------------------------
# resolve-time failure: the SAME assembled batch re-dispatches, bit-exact
# ---------------------------------------------------------------------------

def test_resolve_failure_redispatches_same_batch_bitexact():
    _, _, data, engine = _setup()
    inj = FaultInjector()
    fe = QueryFrontend(engine, max_batch=4, max_k=8, max_wait=1e9,
                       retries=1, retry_backoff=0.0, fault_injector=inj)
    fe.warmup(_ctx(data, 0))
    traced = engine.trace_count
    ks = [3, 7, 1]
    reqs = [fe.submit(_ctx(data, s), k=k) for s, k in enumerate(ks)]
    inj.arm("resolve", count=1)           # deferred device error at read
    fe.drain()
    assert engine.trace_count == traced   # the re-dispatch retraced nothing
    for s, (k, p) in enumerate(zip(ks, reqs)):
        scores, slots = p.result()
        wv, wi = _oracle(engine, data, s, k)
        np.testing.assert_array_equal(scores, wv)
        np.testing.assert_array_equal(slots, wi)
    assert inj.fired("resolve") == 1


def test_resolve_failure_with_dead_backend_fails_typed():
    _, _, data, engine = _setup()
    inj = FaultInjector()
    fe = QueryFrontend(engine, max_batch=2, max_k=4, max_wait=1e9,
                       retries=0, retry_backoff=0.0, fault_injector=inj)
    p = fe.submit(_ctx(data, 0), k=2)
    fe.flush()                            # dispatched clean
    inj.arm("resolve")                    # ...but the read fails
    inj.arm("dispatch")                   # ...and so does the re-dispatch
    fe.drain()
    with pytest.raises(DispatchFailed):
        p.result()


# ---------------------------------------------------------------------------
# circuit breaker: trip, shed fast, half-open probe, tenant isolation
# ---------------------------------------------------------------------------

def test_breaker_trips_sheds_and_recovers():
    _, _, data, engine = _setup()
    inj = FaultInjector()
    clock = FakeClock()
    fe = QueryFrontend(engine, max_batch=2, max_k=4, max_wait=1e9,
                       auto_pump=False, clock=clock, retries=0,
                       retry_backoff=0.0, breaker_threshold=2,
                       breaker_cooldown=1.0, fault_injector=inj)
    inj.arm("dispatch")
    for _ in range(2):                    # two exhausted dispatches: trip
        fe.submit(_ctx(data, 0), k=2)
        fe.flush()
    assert fe.health()["tenants"]["default"]["breaker"] == "open"
    with pytest.raises(Degraded):         # open breaker sheds at submit
        fe.submit(_ctx(data, 1), k=2)
    assert fe.stats["degraded"] == 1

    clock.t = 5.0                         # cooldown elapsed: half-open
    inj.clear()
    probe = fe.submit(_ctx(data, 2), k=2)
    assert fe.health()["tenants"]["default"]["breaker"] == "half_open"
    fe.flush()
    fe.drain()
    assert fe.health()["tenants"]["default"]["breaker"] == "closed"
    wv, wi = _oracle(engine, data, 2, 2)
    np.testing.assert_array_equal(probe.result()[0], wv)
    np.testing.assert_array_equal(probe.result()[1], wi)
    assert fe.lane_stats()["trips"] == 1


def test_breaker_halfopen_probe_failure_reopens():
    _, _, data, engine = _setup()
    inj = FaultInjector()
    clock = FakeClock()
    fe = QueryFrontend(engine, max_batch=2, max_k=4, max_wait=1e9,
                       auto_pump=False, clock=clock, retries=0,
                       breaker_threshold=1, breaker_cooldown=1.0,
                       fault_injector=inj)
    inj.arm("dispatch")
    fe.submit(_ctx(data, 0), k=2)
    fe.flush()                            # trip
    clock.t = 2.0
    fe.submit(_ctx(data, 1), k=2)         # the half-open probe
    fe.flush()                            # probe fails: re-open at once
    assert fe.health()["tenants"]["default"]["breaker"] == "open"
    assert fe.lane_stats()["trips"] == 2
    with pytest.raises(Degraded):
        fe.submit(_ctx(data, 2), k=2)


def test_open_breaker_isolates_tenants_and_churn():
    """Tenant A's open breaker must not touch tenant B: B serves
    bit-exact, B churns, and B's queue/in-flight state never drains on
    A's account."""
    cfg, params, data, ea = _setup()
    qb = data.ranking_query(33, 1)
    eb = CorpusRankingEngine(cfg, qb["item_ids"][0], qb["item_weights"][0],
                             runtime=ea.runtime)
    eb.refresh(params, step=0)
    inj = FaultInjector()
    clock = FakeClock()
    fe = QueryFrontend({"A": ea, "B": eb}, max_batch=2, max_k=4,
                       max_wait=1e9, auto_pump=False, clock=clock,
                       retries=0, breaker_threshold=1,
                       breaker_cooldown=1e9, fault_injector=inj)
    inj.arm("dispatch", count=1)          # exactly A's next dispatch
    fe.submit(_ctx(data, 0), k=2, tenant="A")
    fe.flush()                            # A trips
    assert fe.health()["tenants"]["A"]["breaker"] == "open"
    with pytest.raises(Degraded):
        fe.submit(_ctx(data, 1), k=2, tenant="A")

    # B serves bit-exact while A is open
    pb = fe.submit(_ctx(data, 5), k=3, tenant="B")
    fe.flush()
    fe.drain()
    wv, wi = _oracle(eb, data, 5, 3)
    np.testing.assert_array_equal(pb.result()[0], wv)
    np.testing.assert_array_equal(pb.result()[1], wi)
    assert fe.health()["tenants"]["B"]["breaker"] == "closed"

    # B churns while A is open (the writer barrier drains only B)
    n_b = eb.n_items
    slots = fe.add_items(qb["item_ids"][0][:2], qb["item_weights"][0][:2],
                         tenant="B")
    assert eb.n_items == n_b + 2 and eb.is_live(slots).all()
    assert ea.n_items == 37               # A untouched


def test_remove_tenant_racing_open_breaker():
    """remove_tenant while the lane's breaker is open (and while its
    queue still holds requests accepted before the trip): every queued
    request resolves typed, the lane disappears, other tenants keep
    serving."""
    cfg, params, data, ea = _setup()
    qb = data.ranking_query(33, 1)
    eb = CorpusRankingEngine(cfg, qb["item_ids"][0], qb["item_weights"][0],
                             runtime=ea.runtime)
    eb.refresh(params, step=0)
    inj = FaultInjector()
    clock = FakeClock()
    fe = QueryFrontend({"A": ea, "B": eb}, max_batch=1, max_k=4,
                       max_wait=1e9, auto_pump=False, clock=clock,
                       retries=0, breaker_threshold=1,
                       breaker_cooldown=1e9, fault_injector=inj)
    r1 = fe.submit(_ctx(data, 0), k=2, tenant="A")
    r2 = fe.submit(_ctx(data, 1), k=2, tenant="A")
    inj.arm("dispatch")
    # the removal drain dispatches r1 (fails, TRIPS the breaker) then r2
    # — an open breaker gates submits only, never accepted requests
    fe.remove_tenant("A")
    assert r1.done() and r2.done()
    for r in (r1, r2):
        with pytest.raises(DispatchFailed):
            r.result()
    assert fe.tenants == ("B",)
    with pytest.raises(ValueError):
        fe.submit(_ctx(data, 2), k=2, tenant="A")
    inj.clear()
    pb = fe.submit(_ctx(data, 3), k=2, tenant="B")
    fe.drain()
    wv, _ = _oracle(eb, data, 3, 2)
    np.testing.assert_array_equal(pb.result()[0], wv)


# ---------------------------------------------------------------------------
# the umbrella invariant: under a fault storm, EVERY accepted request
# resolves — a result (bit-exact) or a typed ServingError
# ---------------------------------------------------------------------------

def test_fault_storm_every_request_resolves():
    _, _, data, engine = _setup()
    inj = FaultInjector(seed=3)
    fe = QueryFrontend(engine, max_batch=4, max_k=8, max_wait=1e9,
                       auto_pump=False, retries=1, retry_backoff=0.0,
                       fault_injector=inj)
    fe.warmup(_ctx(data, 0))
    traced = engine.trace_count
    inj.arm("dispatch", rate=0.4)         # seeded: deterministic pattern
    rng = np.random.default_rng(0)
    accepted = []
    for s in range(40):
        k = int(rng.integers(1, 9))
        accepted.append((s, k, fe.submit(_ctx(data, s), k=k)))
        if s % 3 == 0:
            fe.pump()
    fe.drain()
    inj.clear()
    assert engine.trace_count == traced   # retries/failures: zero retraces
    ok = failed = 0
    for s, k, p in accepted:
        assert p.done(), f"request {s} silently dropped"
        try:
            scores, slots = p.result()
        except ServingError:
            failed += 1
            continue
        wv, wi = _oracle(engine, data, s, k)
        np.testing.assert_array_equal(scores, wv)
        np.testing.assert_array_equal(slots, wi)
        ok += 1
    assert ok + failed == 40 and ok > 0 and failed > 0


# ---------------------------------------------------------------------------
# pressure-K clamp: degraded-but-exact prefixes under sustained pressure
# ---------------------------------------------------------------------------

def test_pressure_clamp_serves_exact_prefix():
    _, _, data, engine = _setup()
    fe = QueryFrontend(engine, max_batch=4, max_k=8, max_wait=1e9,
                       auto_pump=False, pressure_depth=4, pressure_k=2)
    reqs = [fe.submit(_ctx(data, s), k=8) for s in range(12)]
    fe.flush()
    fe.drain()
    clamped = [p for p in reqs if p.degraded]
    full = [p for p in reqs if not p.degraded]
    assert len(clamped) == 8 and len(full) == 4   # last batch saw no queue
    assert fe.stats["clamped"] == 8
    for s, p in enumerate(reqs):
        scores, slots = p.result()
        wv, wi = _oracle(engine, data, s, 8)
        want_k = p.served_k
        assert want_k == (2 if p.degraded else 8) and p.k == 8
        # the clamped reply is the EXACT top-served_k prefix
        np.testing.assert_array_equal(scores, wv[:want_k])
        np.testing.assert_array_equal(slots, wi[:want_k])


# ---------------------------------------------------------------------------
# mutation faults: partial churn is never reader-visible
# ---------------------------------------------------------------------------

def test_failed_mutation_never_partially_visible():
    inj = FaultInjector()
    _, params, data, engine = _setup(fault_injector=inj)
    fe = QueryFrontend(engine, max_batch=2, max_k=4, max_wait=1e9,
                       auto_pump=False)
    q2 = data.ranking_query(4, 9)
    before_n = engine.n_items
    before_valid = engine.valid_slots.copy()
    wv, wi = _oracle(engine, data, 0, 4)

    # an in-flight read rides through the failed churn untouched
    p = fe.submit(_ctx(data, 0), k=4)
    fe.flush()
    inj.arm("write")
    with pytest.raises(InjectedFault):
        fe.add_items(q2["item_ids"][0], q2["item_weights"][0])
    with pytest.raises(InjectedFault):
        fe.remove_items([int(wi[0])])
    with pytest.raises(InjectedFault):
        fe.update_items([int(wi[0])], q2["item_ids"][0][:1],
                        q2["item_weights"][0][:1])
    inj.disarm("write")

    # nothing moved: same live count, same slots, same scores — and the
    # in-flight reply resolved against the intact snapshot
    assert engine.n_items == before_n
    np.testing.assert_array_equal(engine.valid_slots, before_valid)
    np.testing.assert_array_equal(p.result()[0], wv)
    np.testing.assert_array_equal(p.result()[1], wi)
    gv, gi = _oracle(engine, data, 0, 4)
    np.testing.assert_array_equal(gv, wv)
    np.testing.assert_array_equal(gi, wi)

    # cleared: the identical mutation now lands
    slots = fe.add_items(q2["item_ids"][0], q2["item_weights"][0])
    assert engine.n_items == before_n + 4 and engine.is_live(slots).all()


def test_failed_slab_growth_is_clean_noop():
    inj = FaultInjector()
    _, params, data, engine = _setup(n=16, capacity=16, fault_injector=inj)
    q2 = data.ranking_query(2, 9)
    inj.arm("alloc", count=1)
    with pytest.raises(InjectedFault):
        engine.add_items(q2["item_ids"][0], q2["item_weights"][0])
    assert engine.capacity == 16 and engine.n_items == 16
    slots = engine.add_items(q2["item_ids"][0], q2["item_weights"][0])
    assert engine.capacity == 32 and engine.is_live(slots).all()


# ---------------------------------------------------------------------------
# checkpoint faults: a bad model push surfaces typed, serving continues
# ---------------------------------------------------------------------------

def test_corrupt_and_torn_refresh_serve_last_good(tmp_path):
    _, params, data, engine = _setup()
    inj = FaultInjector()
    mgr = CheckpointManager(str(tmp_path))
    sel = lambda t: t["params"]
    mgr.save({"params": params}, step=1, blocking=True)
    assert engine.maybe_refresh(mgr, {"params": params}, select=sel)
    wv, wi = _oracle(engine, data, 0, 4)

    # corrupt push: RefreshFailed ONCE, silent same-signature re-polls,
    # last-good snapshot still serving bit-exact
    mgr.save({"params": params}, step=2, blocking=True)
    assert inj.corrupt_checkpoint(str(tmp_path)) == 2
    assert not mgr.step_valid(2) and mgr.step_valid(1)
    with pytest.raises(RefreshFailed) as ei:
        engine.maybe_refresh(mgr, {"params": params}, select=sel)
    assert ei.value.step == 2
    assert not engine.maybe_refresh(mgr, {"params": params}, select=sel)
    assert engine.model_step == 1
    gv, gi = _oracle(engine, data, 0, 4)
    np.testing.assert_array_equal(gv, wv)
    np.testing.assert_array_equal(gi, wi)

    # torn write (manifest intact, payload truncated): same story
    mgr.save({"params": params}, step=3, blocking=True)
    assert engine.maybe_refresh(mgr, {"params": params}, select=sel)
    mgr.save({"params": params}, step=4, blocking=True)
    assert inj.torn_write_checkpoint(str(tmp_path)) == 4
    with pytest.raises(RefreshFailed) as ei:
        engine.maybe_refresh(mgr, {"params": params}, select=sel)
    assert ei.value.step == 4 and engine.model_step == 3

    # a re-save of the torn step lands normally and clears the error
    mgr.save({"params": params}, step=4, blocking=True)
    assert engine.maybe_refresh(mgr, {"params": params}, select=sel)
    assert engine.model_step == 4 and engine.last_refresh_error is None


# ---------------------------------------------------------------------------
# kernel fallback: Pallas launch failure degrades to jnp, zero retraces
# ---------------------------------------------------------------------------

def test_kernel_launch_failure_falls_back_bitexact():
    inj = FaultInjector()
    cfg, params, data, engine = _setup(use_pallas_kernel=True, block_n=16,
                                       fault_injector=inj)
    q = data.ranking_query(37, 0)
    ref = CorpusRankingEngine(cfg, q["item_ids"][0], q["item_weights"][0])
    ref.refresh(params, step=0)
    ctx = np.asarray(_ctx(data, 3)).reshape(1, -1)
    engine.warmup_grid(_ctx(data, 0), max_batch=1, max_k=4)  # BOTH paths
    traced = engine.trace_count
    inj.arm("kernel")
    vals, idx = engine.topk(ctx, 4)
    assert engine.kernel_degraded         # sticky
    assert engine.trace_count == traced   # jnp path was pre-warmed
    rv, ri = ref.topk(ctx, 4)
    np.testing.assert_array_equal(np.asarray(vals), np.asarray(rv))
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(ri))
    engine.topk(ctx, 4)                   # degraded: kernel never probed
    assert inj.calls("kernel") == 1


@pytest.mark.parametrize("warm", [False, True])
def test_kernel_compile_error_propagates_runtime_fault_degrades(
        monkeypatch, warm):
    """A kernel that fails to lower or compile must stop the server, not
    hide behind the jnp path: the first dispatch at a shape raises out of
    ``warmup``/``topk`` with ``kernel_degraded`` still False.  The same
    error from an executable that already ran at that shape is a launch
    fault and degrades (bit-exact jnp result)."""
    cfg, params, data, engine = _setup(use_pallas_kernel=True, block_n=16)
    ctx = np.asarray(_ctx(data, 3)).reshape(1, -1)
    rt = engine.runtime
    if warm:
        engine.warmup_grid(_ctx(data, 0), max_batch=1, max_k=4)
    good = rt.kernel_score

    def lowering_error(*a, **kw):
        raise NotImplementedError(
            "Unimplemented primitive in Pallas TPU lowering: top_k")

    monkeypatch.setattr(rt, "kernel_score", lowering_error)
    if not warm:
        with pytest.raises(NotImplementedError):
            engine.warmup_grid(_ctx(data, 0), max_batch=1, max_k=4)
        with pytest.raises(NotImplementedError):
            engine.topk(ctx, 4)
        assert not engine.kernel_degraded
        return
    vals, idx = engine.topk(ctx, 4)
    assert engine.kernel_degraded
    monkeypatch.setattr(rt, "kernel_score", good)
    rv, ri = rt.topk(params, engine.cache, jax.numpy.asarray(ctx),
                     jax.numpy.ones(ctx.shape, rt.wdtype), K=4)
    np.testing.assert_array_equal(np.asarray(vals), np.asarray(rv))
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(ri))


# ---------------------------------------------------------------------------
# deadline clock skew
# ---------------------------------------------------------------------------

def test_clock_skew_expires_queued_deadlines():
    _, _, data, engine = _setup()
    inj = FaultInjector()
    base = FakeClock()
    fe = QueryFrontend(engine, max_batch=4, max_k=4, max_wait=1e9,
                       auto_pump=False, clock=inj.wrap_clock(base))
    p = fe.submit(_ctx(data, 0), k=2, deadline=5.0)
    inj.arm("clock", skew=10.0)           # the deadline clock jumps ahead
    fe.flush()
    with pytest.raises(DeadlineExceeded):
        p.result()
    assert fe.stats["expired"] == 1


# ---------------------------------------------------------------------------
# pump watchdog: a stalled pump loop is detected and restarted
# ---------------------------------------------------------------------------

def test_pump_watchdog_restarts_stalled_loop():
    _, _, data, engine = _setup()
    inj = FaultInjector()
    fe = QueryFrontend(engine, max_batch=4, max_k=4, max_wait=0.0,
                       auto_pump=False, fault_injector=inj)
    inj.arm("pump", delay=0.6, count=1)   # one slow-fault stall
    fe.start_pump(interval=0.005, watchdog=0.1)
    try:
        p = fe.submit(_ctx(data, 0), k=2)
        deadline = time.monotonic() + 5.0
        # the restarted generation must pick the aged request up and
        # dispatch it (pump dispatches; resolution happens at result())
        while ((fe.stats["pump_restarts"] < 1 or fe.queue_depth > 0)
               and time.monotonic() < deadline):
            time.sleep(0.01)
        assert fe.stats["pump_restarts"] >= 1, "stall never detected"
        assert fe.queue_depth == 0, "restarted pump never dispatched"
        assert fe.health()["pump"]["running"]
        wv, wi = _oracle(engine, data, 0, 2)
        np.testing.assert_array_equal(p.result()[0], wv)
        np.testing.assert_array_equal(p.result()[1], wi)
    finally:
        fe.stop_pump()


# ---------------------------------------------------------------------------
# graceful shutdown + health surface
# ---------------------------------------------------------------------------

def test_close_resolves_inflight_and_fails_queued_typed():
    _, _, data, engine = _setup()
    fe = QueryFrontend(engine, max_batch=2, max_k=4, max_wait=1e9,
                       auto_pump=False)
    a = fe.submit(_ctx(data, 0), k=2)
    b = fe.submit(_ctx(data, 1), k=3)
    fe.flush()                            # a+b in flight
    c = fe.submit(_ctx(data, 2), k=2)     # still queued at close
    fe.close()
    for s, k, p in [(0, 2, a), (1, 3, b)]:
        wv, wi = _oracle(engine, data, s, k)
        np.testing.assert_array_equal(p.result()[0], wv)
        np.testing.assert_array_equal(p.result()[1], wi)
    with pytest.raises(Unservable):
        c.result()
    with pytest.raises(Unservable):
        fe.submit(_ctx(data, 3), k=2)
    h = fe.health()
    assert h["closed"] and not h["ready"]
    assert engine.on_mutate is None       # writer barrier detached
    fe.close()                            # idempotent


def test_health_probe_shape():
    _, _, data, engine = _setup()
    fe = QueryFrontend(engine, max_batch=4, max_k=4, max_wait=1e9,
                       auto_pump=False)
    h = fe.health()
    assert h["ready"] and not h["closed"] and not h["degraded"]
    lane = h["tenants"]["default"]
    assert lane["breaker"] == "closed" and lane["queued"] == 0
    assert lane["n_items"] == 37 and lane["model_step"] == 0
    assert lane["refresh_age"] is not None and lane["refresh_age"] >= 0
    assert lane["last_refresh_error"] is None
    assert not lane["kernel_degraded"]


# ---------------------------------------------------------------------------
# the injector itself: seeded determinism
# ---------------------------------------------------------------------------

def test_injector_rate_schedule_is_deterministic():
    def pattern(seed):
        inj = FaultInjector(seed=seed)
        inj.arm("dispatch", rate=0.5)
        out = []
        for _ in range(50):
            try:
                inj.check("dispatch")
                out.append(0)
            except InjectedFault:
                out.append(1)
        return out

    a, b = pattern(7), pattern(7)
    assert a == b and 0 < sum(a) < 50
    assert pattern(8) != a                # a different seed, a different run
