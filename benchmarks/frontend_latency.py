"""Micro-batching frontend vs sync per-query serving under open-loop
Poisson load (the PR-4 claim).

An online ranking service is judged on latency PERCENTILES and sustained
QPS, not single-batch kernel time.  Sync per-query serving saturates at
1/s1 qps (s1 = one Bq=1 dispatch): past that, the queue — and therefore
p99 — grows without bound.  The frontend coalesces concurrent arrivals
into one padded micro-batch dispatch whose cost grows far slower than Bq
(the corpus scan is shared), multiplying capacity; replies stay bit-exact
vs one-by-one engine calls.

Method: measure s1, then replay the SAME fixed Poisson arrival trace
(mixed per-query K in 1..16, an update-churn burst through the engine's
writer barrier every 50 requests) through sync serving and through the
frontend at each offered rate; rates are chosen as multiples of the
measured sync capacity so the benchmark is machine-independent.  Latency
is completion minus arrival (queueing included); QPS is completed
requests over the span from first arrival to last completion.  Every
frontend run also asserts ZERO scorer retraces after warmup and bit-exact
parity with one-by-one ``engine.topk`` calls for every request scored
against the final corpus state.

The D>1 rows re-run the whole comparison against the mesh-sharded engine
in the same process, on a mesh over ``jax.devices()[:D]`` (like
benchmarks/corpus_shard.py: on the CPU the caller sets
``XLA_FLAGS=--xla_force_host_platform_device_count=4``) — same frontend,
same invariants, the corpus slab split across D devices.

Output lines:
    frontend: <D>,<n>,<rate_qps>,<policy>,<p50_ms>,<p95_ms>,<p99_ms>,<qps>,<parity>
    frontend: <D>,skipped,<n> devices
with policy ``sync`` or ``b<max_batch>/w<max_wait_ms>``; at offered rates
above sync capacity the driver FAILS unless coalescing beats sync on both
p99 and QPS.
"""
from __future__ import annotations

import time

import numpy as np

MAX_K = 16
CHURN_EVERY = 50


def _trace(rng, n_req: int, rate: float):
    """One fixed workload: Poisson arrival times + per-request K."""
    return (np.cumsum(rng.exponential(1.0 / rate, n_req)),
            rng.integers(1, MAX_K + 1, n_req))


def _make_churn(engine, data, rng):
    def churn(s):
        upd = data.ranking_query(2, 90_000 + s)
        slots = rng.choice(engine.valid_slots, 2, replace=False)
        engine.update_items(slots, upd["item_ids"][0], upd["item_weights"][0])
    return churn


def _run_sync(engine, ctxs, arrivals, ks, churn):
    import jax

    from repro.serving.corpus import next_pow2

    n = len(ctxs)
    lat = np.empty(n)
    t0 = time.perf_counter()
    for s in range(n):
        now = time.perf_counter() - t0
        if arrivals[s] > now:
            time.sleep(arrivals[s] - now)
        if s and s % CHURN_EVERY == 0:
            churn(s)
        jax.block_until_ready(
            engine.topk(ctxs[s], int(next_pow2(int(ks[s]))))[0])
        lat[s] = (time.perf_counter() - t0 - arrivals[s]) * 1e3
    qps = n / max(time.perf_counter() - t0, 1e-9)
    return lat, qps, "ok"


def _run_frontend(engine, ctxs, arrivals, ks, churn, *, max_batch,
                  max_wait):
    from repro.serving import QueryFrontend

    n = len(ctxs)
    fe = QueryFrontend(engine, max_batch=max_batch, max_k=MAX_K,
                       max_wait=max_wait)
    fe.warmup(np.asarray(ctxs[0]))
    traced = engine.trace_count
    pend = []
    t0 = time.perf_counter()
    for s in range(n):
        now = time.perf_counter() - t0
        if arrivals[s] > now:
            time.sleep(arrivals[s] - now)
        if s and s % CHURN_EVERY == 0:
            churn(s)
        pend.append(fe.submit(ctxs[s], k=int(ks[s])))
    fe.drain()
    qps = n / max(time.perf_counter() - t0, 1e-9)
    # completion minus SCHEDULED arrival, symmetric with _run_sync: when
    # submit itself lags the Poisson schedule (window eviction blocked
    # the submit loop), that backlog is queueing and must be charged
    lat = np.asarray([(p.done_time - t0 - arrivals[s]) * 1e3
                      for s, p in enumerate(pend)])

    parity = "ok"
    if engine.trace_count != traced:
        parity = f"RETRACED({engine.trace_count - traced})"
    # bit-exact one-by-one parity for every request scored against the
    # final corpus state (requests after the last churn burst; earlier
    # replies were computed on the pre-churn snapshot their batch saw)
    last_churn = (n - 1) // CHURN_EVERY * CHURN_EVERY
    for s in range(last_churn + 1, n):
        sc, sl = pend[s].result()
        wv, wi = engine.topk(np.asarray(ctxs[s]).reshape(1, -1), int(ks[s]))
        if not (np.array_equal(sc, np.asarray(wv)[0])
                and np.array_equal(sl, np.asarray(wi)[0])):
            parity = "FAIL"
    if not all(engine.is_live(p.result()[1]).all() for p in pend):
        parity = "DEAD-SLOT"
    engine.on_mutate = None           # detach before the next policy's fe
    return lat, qps, parity


def leg(devices: int, n: int, n_req: int, rate_mults: list[float],
        batches: list[int]) -> None:
    import jax

    from repro.core.fields import uniform_layout
    from repro.data.synthetic_ctr import SyntheticCTR
    from repro.launch.mesh import make_host_mesh
    from repro.models.recsys import fwfm
    from repro.serving import CorpusRankingEngine
    from repro.serving.corpus import next_pow2

    layout = uniform_layout(25, 38, 1000)
    cfg = fwfm.FwFMConfig(layout=layout, embed_dim=16, interaction="dplr",
                          rank=3)
    params = fwfm.init(jax.random.PRNGKey(0), cfg)
    data = SyntheticCTR(layout, embed_dim=8, seed=0)
    corpus = data.ranking_query(n, 0)
    mesh = None if devices == 1 else make_host_mesh(model=devices)
    engine = CorpusRankingEngine(cfg, corpus["item_ids"][0],
                                 corpus["item_weights"][0],
                                 capacity=next_pow2(2 * n), mesh=mesh)
    engine.refresh(params, step=0)

    rng = np.random.default_rng(0)
    ctxs = [data.context_query(s)["context_ids"] for s in range(n_req)]
    churn = _make_churn(engine, data, rng)
    churn(-1)                                     # warm the churn path

    # warm every (Bq=1, K bucket) shape the sync path will hit, so its
    # first timed run measures queueing, not tracing
    ctx0 = ctxs[0]
    k = 1
    while k <= next_pow2(MAX_K):
        jax.block_until_ready(engine.topk(ctx0, k)[0])
        k *= 2
    # sync capacity: one bucketed-K Bq=1 dispatch, blocked
    for _ in range(5):
        jax.block_until_ready(engine.topk(ctx0, next_pow2(MAX_K))[0])
    t0 = time.perf_counter()
    for _ in range(20):
        jax.block_until_ready(engine.topk(ctx0, next_pow2(MAX_K))[0])
    s1 = (time.perf_counter() - t0) / 20

    for mult in rate_mults:
        rate = mult / s1
        arrivals, ks = _trace(np.random.default_rng(7), n_req, rate)
        rows = {}
        runs = [("sync", None)] + [
            (f"b{b}/w{2 * s1 * 1e3:.1f}", b) for b in batches]
        for policy, b in runs:
            if b is None:
                lat, qps, parity = _run_sync(engine, ctxs, arrivals, ks,
                                             churn)
            else:
                lat, qps, parity = _run_frontend(
                    engine, ctxs, arrivals, ks, churn,
                    max_batch=b, max_wait=2 * s1)
            rows[policy] = (np.percentile(lat, 99), qps)
            print(f"frontend: {devices},{n},{rate:.0f},{policy},"
                  f"{np.percentile(lat, 50):.2f},"
                  f"{np.percentile(lat, 95):.2f},"
                  f"{np.percentile(lat, 99):.2f},{qps:.0f},{parity}",
                  flush=True)
            if parity != "ok":
                raise SystemExit(f"frontend invariant violated at "
                                 f"D={devices} rate={rate:.0f}: {parity}")
        if mult > 1.0:          # above sync capacity: coalescing MUST win
            sync_p99, sync_qps = rows["sync"]
            for policy, (p99, qps) in rows.items():
                if policy != "sync" and not (p99 < sync_p99
                                             and qps > sync_qps):
                    raise SystemExit(
                        f"coalescing lost to sync at {mult:.1f}x capacity "
                        f"(D={devices}, {policy}: p99 {p99:.2f} vs "
                        f"{sync_p99:.2f} ms, qps {qps:.0f} vs "
                        f"{sync_qps:.0f})")


def main(quick: bool = False) -> None:
    import jax

    n = 2048 if quick else 8192
    n_req = 150 if quick else 400
    rate_mults = [1.5, 3.0] if quick else [0.7, 1.5, 3.0]
    batches = [8, 32]
    for d, reqs in [(1, n_req), (4, 100 if quick else n_req)]:
        if d > jax.device_count():
            print(f"frontend: {d},skipped,{jax.device_count()} devices",
                  flush=True)
            continue
        leg(d, n, reqs, rate_mults, batches)


if __name__ == "__main__":
    import sys

    main(quick="--quick" in sys.argv)
