#!/usr/bin/env python3
"""Smoke run of the DPLR corpus ranking server on a TPU.

    python3 chip_smoke.py [--seed 0]          # one chip
    python3 chip_smoke.py --chips 4           # the four-chip sharded path

Drives the path a user serves through — ``CorpusRankingEngine`` with the
Pallas scorer (``dplr_corpus_score``) behind ``QueryFrontend``, the
objects ``launch/serve.py`` builds — at the paper's deployed width
(``dplr-fwfm --config full``: 44 context and 38 item fields, k = 16,
rank 3, about 3.3e7 embedding rows) with random weights from ``--seed``.

One chip, in phases that each fail the script on error:
  1. device — print the devices; anything but a TPU exits non-zero with
     no result line (there is no CPU path);
  2. build  — full-width params, a corpus of 8192 items (fig1's largest
     auction) in a slab of capacity 8192;
  3. warmup — the frontend's (Bq x K) grid plus full-mode scores at
     Bq = 1 and 16, with the compilation-cache entries before and after
     and the cache's hits and misses; then requests with K in
     1..16 in bursts of mixed size;
  4. churn + refresh — remove, add and update a few items, install a new
     model snapshot, serve again;
  5. asserts — ``kernel_degraded`` is False, ``trace_count`` did not move
     after warmup, every returned slot is live, every warmup compile
     either hit the compilation cache or was written to it, and sampled
     top-Ks agree (``topk_agrees``) with a float64 NumPy oracle computed
     from the weights and the ids in each slot (``Oracle``).
``--chips 4`` runs only the sharded slab (capacity 32768, four shards)
behind the frontend, each reply checked against a single-device engine
over the same corpus and against the oracle.  Request latencies printed
are a smoke reading, not a benchmark.  The last line of stdout is
``{"ok": true, "device": {...}}``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

N_ITEMS = 8192              # fig1's largest auction size
SHARDED_CAPACITY = 32768    # four shards of 8192
MAX_BATCH = 16
MAX_K = 16
EPS32 = float(np.finfo(np.float32).eps)


def _ids(layout, kind: str, n: int, rng) -> np.ndarray:
    """(n, slots) Zipfian local ids for one side of the layout — the
    synthetic generator's id law, drawn without its dense teacher tables
    (which at full width would be gigabytes of host memory)."""
    cols = [(rng.zipf(1.3, n) - 1) % layout.fields[f].vocab_size
            for f in layout.slot_to_field[layout.slots_of(kind)]]
    return np.stack(cols, axis=1).astype(np.int32)


def _require(ok: bool, what: str) -> None:
    if not ok:
        raise AssertionError(what)


def _hbm_bytes() -> int | None:
    import jax
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("bytes_in_use")


class Oracle:
    """float64 NumPy scores of every slab slot, from the model's weights
    and the ids this script put in each slot — apart from the program's
    item rows, churn writes, context cache and scorer.  Embeddings and
    linear weights are gathered on the host from one copy of the arena
    (arena row = the field's offset + the local id; every weight is 1);
    the model is FwFM with R = U^T diag(e) U - diag(U^T diag(e) U):

        score = bias + lin_C + lin_I
                + 0.5 (sum_f d_f |v_f|^2 + sum_r e_r |sum_f U_rf v_f|^2)

    Per slot the tolerance is f32 epsilon x the longest reduction (the
    context projection over m_C fields, the item projection over m_I,
    rho*k squared terms, and the addends) x the same expression over the
    absolute values of every term.  Dead slots score -inf."""

    def __init__(self, cfg, params, item_ids):
        self.layout = cfg.layout
        self.ids = np.array(item_ids)
        self.live = np.ones(len(self.ids), bool)
        self.set_params(params)

    def set_params(self, params) -> None:
        f64 = lambda a: np.asarray(a, np.float64)          # noqa: E731
        self.emb = np.asarray(params["embedding"])
        self.lin = np.asarray(params["linear"]).reshape(-1)
        self.U, self.e = f64(params["U"]), f64(params["e"])
        self.bias = float(params["bias"])
        self.d = -np.einsum("r,rm,rm->m", self.e, self.U, self.U)
        self._items = None

    def write(self, slots, ids) -> None:
        """``ids`` now sit in ``slots``, live."""
        self.ids[slots] = ids
        self.live[slots] = True
        self._items = None

    def remove(self, slots) -> None:
        self.live[slots] = False

    def _side(self, kind: str, ids):
        """float64 (P, |P|, s, |s|, lin) of one side's rows: the rank-space
        projection sum_f U_rf v_f, its d-term sum_f d_f |v_f|^2, the
        first-order term, with the absolute-value bounds."""
        lay = self.layout
        fields = (lay.context_field_idx if kind == "context"
                  else lay.item_field_idx)
        col = {int(f): j for j, f in enumerate(fields)}
        V = np.zeros((len(ids), len(fields), self.emb.shape[1]))
        lin = np.zeros(len(ids))
        for j, slot in enumerate(lay.slots_of(kind)):
            f = int(lay.slot_to_field[slot])
            rows = ids[:, j].astype(np.int64) + int(lay.field_offsets[f])
            V[:, col[f]] += self.emb[rows]
            lin += self.lin[rows]
        U, d = self.U[:, fields], self.d[fields]
        sq = (V * V).sum(-1)
        return (np.einsum("rm,nmk->nrk", U, V),
                np.einsum("rm,nmk->nrk", np.abs(U), np.abs(V)),
                sq @ d, sq @ np.abs(d), lin)

    def scores(self, ctx_ids):
        """((Bq, slots) scores, (Bq, slots) tolerances) for contexts."""
        if self._items is None:
            self._items = self._side("item", self.ids)
        QI, QIa, tI, tIa, linI = self._items
        PC, PCa, sC, sCa, linC = self._side("context", np.asarray(ctx_ids))
        e = self.e
        pair = ((PC[:, None] + QI[None]) ** 2).sum(-1) @ e
        pair_abs = ((PCa[:, None] + QIa[None]) ** 2).sum(-1) @ np.abs(e)
        score = self.bias + linC[:, None] + linI[None] + 0.5 * (
            sC[:, None] + tI[None] + pair)
        absum = (abs(self.bias) + np.abs(linC)[:, None] + np.abs(linI)[None]
                 + 0.5 * (sCa[:, None] + tIa[None] + pair_abs))
        rho, k = QI.shape[1:]
        length = (self.layout.n_context + self.layout.n_item + rho * k + 8)
        tol = EPS32 * length * absum
        return np.where(self.live[None], score, -np.inf), tol


def topk_agrees(vals, idx, score, tol) -> str | None:
    """One row's top-K against the oracle.  Values match the oracle's
    scores of the returned slots within tolerance; the index set equals
    the oracle's top-K except for slots whose oracle score is within
    tolerance of the oracle's K-th score.  Returns the reason it fails."""
    K = len(idx)
    order = np.argsort(-score, kind="stable")
    kth = score[order[K - 1]]
    band = tol.max()
    miss = np.abs(vals - score[idx]) - tol[idx]
    if (miss > 0).any():
        j = int(np.argmax(miss))
        return (f"value {vals[j]!r} of slot {idx[j]} is {miss[j] + tol[idx[j]]:.3e}"
                f" from the oracle's {score[idx[j]]!r}, tolerance "
                f"{tol[idx[j]]:.3e}")
    if not np.all(np.diff(vals) <= 0):
        return "row not sorted best-first"
    for i in set(idx.tolist()) ^ set(order[:K].tolist()):
        if abs(score[i] - kth) > band:
            return f"slot {i} differs from the oracle's top-{K} off a tie"
    return None


def _serve(fe, ctxs, ks, bursts):
    """Submit ``ctxs`` in bursts (each flushed as one or more micro-
    batches); returns [(pending, latency_s)]."""
    out, s = [], 0
    for b in bursts:
        t0 = time.perf_counter()
        pend = [fe.submit(ctxs[j], k=int(ks[j])) for j in range(s, s + b)]
        fe.flush()
        for p in pend:
            p.result()
        out += [(p, p.done_time - t0) for p in pend]
        s += b
    return out


def _check(engine, ref, ctxs, served, sample) -> int:
    """Liveness of every reply, oracle agreement of ``sample`` of them."""
    for p, _ in served:
        _, slots = p.result()
        if not engine.is_live(slots).all():
            raise AssertionError(f"reply surfaced a dead slot: {slots}")
    score, tol = ref.scores(ctxs[sample])
    for row, j in enumerate(sample):
        vals, slots = served[j][0].result()
        why = topk_agrees(np.asarray(vals, np.float64), np.asarray(slots),
                          score[row], tol[row])
        if why:
            raise AssertionError(f"request {j}: {why}")
    return len(sample)


def one_chip(cfg, seed: int, n_items: int, cache_dir: str,
             n_requests: int = 32) -> None:
    import jax
    import jax.numpy as jnp

    from repro.launch.compile_cache import cache_counts, cache_entries
    from repro.models.recsys import fwfm
    from repro.serving import CorpusRankingEngine, QueryFrontend

    rng = np.random.default_rng(seed)
    t0 = time.perf_counter()
    params = fwfm.init(jax.random.PRNGKey(seed), cfg)
    items = _ids(cfg.layout, "item", n_items, rng)
    engine = CorpusRankingEngine(cfg, items, capacity=n_items,
                                 use_pallas_kernel=True)
    engine.refresh(params, step=0)
    jax.block_until_ready(engine.cache)
    print(f"build: {time.perf_counter() - t0:.3f} s, {n_items} items, "
          f"capacity {engine.capacity}, hbm bytes_in_use={_hbm_bytes()}")
    ref = Oracle(cfg, params, items)

    ctxs = _ids(cfg.layout, "context", 2 * n_requests, rng)
    fe = QueryFrontend(engine, max_batch=MAX_BATCH, max_k=MAX_K,
                       auto_pump=False)
    before, (hits0, misses0) = cache_entries(cache_dir), cache_counts()
    t0 = time.perf_counter()
    dispatches = fe.warmup(ctxs[0])
    for bq in (1, MAX_BATCH):
        jax.block_until_ready(engine.score(ctxs[:bq]))
    warm_s = time.perf_counter() - t0
    after, (hits, misses) = cache_entries(cache_dir), cache_counts()
    hits, misses = hits - hits0, misses - misses0
    written = len(after - before)
    traced = engine.trace_count
    print(f"warmup: {dispatches} grid dispatches + full-mode Bq=1,16 in "
          f"{warm_s:.3f} s, {traced} traces, compilation cache {cache_dir}: "
          f"{len(before)} -> {len(after)} entries ({written} written), "
          f"{hits} hits, {misses} misses")

    ks = rng.integers(1, MAX_K + 1, 2 * n_requests)
    bursts = [1, 3, 16, 5, 2, 4, 1]                  # 32 requests
    served = _serve(fe, ctxs, ks, bursts)
    lat = [t for _, t in served]
    checked = _check(engine, ref, ctxs, served,
                     list(range(0, n_requests, 3)))
    print(f"serve: {len(served)} requests in bursts {bursts}, "
          f"{fe.stats['dispatches']} dispatches; smoke reading (not a "
          f"benchmark): median request latency {np.median(lat) * 1e3:.3f} "
          f"ms; oracle agreement on {checked} sampled requests")

    live = engine.valid_slots
    gone = live[rng.choice(len(live), 4, replace=False)]
    engine.remove_items(gone)
    ref.remove(gone)
    new = _ids(cfg.layout, "item", 4, rng)
    added = engine.add_items(new)
    _require(added.max() < len(ref.live) and not ref.live[added].any(),
             f"add_items returned slots {added} that were not free")
    ref.write(added, new)
    upd = np.setdiff1d(live, gone)[:3]
    new = _ids(cfg.layout, "item", 3, rng)
    engine.update_items(upd, new)
    ref.write(upd, new)
    bumped = dict(params)
    bumped["bias"] = params["bias"] + jnp.asarray(0.5, params["bias"].dtype)
    t0 = time.perf_counter()
    engine.refresh(bumped, step=1)
    jax.block_until_ready(engine.cache)
    refresh_s = time.perf_counter() - t0
    ref.set_params(bumped)
    served2 = _serve(fe, ctxs[n_requests:], ks[n_requests:], [8, 1, 7])
    checked2 = _check(engine, ref, ctxs[n_requests:], served2,
                      list(range(len(served2))))
    print(f"churn: removed {gone.tolist()}, added into {added.tolist()}, "
          f"updated {upd.tolist()}; refresh {refresh_s:.3f} s; "
          f"{len(served2)} more requests, oracle agreement on {checked2}")

    _require(not engine.kernel_degraded, "kernel path degraded to jnp")
    _require(engine.trace_count == traced,
             f"retraced after warmup: {traced} -> {engine.trace_count}")
    _require(hits + misses > 0, "warmup never consulted the compilation "
             "cache")
    _require(written >= misses, f"{misses} warmup compiles missed the "
             f"cache but only {written} entries were written")
    print(f"asserts: kernel_degraded=False, retraces after warmup=0, "
          f"all returned slots live, every warmup compile hit the "
          f"compilation cache ({hits}) or was written to it ({misses})")


def four_chips(cfg, seed: int, capacity: int, n_requests: int = 24) -> None:
    import jax

    from repro.launch.mesh import make_host_mesh
    from repro.models.recsys import fwfm
    from repro.serving import CorpusRankingEngine, QueryFrontend
    from repro.serving.corpus import next_pow2

    rng = np.random.default_rng(seed)
    t0 = time.perf_counter()
    params = fwfm.init(jax.random.PRNGKey(seed), cfg)
    items = _ids(cfg.layout, "item", capacity, rng)
    sharded = CorpusRankingEngine(cfg, items, capacity=capacity,
                                  mesh=make_host_mesh(model=4),
                                  use_pallas_kernel=True)
    single = CorpusRankingEngine(cfg, items, capacity=capacity,
                                 use_pallas_kernel=True)
    sharded.refresh(params, step=0)
    single.refresh(params, step=0)
    jax.block_until_ready((sharded.cache, single.cache))
    print(f"build: {time.perf_counter() - t0:.3f} s, capacity {capacity} "
          f"in {sharded.n_shards} shards of {sharded.local_capacity}")
    ref = Oracle(cfg, params, items)

    ctxs = _ids(cfg.layout, "context", n_requests, rng)
    fe = QueryFrontend(sharded, max_batch=MAX_BATCH, max_k=MAX_K,
                       auto_pump=False)
    t0 = time.perf_counter()
    fe.warmup(ctxs[0])
    traced = sharded.trace_count
    print(f"warmup: {time.perf_counter() - t0:.3f} s, {traced} traces")
    ks = rng.integers(1, MAX_K + 1, n_requests)
    served = _serve(fe, ctxs, ks, [1, 8, 4, 11])
    exact = 0
    for j, (p, _) in enumerate(served):
        sv, si = p.result()
        # the single-device engine answers at the K bucket (one compile
        # per bucket); truncating a top-K row is the top-K' row
        rv, ri = (np.asarray(a)[0, :ks[j]] for a in single.topk(
            ctxs[j:j + 1], next_pow2(int(ks[j]))))
        exact += int(np.array_equal(si, ri) and np.array_equal(sv, rv))
        score, tol = ref.scores(ctxs[j:j + 1])
        for name, v, i in (("sharded", sv, si), ("single", rv, ri)):
            why = topk_agrees(np.asarray(v, np.float64), np.asarray(i),
                              score[0], tol[0])
            if why:
                raise AssertionError(f"request {j} ({name}): {why}")
        if not sharded.is_live(si).all():
            raise AssertionError(f"request {j}: dead slot in {si}")
    lat = [t for _, t in served]
    _require(not (sharded.kernel_degraded or single.kernel_degraded),
             "kernel path degraded to jnp")
    _require(sharded.trace_count == traced, "sharded engine retraced")
    print(f"sharded vs single-device top-K: {len(served)} requests agree "
          f"within the oracle tolerance ({exact} bit-identical); smoke "
          f"reading (not a benchmark): median request latency "
          f"{np.median(lat) * 1e3:.3f} ms; kernel_degraded=False, "
          f"retraces after warmup=0")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    args = ap.parse_args(argv)

    from repro.launch.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()

    import jax

    from repro.configs.dplr_fwfm import make_config

    devs = jax.devices()
    dev = devs[0]
    print(f"devices: {devs}")
    print(f"device: platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devs)}", flush=True)
    if dev.platform != "tpu":
        print("chip_smoke: JAX found no TPU", file=sys.stderr)
        return 1
    if len(devs) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} devices",
              file=sys.stderr)
        return 1
    cfg = make_config()
    if args.chips == 4:
        four_chips(cfg, args.seed, SHARDED_CAPACITY)
    else:
        one_chip(cfg, args.seed, N_ITEMS, cache_dir)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
