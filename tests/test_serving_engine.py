"""Corpus-precomputation serving engine + dplr_corpus_score kernel:
numeric parity (atol 1e-5) against the per-query Algorithm 1 path, fused
top-K vs argsort, checkpoint-refresh without scorer retrace, and the
mutable-corpus churn suite (add/remove/update vs from-scratch rebuild
oracle — bit-exact; masked top-K never surfaces a dead slot; zero scorer
retraces across churn + refresh; corrupt-newest-checkpoint regression)."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import ranking as rk
from repro.core.dplr import DPLRParams, dplr_diagonal
from repro.core.fields import uniform_layout
from repro.data.synthetic_ctr import SyntheticCTR
from repro.embedding.bag import (item_arena_ids, lookup_item_embeddings)
from repro.kernels import ops, ref
from repro.models.recsys import fwfm
from repro.serving import CorpusRankingEngine, build_corpus_cache


def _setup(nC=5, nI=4, vocab=50, k=8, rho=2, n=37, seed=0):
    layout = uniform_layout(nC, nI, vocab)
    cfg = fwfm.FwFMConfig(layout=layout, embed_dim=k, interaction="dplr",
                          rank=rho)
    params = fwfm.init(jax.random.PRNGKey(seed), cfg)
    data = SyntheticCTR(layout, embed_dim=4, seed=seed)
    q = {k_: jnp.asarray(v) for k_, v in data.ranking_query(n, seed).items()}
    return layout, cfg, params, data, q


def _batched_query(data, q, Bq, n):
    """Bq distinct contexts against q's item corpus."""
    ctxs = [jnp.asarray(data.ranking_query(n, 100 + b)["context_ids"])
            for b in range(Bq)]
    ctx = jnp.concatenate(ctxs, 0)
    return {
        "context_ids": ctx,
        "context_weights": jnp.ones(ctx.shape, jnp.float32),
        "item_ids": jnp.broadcast_to(q["item_ids"][0],
                                     (Bq, *q["item_ids"].shape[1:])),
        "item_weights": jnp.broadcast_to(q["item_weights"][0],
                                         (Bq, *q["item_weights"].shape[1:])),
    }


# ---------------------------------------------------------------------------
# Corpus cache + engine parity vs the per-query Algorithm 1 path
# ---------------------------------------------------------------------------

def test_corpus_cache_matches_per_query_projection():
    layout, cfg, params, data, q = _setup()
    cache = build_corpus_cache(params, cfg, q["item_ids"][0],
                               q["item_weights"][0])
    V_I = lookup_item_embeddings(params["embedding"], layout,
                                 q["item_ids"][0], q["item_weights"][0])
    p = DPLRParams(params["U"], params["e"])
    nC = layout.n_context
    want_Q = jnp.einsum("rm,nmk->nrk", p.U[:, nC:], V_I)
    np.testing.assert_allclose(cache.Q_I, want_Q, atol=1e-6)
    d = dplr_diagonal(p)
    want_t = jnp.einsum("nmk,m->n", V_I * V_I, d[nC:])
    np.testing.assert_allclose(cache.t_I, want_t, atol=1e-6)


@pytest.mark.parametrize("Bq", [1, 3])
def test_engine_score_equals_rank_items(Bq):
    _, cfg, params, data, q = _setup(n=37)
    qb = _batched_query(data, q, Bq, 37)
    want = fwfm.rank_items(params, cfg, qb)
    engine = CorpusRankingEngine(cfg, q["item_ids"][0], q["item_weights"][0])
    engine.refresh(params, step=0)
    got = engine.score(qb["context_ids"], qb["context_weights"])
    # slab rounds 37 items up to a power-of-two capacity; padding slots are
    # dead and pinned to exactly the mask sentinel
    assert engine.capacity == 64 and engine.n_items == 37
    assert got.shape == (Bq, 64)
    np.testing.assert_allclose(got[:, :37], want, atol=1e-5)
    assert np.all(np.asarray(got)[:, 37:] == -1e30)


@pytest.mark.parametrize("Bq", [1, 2])
def test_engine_pallas_kernel_equals_rank_items(Bq):
    """Kernel path (interpret mode), non-divisible block_n."""
    _, cfg, params, data, q = _setup(n=37)
    qb = _batched_query(data, q, Bq, 37)
    want = fwfm.rank_items(params, cfg, qb)
    engine = CorpusRankingEngine(cfg, q["item_ids"][0], q["item_weights"][0],
                                 use_pallas_kernel=True, block_n=16)
    engine.refresh(params)
    got = engine.score(qb["context_ids"], qb["context_weights"])
    np.testing.assert_allclose(got[:, :37], want, atol=1e-5)
    assert np.all(np.asarray(got)[:, 37:] == -1e30)


def test_engine_topk_matches_full_scores():
    _, cfg, params, data, q = _setup(n=37)
    qb = _batched_query(data, q, 2, 37)
    engine = CorpusRankingEngine(cfg, q["item_ids"][0], q["item_weights"][0])
    engine.refresh(params)
    full = np.asarray(engine.score(qb["context_ids"],
                                   qb["context_weights"]))[:, :37]
    vals, idx = engine.topk(qb["context_ids"], 5, qb["context_weights"])
    want_idx = np.argsort(-full, axis=1)[:, :5]
    np.testing.assert_array_equal(np.asarray(idx), want_idx)
    np.testing.assert_allclose(np.asarray(vals),
                               np.take_along_axis(full, want_idx, 1),
                               atol=1e-6)


# ---------------------------------------------------------------------------
# dplr_corpus_score kernel vs jnp oracle and vs rk.dplr_score_items
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n,rho,k,Bq,block_n", [
    (64, 2, 8, 1, 32),
    (1000, 3, 16, 4, 256),      # non-divisible n -> padding path
    (130, 5, 16, 2, 64),
    (300, 3, 16, 16, 128),      # lane-width tiles, ragged last tile
    (700, 5, 16, 3, 256),
])
def test_corpus_score_kernel_vs_ref(rng, n, rho, k, Bq, block_n):
    Q = jnp.asarray(rng.standard_normal((n, rho, k), dtype=np.float32))
    a_I = jnp.asarray(rng.standard_normal(n).astype(np.float32))
    e = jnp.asarray(rng.standard_normal(rho).astype(np.float32))
    PC = jnp.asarray(rng.standard_normal((Bq, rho, k), dtype=np.float32))
    a_C = jnp.asarray(rng.standard_normal(Bq).astype(np.float32))
    out = ops.dplr_corpus_score(Q, a_I, e, PC, a_C, block_n=block_n)
    want = ref.dplr_corpus_score_ref(Q, a_I, e, PC, a_C)
    np.testing.assert_allclose(out, want, atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("n,block_n,K,rho,k", [
    pytest.param(100, 32, 7, 3, 8, id="100-32-7"),  # padding, K ragged
    pytest.param(256, 64, 16, 3, 8, id="256-64-16"),
    pytest.param(300, 128, 16, 3, 16, id="300-128-16"),  # lane-width
    pytest.param(600, 256, 16, 5, 16, id="600-256-16-rho5"),
])
def test_corpus_score_kernel_topk_vs_argsort(rng, n, block_n, K, rho, k):
    Bq = 3
    Q = jnp.asarray(rng.standard_normal((n, rho, k), dtype=np.float32))
    a_I = jnp.asarray(rng.standard_normal(n).astype(np.float32))
    e = jnp.asarray(rng.standard_normal(rho).astype(np.float32))
    PC = jnp.asarray(rng.standard_normal((Bq, rho, k), dtype=np.float32))
    a_C = jnp.asarray(rng.standard_normal(Bq).astype(np.float32))
    vals, idx = ops.dplr_corpus_score(Q, a_I, e, PC, a_C, topk=K,
                                      block_n=block_n)
    want_v, want_i = ref.dplr_corpus_topk_ref(Q, a_I, e, PC, a_C, K)
    np.testing.assert_allclose(vals, want_v, atol=1e-5, rtol=1e-5)
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(want_i))


def _merge_case(rng, n, rho=3, k=8, Bq=3, dup=0):
    """Kernel inputs with the first ``dup`` rows of Q/a_I repeated through
    the slab (exact score ties across tiles)."""
    Q = rng.standard_normal((n, rho, k), dtype=np.float32)
    a_I = rng.standard_normal(n).astype(np.float32)
    if dup:
        Q = Q[np.arange(n) % dup]
        a_I = a_I[np.arange(n) % dup]
    e = rng.standard_normal(rho).astype(np.float32)
    PC = rng.standard_normal((Bq, rho, k), dtype=np.float32)
    a_C = rng.standard_normal(Bq).astype(np.float32)
    return [jnp.asarray(x) for x in (Q, a_I, e, PC, a_C)]


@pytest.mark.parametrize("case", ["ties", "K=block_n+1", "K=live"])
def test_corpus_topk_merge_matches_lax_top_k(rng, case):
    """The in-kernel selection merge keeps ``lax.top_k``'s contract over
    the slab: rows best-first, equal scores to the LOWEST slot, dead slots
    never ahead of a live one — checked against ``lax.top_k`` of the
    kernel's own full-mode scores (same per-item arithmetic, so exact)."""
    block_n = 8
    n, K, valid = 64, 12, None
    if case == "ties":                  # 5 distinct rows repeated 13 times
        args = _merge_case(rng, n, dup=5)
        valid = jnp.asarray(np.arange(n) % 7 != 3)
    elif case == "K=block_n+1":
        args = _merge_case(rng, n)
        K = block_n + 1
    else:                               # every live slot, none dead shown
        args = _merge_case(rng, n)
        live = rng.random(n) < 0.3
        valid = jnp.asarray(live)
        K = int(live.sum())
    full = ops.dplr_corpus_score(*args, valid, block_n=block_n)
    want_v, want_i = jax.lax.top_k(full, K)
    vals, idx = ops.dplr_corpus_score(*args, valid, topk=K, block_n=block_n)
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(want_i))
    np.testing.assert_array_equal(np.asarray(vals), np.asarray(want_v))
    if valid is not None:
        assert np.asarray(valid)[np.asarray(idx)].all()


def test_corpus_kernel_consistent_with_algorithm1(rng):
    """Corpus kernel == rk.dplr_score_items on a real DPLR parameterization
    (pairwise term only: a_I = 0.5 t_I, a_C = 0.5 s_C)."""
    m, nC, k, rho, n = 12, 7, 8, 3, 100
    from repro.core.dplr import init_dplr
    p = init_dplr(jax.random.PRNGKey(0), m, rho)
    V_C = jnp.asarray(rng.standard_normal((1, nC, k), dtype=np.float32))
    V_I = jnp.asarray(rng.standard_normal((1, n, m - nC, k), dtype=np.float32))
    cache = rk.dplr_context_cache(p, V_C, nC)
    want = rk.dplr_score_items(p, cache, V_I, nC)
    d = dplr_diagonal(p)
    Q_I = jnp.einsum("rm,nmk->nrk", p.U[:, nC:], V_I[0])
    t_I = jnp.einsum("nmk,m->n", V_I[0] * V_I[0], d[nC:])
    got = ops.dplr_corpus_score(Q_I, 0.5 * t_I, p.e, cache.P_C,
                                0.5 * cache.s_C, block_n=64)
    np.testing.assert_allclose(got, want, atol=1e-5, rtol=1e-4)


# ---------------------------------------------------------------------------
# Checkpoint refresh: cache rebuilds, jitted scorer does not retrace
# ---------------------------------------------------------------------------

def test_engine_checkpoint_refresh_no_retrace(tmp_path):
    from repro.checkpoint import CheckpointManager

    _, cfg, params, data, q = _setup(n=20)
    engine = CorpusRankingEngine(cfg, q["item_ids"][0], q["item_weights"][0])
    engine.refresh(params, step=0)
    s0 = engine.score(q["context_ids"], q["context_weights"])
    assert engine.trace_count == 1

    mgr = CheckpointManager(str(tmp_path))
    bumped = dict(params)
    bumped["bias"] = params["bias"] + 2.0
    mgr.save({"params": bumped}, step=1, blocking=True)

    assert engine.maybe_refresh(mgr, {"params": params},
                                select=lambda t: t["params"])
    assert engine.model_step == 1 and engine.refresh_count == 2
    s1 = engine.score(q["context_ids"], q["context_weights"])
    # model changed -> scores changed (by exactly the bias bump) ...
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s0) + 2.0,
                               atol=1e-5)
    # ... but the jitted scorer was NOT retraced, let alone restarted
    assert engine.trace_count == 1
    # idempotent: same step -> no refresh
    assert not engine.maybe_refresh(mgr, {"params": params},
                                    select=lambda t: t["params"])


# ---------------------------------------------------------------------------
# Mutable corpus: churn parity vs from-scratch rebuild oracle (bit-exact),
# masked top-K, zero retraces, slab doubling
# ---------------------------------------------------------------------------

def _churned_engine(cfg, params, data, q, **kw):
    """Engine after a representative add/remove/update sequence."""
    engine = CorpusRankingEngine(cfg, q["item_ids"][0], q["item_weights"][0],
                                 capacity=32, **kw)
    engine.refresh(params, step=0)
    added = engine.add_items(data.ranking_query(7, 90)["item_ids"][0])
    engine.remove_items([1, 3, 5, int(added[0]), int(added[3])])
    upd = data.ranking_query(4, 91)
    engine.update_items([0, 2, int(added[1]), int(added[6])],
                        upd["item_ids"][0], upd["item_weights"][0])
    engine.add_items(data.ranking_query(3, 92)["item_ids"][0])
    return engine


def _rebuild_oracle(cfg, params, engine, **kw):
    """From-scratch engine over exactly the live items, in slot order."""
    live = engine.valid_slots
    oracle = CorpusRankingEngine(cfg, engine._slab_ids[live],
                                 engine._slab_w[live],
                                 capacity=engine.capacity, **kw)
    oracle.refresh(params, step=0)
    return live, oracle


@pytest.mark.parametrize("use_pallas", [False, True])
def test_churn_matches_rebuild_oracle_bit_exact(use_pallas):
    _, cfg, params, data, q = _setup(n=20)
    kw = dict(use_pallas_kernel=use_pallas, block_n=8) if use_pallas else {}
    engine = _churned_engine(cfg, params, data, q, **kw)
    live, oracle = _rebuild_oracle(cfg, params, engine, **kw)
    got = np.asarray(engine.score(q["context_ids"], q["context_weights"]))
    want = np.asarray(oracle.score(q["context_ids"], q["context_weights"]))
    # delta-scattered rows == from-scratch rows, BIT-exact (same jitted row
    # math, corpus.corpus_rows, reached through a different batch shape)
    np.testing.assert_array_equal(got[:, live], want[:, :len(live)])
    # dead slots are pinned to exactly the mask sentinel
    dead = ~engine._valid_np
    assert np.all(got[:, dead] == -1e30)


@pytest.mark.parametrize("use_pallas", [False, True])
def test_masked_topk_never_returns_dead_slot(use_pallas):
    _, cfg, params, data, q = _setup(n=20)
    kw = dict(use_pallas_kernel=use_pallas, block_n=8) if use_pallas else {}
    engine = _churned_engine(cfg, params, data, q, **kw)
    live, oracle = _rebuild_oracle(cfg, params, engine, **kw)
    K = engine.n_items          # every live item — the hardest mask case
    vals, idx = engine.topk(q["context_ids"], K, q["context_weights"])
    idx = np.asarray(idx)
    assert engine._valid_np[idx.ravel()].all(), "top-K surfaced a dead slot"
    # matches the oracle's top-K item-for-item, bit-exact values
    ov, oi = oracle.topk(q["context_ids"], K, q["context_weights"])
    np.testing.assert_array_equal(np.asarray(vals), np.asarray(ov))
    for row in idx:                 # each row is a permutation of live
        np.testing.assert_array_equal(np.sort(row), live)
    # K beyond the live count must be refused (would have to surface a
    # dead slot)
    with pytest.raises(ValueError):
        engine.topk(q["context_ids"], engine.n_items + 1,
                    q["context_weights"])


@pytest.mark.parametrize("use_pallas", [False, True])
def test_slab_doubling_preserves_slots_and_parity(use_pallas):
    _, cfg, params, data, q = _setup(n=20)
    kw = dict(use_pallas_kernel=use_pallas, block_n=8) if use_pallas else {}
    engine = CorpusRankingEngine(cfg, q["item_ids"][0], q["item_weights"][0],
                                 capacity=32, **kw)
    engine.refresh(params, step=0)
    s_before = np.asarray(engine.score(q["context_ids"],
                                       q["context_weights"]))
    slots = engine.add_items(data.ranking_query(20, 77)["item_ids"][0])
    assert engine.capacity == 64 and engine.n_items == 40
    assert list(slots[:12]) == list(range(20, 32))   # filled the old slab
    got = np.asarray(engine.score(q["context_ids"], q["context_weights"]))
    # pre-existing slots kept their rows bit-for-bit across the doubling
    np.testing.assert_array_equal(got[:, :20], s_before[:, :20])
    live, oracle = _rebuild_oracle(cfg, params, engine, **kw)
    want = np.asarray(oracle.score(q["context_ids"], q["context_weights"]))
    np.testing.assert_array_equal(got[:, live], want[:, :len(live)])
    vals, idx = engine.topk(q["context_ids"], 40, q["context_weights"])
    assert engine._valid_np[np.asarray(idx).ravel()].all()


def test_trace_count_flat_across_churn_and_refresh(tmp_path):
    from repro.checkpoint import CheckpointManager

    _, cfg, params, data, q = _setup(n=20)
    engine = CorpusRankingEngine(cfg, q["item_ids"][0], q["item_weights"][0],
                                 capacity=64)
    engine.refresh(params, step=0)
    engine.score(q["context_ids"], q["context_weights"])
    assert engine.trace_count == 1
    rng = np.random.default_rng(0)
    for s in range(30):
        kind = s % 3
        if kind == 0 and engine.n_items + 4 <= engine.capacity:
            engine.add_items(data.ranking_query(4, 200 + s)["item_ids"][0])
        elif kind == 1 and engine.n_items > 10:
            engine.remove_items(rng.choice(engine.valid_slots, 3,
                                           replace=False))
        else:
            upd = data.ranking_query(2, 300 + s)
            engine.update_items(rng.choice(engine.valid_slots, 2,
                                           replace=False),
                                upd["item_ids"][0], upd["item_weights"][0])
        engine.score(q["context_ids"], q["context_weights"])
    # mid-stream model refresh: in-place rebuild, slots preserved
    mgr = CheckpointManager(str(tmp_path))
    bumped = dict(params)
    bumped["bias"] = params["bias"] + 1.0
    mgr.save({"params": bumped}, step=1, blocking=True)
    assert engine.maybe_refresh(mgr, {"params": params},
                                select=lambda t: t["params"])
    engine.score(q["context_ids"], q["context_weights"])
    assert engine.trace_count == 1, \
        f"scorer retraced under churn/refresh ({engine.trace_count})"


def test_mutation_argument_validation():
    _, cfg, params, data, q = _setup(n=20)
    engine = CorpusRankingEngine(cfg, q["item_ids"][0], q["item_weights"][0],
                                 capacity=32)
    with pytest.raises(RuntimeError):     # no model installed yet
        engine.add_items(q["item_ids"][0][:1])
    engine.refresh(params)
    with pytest.raises(ValueError):       # slot 25 was never filled
        engine.remove_items([25])
    engine.remove_items([4])
    with pytest.raises(ValueError):       # already dead
        engine.update_items([4], q["item_ids"][0][:1])
    with pytest.raises(ValueError):       # duplicate slots
        engine.remove_items([2, 2])
    with pytest.raises(ValueError):       # 2 slots, 1 payload row: would
        engine.update_items([1, 2], q["item_ids"][0][:1])  # broadcast
    with pytest.raises(ValueError):       # 2 id rows, 1 weight row
        engine.update_items([1, 2], q["item_ids"][0][:2],
                            q["item_weights"][0][:1])
    with pytest.raises(ValueError):       # same for add_items
        engine.add_items(q["item_ids"][0][:2], q["item_weights"][0][:1])


# ---------------------------------------------------------------------------
# Masked kernel vs oracle (standalone shapes, random mask)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("topk", [None, 7])
def test_corpus_score_kernel_masked_vs_ref(rng, topk):
    n, rho, k, Bq, block_n = 100, 3, 8, 2, 32
    Q = jnp.asarray(rng.standard_normal((n, rho, k), dtype=np.float32))
    a_I = jnp.asarray(rng.standard_normal(n).astype(np.float32))
    e = jnp.asarray(rng.standard_normal(rho).astype(np.float32))
    PC = jnp.asarray(rng.standard_normal((Bq, rho, k), dtype=np.float32))
    a_C = jnp.asarray(rng.standard_normal(Bq).astype(np.float32))
    valid = jnp.asarray(rng.random(n) > 0.4)
    if topk is None:
        out = ops.dplr_corpus_score(Q, a_I, e, PC, a_C, valid,
                                    block_n=block_n)
        want = ref.dplr_corpus_score_ref(Q, a_I, e, PC, a_C, valid)
        np.testing.assert_allclose(out, want, atol=1e-5, rtol=1e-5)
        assert np.all(np.asarray(out)[:, ~np.asarray(valid)] == -1e30)
    else:
        vals, idx = ops.dplr_corpus_score(Q, a_I, e, PC, a_C, valid,
                                          topk=topk, block_n=block_n)
        assert np.asarray(valid)[np.asarray(idx).ravel()].all()
        want_v, want_i = ref.dplr_corpus_topk_ref(Q, a_I, e, PC, a_C, topk,
                                                  valid)
        np.testing.assert_allclose(vals, want_v, atol=1e-5, rtol=1e-5)
        np.testing.assert_array_equal(np.asarray(idx), np.asarray(want_i))


# ---------------------------------------------------------------------------
# Tile-size invariance, accumulation dtype, and the multi-segment kernel
# ---------------------------------------------------------------------------

def _corpus_inputs(rng, n, rho=3, k=8, Bq=2, masked=False):
    Q = jnp.asarray(rng.standard_normal((n, rho, k), dtype=np.float32))
    a_I = jnp.asarray(rng.standard_normal(n).astype(np.float32))
    e = jnp.asarray(rng.standard_normal(rho).astype(np.float32))
    PC = jnp.asarray(rng.standard_normal((Bq, rho, k), dtype=np.float32))
    a_C = jnp.asarray(rng.standard_normal(Bq).astype(np.float32))
    valid = jnp.asarray(rng.random(n) > 0.3) if masked else None
    return Q, a_I, e, PC, a_C, valid


def test_corpus_score_block_n_property_sweep(rng):
    """Tile-size invariance: full scores AND top-K are bit-identical
    across block_n — including tiles LARGER than n (clamped) and a
    non-power-of-two n (ragged last tile)."""
    n, K = 100, 9                        # non-pow2 n
    Q, a_I, e, PC, a_C, valid = _corpus_inputs(rng, n, masked=True)
    ref_full = np.asarray(ops.dplr_corpus_score(Q, a_I, e, PC, a_C, valid,
                                                block_n=n))
    rv, ri = ops.dplr_corpus_score(Q, a_I, e, PC, a_C, valid, topk=K,
                                   block_n=n)
    rv, ri = np.asarray(rv), np.asarray(ri)
    for bn in (7, 32, 64, 100, 128, 4096):   # incl. block_n > n
        out = np.asarray(ops.dplr_corpus_score(Q, a_I, e, PC, a_C, valid,
                                               block_n=bn))
        np.testing.assert_array_equal(out, ref_full,
                                      err_msg=f"block_n={bn}")
        v, i = ops.dplr_corpus_score(Q, a_I, e, PC, a_C, valid, topk=K,
                                     block_n=bn)
        np.testing.assert_array_equal(np.asarray(v), rv,
                                      err_msg=f"block_n={bn}")
        np.testing.assert_array_equal(np.asarray(i), ri,
                                      err_msg=f"block_n={bn}")


def test_corpus_score_acc_dtype(rng):
    """acc_dtype='float32' is byte-identical to the historical kernel;
    bf16 accumulation stays within bf16 tolerance of the f32 oracle."""
    n, K = 256, 8
    Q, a_I, e, PC, a_C, valid = _corpus_inputs(rng, n, masked=True)
    v32, i32 = ops.dplr_corpus_score(Q, a_I, e, PC, a_C, valid, topk=K,
                                     block_n=64, acc_dtype="float32")
    vd, idd = ops.dplr_corpus_score(Q, a_I, e, PC, a_C, valid, topk=K,
                                    block_n=64)
    np.testing.assert_array_equal(np.asarray(v32), np.asarray(vd))
    np.testing.assert_array_equal(np.asarray(i32), np.asarray(idd))
    vb, ib = ops.dplr_corpus_score(Q, a_I, e, PC, a_C, valid, topk=K,
                                   block_n=64, acc_dtype="bfloat16")
    # judge the bf16-selected ITEMS by their f32 scores (rank swaps are
    # allowed only between near-ties the tolerance covers, so compare the
    # sorted score multisets rather than positions)
    full = np.asarray(ref.dplr_corpus_score_ref(Q, a_I, e, PC, a_C, valid))
    got = np.take_along_axis(full, np.asarray(ib), axis=1)
    np.testing.assert_allclose(-np.sort(-got, axis=1), np.asarray(vd),
                               rtol=0, atol=5e-2)
    # the accumulated values themselves carry bf16 rounding across the
    # rho*k reduction — a coarser envelope than the selection gate above
    np.testing.assert_allclose(np.asarray(vb), got, rtol=2e-2, atol=1e-1)


@pytest.mark.parametrize("masked", [False, True])
@pytest.mark.parametrize("ns,rho,k,block_n", [
    pytest.param((64, 64), 3, 8, 32, id="ns0"),
    pytest.param((100, 37, 64), 3, 8, 32, id="ns1"),
    pytest.param((300, 129), 5, 16, 128, id="ns2-rho5-lane"),
])
def test_corpus_score_multi_vs_ref(rng, ns, rho, k, block_n, masked):
    """Multi-segment fused kernel == per-segment oracle, exactly —
    uneven segment sizes, non-pow2 sizes, ragged tiles."""
    Bq, K = 2, 7
    parts = [_corpus_inputs(rng, n, rho, k, Bq, masked) for n in ns]
    Q_parts = tuple(p[0] for p in parts)
    a_parts = tuple(p[1] for p in parts)
    valid_parts = tuple(p[5] for p in parts) if masked else None
    e = jnp.stack([p[2] for p in parts])
    PC = jnp.stack([p[3] for p in parts])
    a_C = jnp.stack([p[4] for p in parts])
    vals, idx = ops.dplr_corpus_score_multi(
        Q_parts, a_parts, valid_parts, e, PC, a_C, topk=K, block_n=block_n)
    want_v, want_i = ref.dplr_corpus_multi_topk_ref(
        Q_parts, a_parts, valid_parts, e, PC, a_C, K)
    np.testing.assert_allclose(np.asarray(vals), np.asarray(want_v),
                               atol=1e-5, rtol=1e-5)
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(want_i))
    # and bit-exact vs S independent single-segment kernel calls
    for s, (Q, a_I, es, PCs, aCs, valid) in enumerate(parts):
        v1, i1 = ops.dplr_corpus_score(Q, a_I, es, PCs, aCs,
                                       valid=valid if masked else None,
                                       topk=K, block_n=block_n)
        np.testing.assert_array_equal(np.asarray(vals)[s], np.asarray(v1))
        np.testing.assert_array_equal(np.asarray(idx)[s], np.asarray(i1))


def test_corpus_score_multi_segment_isolation(rng):
    """A segment's winners can NEVER come from a neighbour segment, even
    when the neighbour's scores dominate by orders of magnitude, and
    returned indices are segment-LOCAL."""
    rho, k, Bq, K = 2, 4, 2, 5
    n0, n1 = 37, 64
    Q0, a0, e0, P0, c0, _ = _corpus_inputs(rng, n0, rho, k, Bq)
    Q1, a1, e1, P1, c1, _ = _corpus_inputs(rng, n1, rho, k, Bq)
    a1 = a1 + 1e6                         # segment 1 dwarfs segment 0
    vals, idx = ops.dplr_corpus_score_multi(
        (Q0, Q1), (a0, a1), None, jnp.stack([e0, e1]),
        jnp.stack([P0, P1]), jnp.stack([c0, c1]), topk=K, block_n=16)
    idx = np.asarray(idx)
    assert (0 <= idx[0]).all() and (idx[0] < n0).all()
    assert (0 <= idx[1]).all() and (idx[1] < n1).all()
    assert np.asarray(vals)[0].max() < 1e5   # no leaked segment-1 score
    v0, i0 = ops.dplr_corpus_score(Q0, a0, e0, P0, c0, topk=K, block_n=16)
    np.testing.assert_array_equal(idx[0], np.asarray(i0))
    np.testing.assert_array_equal(np.asarray(vals)[0], np.asarray(v0))


def test_corpus_score_multi_validates(rng):
    Q, a_I, e, PC, a_C, _ = _corpus_inputs(rng, 32)
    with pytest.raises(ValueError, match=">= 1 segment"):
        ops.dplr_corpus_score_multi((), (), None, e[None], PC[None],
                                    a_C[None], topk=4)
    with pytest.raises(ValueError, match="segment"):
        ops.dplr_corpus_score_multi((Q, Q), (a_I,), None,
                                    jnp.stack([e, e]),
                                    jnp.stack([PC, PC]),
                                    jnp.stack([a_C, a_C]), topk=4)
    with pytest.raises(ValueError, match="topk"):
        ops.dplr_corpus_score_multi((Q,), (a_I,), None, e[None], PC[None],
                                    a_C[None], topk=33)


# ---------------------------------------------------------------------------
# maybe_refresh regression: a corrupt NEWEST checkpoint must cost one
# restore attempt total, not a restore + full cache rebuild per poll
# ---------------------------------------------------------------------------

def test_maybe_refresh_corrupt_newest_no_rebuild_storm(tmp_path):
    import os
    from repro.checkpoint import CheckpointManager

    _, cfg, params, data, q = _setup(n=20)
    engine = CorpusRankingEngine(cfg, q["item_ids"][0], q["item_weights"][0])
    mgr = CheckpointManager(str(tmp_path))
    sel = lambda t: t["params"]
    mgr.save({"params": params}, step=1, blocking=True)
    assert engine.maybe_refresh(mgr, {"params": params}, select=sel)
    assert engine.model_step == 1 and engine.refresh_count == 1

    # a newer checkpoint lands CORRUPT: latest_step(validate=False) sees 2
    # but restore() falls back to valid step 1
    bumped = dict(params)
    bumped["bias"] = params["bias"] + 1.0
    mgr.save({"params": bumped}, step=2, blocking=True)
    newest = os.path.join(str(tmp_path), "step_00000002")
    with open(os.path.join(newest, "arrays.npz"), "wb") as f:
        f.write(b"garbage")

    restores = 0
    orig_restore = mgr.restore

    def counting_restore(*a, **k):
        nonlocal restores
        restores += 1
        return orig_restore(*a, **k)

    mgr.restore = counting_restore
    # the FIRST poll of the corrupt landing surfaces the bad push as a
    # typed RefreshFailed (step + signature attached); the engine keeps
    # serving step 1 and subsequent same-signature polls are silent no-ops
    import pytest
    from repro.serving import RefreshFailed
    with pytest.raises(RefreshFailed) as ei:
        engine.maybe_refresh(mgr, {"params": params}, select=sel)
    assert ei.value.step == 2 and ei.value.signature is not None
    for _ in range(4):
        assert not engine.maybe_refresh(mgr, {"params": params}, select=sel)
    assert restores == 1, f"rebuild storm: {restores} restores for 5 polls"
    assert engine.refresh_count == 1 and engine.model_step == 1
    assert engine.last_refresh_error is not None

    # a restarted trainer RE-SAVES the same step number, now valid: the
    # new manifest mtime changes the step signature, so it must land
    mgr.save({"params": bumped}, step=2, blocking=True)
    assert engine.maybe_refresh(mgr, {"params": params}, select=sel)
    assert engine.model_step == 2 and engine.refresh_count == 2

    # a later VALID step still lands normally
    mgr.save({"params": bumped}, step=3, blocking=True)
    assert engine.maybe_refresh(mgr, {"params": params}, select=sel)
    assert engine.model_step == 3 and engine.refresh_count == 3


def test_maybe_refresh_corrupt_newest_does_not_block_lower_valid_step(
        tmp_path):
    """Corrupt step 7 persists on disk while a restarted trainer lands a
    VALID step 6: the poll signature (which includes the checkpoint
    directory mtime) must change, so step 6 is installed rather than the
    engine serving stale params forever."""
    import os
    from repro.checkpoint import CheckpointManager

    _, cfg, params, data, q = _setup(n=16)
    engine = CorpusRankingEngine(cfg, q["item_ids"][0], q["item_weights"][0])
    mgr = CheckpointManager(str(tmp_path), keep=5)
    sel = lambda t: t["params"]
    mgr.save({"params": params}, step=5, blocking=True)
    assert engine.maybe_refresh(mgr, {"params": params}, select=sel)

    bumped = dict(params)
    bumped["bias"] = params["bias"] + 1.0
    mgr.save({"params": bumped}, step=7, blocking=True)
    with open(os.path.join(str(tmp_path), "step_00000007", "arrays.npz"),
              "wb") as f:
        f.write(b"garbage")
    import pytest
    from repro.serving import RefreshFailed
    with pytest.raises(RefreshFailed):    # first poll: the bad push surfaces
        engine.maybe_refresh(mgr, {"params": params}, select=sel)
    assert not engine.maybe_refresh(mgr, {"params": params}, select=sel)
    assert engine.model_step == 5

    mgr.save({"params": bumped}, step=6, blocking=True)   # valid, < 7
    assert engine.maybe_refresh(mgr, {"params": params}, select=sel)
    assert engine.model_step == 6
    # the corrupt-7 push stays recorded: 6 installed as a FALLBACK
    assert engine.last_refresh_error is not None


def test_engine_bf16_weights_follow_cfg_dtype():
    """The satellite dtype fix: default context/item weights must follow
    cfg.dtype so a bf16 serving path is not silently promoted to f32."""
    import dataclasses
    _, cfg, params, data, q = _setup(n=16)
    cfg16 = dataclasses.replace(cfg, dtype=jnp.bfloat16)
    p16 = jax.tree.map(
        lambda a: a.astype(jnp.bfloat16) if a.dtype == jnp.float32 else a,
        params)
    engine = CorpusRankingEngine(cfg16, q["item_ids"][0])
    engine.refresh(p16, step=0)
    assert engine.cache.Q_I.dtype == jnp.bfloat16
    s = engine.score(q["context_ids"])
    assert s.dtype == jnp.bfloat16
    assert engine._ctx_arrays(q["context_ids"], None)[1].dtype == jnp.bfloat16


# ---------------------------------------------------------------------------
# Satellites: shared item-lookup helper + use_pallas_kernels flag
# ---------------------------------------------------------------------------

def test_lookup_item_embeddings_helper(rng):
    layout, cfg, params, _, q = _setup()
    table = params["embedding"]
    item_layout = layout.subset("item")
    from repro.embedding.bag import embedding_bag
    want = embedding_bag(
        table,
        item_arena_ids(layout, q["item_ids"])
        + jnp.asarray(item_layout.slot_offsets),
        q["item_weights"], item_layout.slot_to_field, item_layout.n_fields)
    got = lookup_item_embeddings(table, layout, q["item_ids"],
                                 q["item_weights"])
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_use_pallas_kernels_flag_routes_rank_items():
    import dataclasses
    _, cfg, params, data, q = _setup(n=25)
    qb = _batched_query(data, q, 2, 25)
    want = fwfm.rank_items(params, cfg, qb)
    cfg_k = dataclasses.replace(cfg, use_pallas_kernels=True)
    got = fwfm.rank_items(params, cfg_k, qb)
    np.testing.assert_allclose(got, want, atol=1e-5)
