"""MIND served through the normal path (``CorpusState`` on a
``ScorerRuntime``, ``QueryFrontend``, RPC) against the float32 reference
``models/recsys/mind.py``, at smoke size on the CPU: the jnp scorer and
the Pallas kernel ``mind_corpus_score`` (interpret mode), the adaptive
interest count, churn and slab growth, and the query-vector counters.

Scores are compared to 2e-6 absolute: a score is an inner product of
d = 16 float32 terms of order 0.1, summed in another order by each path
(the kernel's tiles, XLA's einsum), so paths differ by a few ulps of the
terms' magnitudes; slots must match exactly.
"""
import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs.mind import make_smoke
from repro.kernels import ops, ref
from repro.models.recsys import mind
from repro.serving import (CorpusState, QueryFrontend, RpcClient,
                           ScorerRuntime, serve_in_thread)

ATOL = 2e-6


@pytest.fixture(scope="module")
def cfg():
    return make_smoke()


def _params(cfg, seed=0):
    p = mind.init(jax.random.PRNGKey(seed), cfg)
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed + 1))
    # non-zero biases so every term of the tower is live
    return dict(p, b1=0.1 * jax.random.normal(k1, p["b1"].shape),
                b2=0.1 * jax.random.normal(k2, p["b2"].shape))


def _queries(cfg, rng, lengths):
    """Query rows: profile ids, then distinct history ids, -1 padding."""
    n_items = cfg.layout.fields[-1].vocab_size
    prof = np.stack([rng.integers(0, f.vocab_size, len(lengths))
                     for f in cfg.layout.fields[:-1]], axis=1)
    hist = np.full((len(lengths), cfg.seq_len), -1)
    for q, n in enumerate(lengths):
        hist[q, :n] = rng.choice(n_items, n, replace=False)
    return np.concatenate([prof, hist], axis=1).astype(np.int32)


def _reference_topk(params, cfg, rows, items, valid, K):
    """``mind.rank_items`` over the live slab + ``lax.top_k``."""
    m = cfg.layout.n_context
    hist = rows[:, m:]
    q = {"profile_ids": jnp.asarray(rows[:, :m]),
         "hist_ids": jnp.asarray(np.maximum(hist, 0)),
         "hist_mask": jnp.asarray((hist >= 0).astype(np.float32)),
         "item_ids": jnp.broadcast_to(jnp.asarray(items)[None],
                                      (len(rows), *items.shape))}
    s = mind.rank_items(params, cfg, q)
    return jax.lax.top_k(jnp.where(jnp.asarray(valid)[None], s, -jnp.inf), K)


def _slab(cfg, rng, n, capacity):
    items = rng.permutation(cfg.layout.fields[-1].vocab_size)[:n]
    return items.reshape(n, 1).astype(np.int32)


@pytest.mark.parametrize("pallas,Bq,K,block_n", [
    (False, 1, 4, None), (False, 5, 8, None), (True, 1, 1, 128),
    (True, 3, 8, 128), (True, 8, 16, 256), (True, 16, 5, None),
])
def test_served_topk_matches_reference(cfg, pallas, Bq, K, block_n):
    rng = np.random.default_rng(Bq * 100 + K)
    params = _params(cfg)
    items = _slab(cfg, rng, 200, 256)
    rt = ScorerRuntime(cfg, use_pallas_kernel=pallas, block_n=block_n)
    st = CorpusState(None, items, capacity=256, runtime=rt)
    st.refresh(params, step=0)
    dead = rng.choice(200, 30, replace=False)
    st.remove_items(dead)
    rows = _queries(cfg, rng, rng.integers(1, cfg.seq_len + 1, Bq))
    vals, slots = st.topk(rows, K)
    valid = np.ones(200, bool)
    valid[dead] = False
    want_v, want_i = _reference_topk(params, cfg, rows, items, valid, K)
    np.testing.assert_array_equal(np.asarray(slots), np.asarray(want_i))
    np.testing.assert_allclose(np.asarray(vals), np.asarray(want_v),
                               rtol=0, atol=ATOL)
    assert not st.is_live(dead).any()
    assert not st.kernel_degraded


@pytest.mark.parametrize("Bq,topk,block_n", [(2, 3, 128), (9, 16, 512),
                                             (16, 8, None)])
def test_kernel_matches_oracle_with_dead_interests(Bq, topk, block_n):
    """``mind_corpus_score`` against its oracle, with a dead interest made
    the strongest of its row: it must never win."""
    rng = np.random.default_rng(Bq)
    n, K, d = 1000, 4, 64
    E = jnp.asarray(rng.normal(size=(n, d)).astype(np.float32) / 8)
    U = rng.normal(size=(Bq, K, d)).astype(np.float32)
    live = rng.random((Bq, K)) < 0.6
    live[:, 0] = True
    U[:, -1] *= 100.0
    live[:, -1] = False
    U, live = jnp.asarray(U), jnp.asarray(live)
    valid = jnp.asarray(rng.random(n) < 0.8)
    vals, idx = ops.mind_corpus_score(E, U, live, valid, topk=topk,
                                      block_n=block_n)
    want_v, want_i = ref.mind_corpus_topk_ref(E, U, live, topk, valid)
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(want_i))
    np.testing.assert_allclose(vals, want_v, rtol=0, atol=1e-5)
    full = ops.mind_corpus_score(E, U, live, valid, block_n=block_n)
    np.testing.assert_allclose(full, ref.mind_corpus_score_ref(E, U, live,
                                                               valid),
                               rtol=0, atol=1e-5)
    everyone = ref.mind_corpus_score_ref(E, U, jnp.ones_like(live), valid)
    assert not np.allclose(np.asarray(full), np.asarray(everyone))


def test_adaptive_interest_count_and_padding():
    """Histories of 1, 3, 8 and 50 behaviours route over 1, 1, 3 and 4
    interests (K'_u = max(1, min(4, floor(log2 n)))), and a served score
    is the max over the live ones only."""
    cfg = dataclasses.replace(make_smoke(), n_interests=4, seq_len=50)
    params = _params(cfg)
    rng = np.random.default_rng(0)
    rows = _queries(cfg, rng, [1, 3, 8, 50])
    assert mind.interest_counts(np.array([1, 3, 8, 50]), 4).tolist() == [
        1, 1, 3, 4]
    st = CorpusState(cfg, _slab(cfg, rng, 256, 256), capacity=256)
    st.refresh(params, step=0)
    U, live = st.runtime.model.query(params, jnp.asarray(rows), None)
    assert np.asarray(live).sum(1).tolist() == [1, 1, 3, 4]
    assert st.query_vectors(rows) == (16, 9)
    scores = np.asarray(st.score(rows))
    E = np.asarray(st.cache.E)
    every = np.einsum("qkd,nd->qkn", np.asarray(U), E)
    want = np.where(np.asarray(live)[..., None], every, -np.inf).max(1)
    np.testing.assert_allclose(scores, want, rtol=0, atol=ATOL)
    # the dead interests would have won somewhere: the mask has teeth
    assert (every.max(1) > want + 1e-3).any()


def test_churn_equals_fresh_build_with_zero_retraces(cfg):
    rng = np.random.default_rng(7)
    params = _params(cfg, seed=3)
    vocab = cfg.layout.fields[-1].vocab_size
    items = _slab(cfg, rng, 60, 64)
    st = CorpusState(cfg, items, capacity=64, use_pallas_kernel=True)
    st.refresh(params, step=0)
    rows = _queries(cfg, rng, [2, 8, 5, 1])
    st.topk(rows, 8)
    st.runtime.topk(params, st.cache, jnp.asarray(rows),
                    jnp.ones(rows.shape), K=8)
    st.remove_items([1, 2])
    st.add_items(np.array([[7]], np.int32))       # fills slot 1
    st.update_items([5, 6], np.array([[9], [11]], np.int32))
    st.topk(rows, 8)
    warm = st.trace_count
    st.remove_items([3])
    st.update_items([4], np.array([[13]], np.int32))
    st.topk(rows, 8)
    assert st.trace_count == warm                  # churn retraced nothing
    st.add_items(rng.choice(vocab, 8).reshape(8, 1).astype(np.int32))
    assert st.capacity == 128                      # the slab grew
    # a fresh build of the same slots, live set and item ids
    ids = np.asarray(st._slab_ids[st.valid_slots])
    fresh = CorpusState(cfg, np.zeros((128, 1), np.int32), capacity=128)
    fresh._slab_ids[:] = st._slab_ids
    fresh._valid_np[:] = st._valid_np
    fresh.refresh(params, step=0)
    np.testing.assert_array_equal(np.asarray(st.cache.valid),
                                  np.asarray(fresh.cache.valid))
    live = st.valid_slots
    np.testing.assert_array_equal(np.asarray(st.cache.E)[live],
                                  np.asarray(fresh.cache.E)[live])
    v, i = st.topk(rows, 8)
    fv, fi = fresh.runtime.topk(params, fresh.cache, jnp.asarray(rows),
                                jnp.ones(rows.shape), K=8)
    np.testing.assert_array_equal(np.asarray(i), np.asarray(fi))
    np.testing.assert_allclose(np.asarray(v), np.asarray(fv), rtol=0,
                               atol=ATOL)
    assert len(ids) == st.n_items


def test_rpc_round_trip_and_vector_counters(cfg):
    """One request over the wire equals the engine's own top-K, and the
    frontend counts the launches' scanned and live interest vectors."""
    rng = np.random.default_rng(11)
    params = _params(cfg)
    st = CorpusState(cfg, _slab(cfg, rng, 200, 256), capacity=256,
                     use_pallas_kernel=True)
    st.refresh(params, step=0)
    fe = QueryFrontend(st, max_batch=4, max_k=8, max_wait=1e-3,
                       auto_pump=False)
    assert fe._lanes["default"].n_ctx == (
        cfg.layout.n_context + cfg.seq_len)
    rows = _queries(cfg, rng, [1, 8, 4])
    before = dict(fe.stats)
    for r in rows:
        fe.submit(r, k=4)
    fe.pump(now=fe.clock() + 1.0)
    fe.resolve()
    K = cfg.n_interests
    # three rows pad to a Bq-4 launch by repeating the first row
    assert fe.stats["scan_vectors"] - before["scan_vectors"] == 4 * K
    assert fe.stats["live_vectors"] - before["live_vectors"] == 1 + 3 + 2 + 1
    with pytest.raises(ValueError):
        fe.submit(rows[0][:-1], k=4)
    server = serve_in_thread(fe)
    try:
        with RpcClient("127.0.0.1", server.port, timeout=30.0) as cli:
            sc, sl = cli.rank(rows[1], k=8)
    finally:
        server.stop()
    wv, wi = st.topk(rows[1:2], 8)
    np.testing.assert_array_equal(sl, np.asarray(wi)[0])
    np.testing.assert_allclose(sc, np.asarray(wv)[0], rtol=0, atol=ATOL)
