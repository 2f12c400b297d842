"""Algorithm 1 (context-cached ranking) vs direct pointwise scoring, for
every interaction variant and every recsys architecture."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs import REGISTRY
from repro.core import ranking as rk
from repro.core.dplr import init_dplr
from repro.core.fields import uniform_layout
from repro.core.interactions import dplr_pairwise
from repro.core.pruning import prune_matched
from repro.models.recsys import autoint, bst, fwfm, mind, wide_deep


def _query(rng, layout, B, N):
    nC = layout.n_context
    n_item_slots = layout.subset("item").n_slots
    ctx_ids = jnp.asarray(rng.integers(0, 16, (B, nC)).astype(np.int32))
    item_ids = jnp.asarray(rng.integers(0, 16, (B, N, n_item_slots)).astype(np.int32))
    return {
        "context_ids": ctx_ids,
        "context_weights": jnp.ones((B, nC), jnp.float32),
        "item_ids": item_ids,
        "item_weights": jnp.ones((B, N, n_item_slots), jnp.float32),
    }


@pytest.mark.parametrize("interaction", ["fm", "fwfm", "dplr"])
def test_fwfm_family_rank_equals_pointwise(rng, interaction):
    layout = uniform_layout(7, 5, 40)
    cfg = fwfm.FwFMConfig(layout=layout, embed_dim=8, interaction=interaction,
                          rank=2)
    params = fwfm.init(jax.random.PRNGKey(1), cfg)
    B, N = 3, 6
    q = _query(rng, layout, B, N)
    scores = fwfm.rank_items(params, cfg, q)
    full_ids = jnp.concatenate(
        [jnp.broadcast_to(q["context_ids"][:, None], (B, N, 7)),
         q["item_ids"]], -1)
    ref = fwfm.apply(params, cfg, {
        "ids": full_ids.reshape(B * N, -1),
        "weights": jnp.ones((B * N, layout.n_slots))}).reshape(B, N)
    np.testing.assert_allclose(scores, ref, rtol=1e-4, atol=1e-4)


def test_pruned_rank_equals_pointwise(rng):
    layout = uniform_layout(7, 5, 40)
    cfg = fwfm.FwFMConfig(layout=layout, embed_dim=8, interaction="fwfm")
    params = fwfm.init(jax.random.PRNGKey(2), cfg)
    R = fwfm.field_matrix(params, cfg)
    pr = prune_matched(R, 12, 2)
    B, N = 2, 5
    q = _query(rng, layout, B, N)
    scores = fwfm.rank_items(params, cfg, q, pruned=pr)
    full_ids = jnp.concatenate(
        [jnp.broadcast_to(q["context_ids"][:, None], (B, N, 7)),
         q["item_ids"]], -1)
    ref = fwfm.apply(params, cfg,
                     {"ids": full_ids.reshape(B * N, -1),
                      "weights": jnp.ones((B * N, 12))},
                     pruned_mask=pr.mask).reshape(B, N)
    np.testing.assert_allclose(scores, ref, rtol=1e-4, atol=1e-4)


def test_context_cache_is_item_independent(rng):
    """The cached context computation must not depend on the item set —
    the structural property that gives O(rho |I| k) per item."""
    layout = uniform_layout(5, 3, 30)
    m = layout.n_fields
    p = init_dplr(jax.random.PRNGKey(0), m, 2)
    V_C = jnp.asarray(rng.standard_normal((2, 5, 8), dtype=np.float32))
    c1 = rk.dplr_context_cache(p, V_C, 5)
    c2 = rk.dplr_context_cache(p, V_C, 5)
    np.testing.assert_array_equal(c1.P_C, c2.P_C)
    # scoring different item sets reuses the same cache
    for N in (1, 4):
        V_I = jnp.asarray(rng.standard_normal((2, N, 3, 8), dtype=np.float32))
        s = rk.dplr_score_items(p, c1, V_I, 5)
        Vfull = jnp.concatenate(
            [jnp.broadcast_to(V_C[:, None], (2, N, 5, 8)), V_I], axis=2)
        np.testing.assert_allclose(s, dplr_pairwise(Vfull, p), rtol=1e-4,
                                   atol=1e-4)


def test_wide_deep_and_autoint_rank(rng):
    layout = uniform_layout(4, 4, 50)
    B, N = 2, 4
    q = _query(rng, layout, B, N)
    full_ids = jnp.concatenate(
        [jnp.broadcast_to(q["context_ids"][:, None], (B, N, 4)),
         q["item_ids"]], -1).reshape(B * N, -1)
    w = jnp.ones((B * N, layout.n_slots))

    cfg = wide_deep.WideDeepConfig(layout=layout, embed_dim=8,
                                   mlp_dims=(16,), use_dplr_head=True)
    p = wide_deep.init(jax.random.PRNGKey(3), cfg)
    np.testing.assert_allclose(
        wide_deep.rank_items(p, cfg, q),
        wide_deep.apply(p, cfg, {"ids": full_ids, "weights": w}).reshape(B, N),
        rtol=1e-4, atol=1e-4)

    cfg2 = autoint.AutoIntConfig(layout=layout, embed_dim=8, n_attn_layers=2,
                                 n_heads=2, d_attn=16)
    p2 = autoint.init(jax.random.PRNGKey(4), cfg2)
    np.testing.assert_allclose(
        autoint.rank_items(p2, cfg2, q),
        autoint.apply(p2, cfg2, {"ids": full_ids, "weights": w}).reshape(B, N),
        rtol=1e-4, atol=1e-4)


def test_bst_and_mind_rank(rng):
    spec = REGISTRY["bst"]
    cfg = spec.make_smoke()
    p = bst.init(jax.random.PRNGKey(5), cfg)
    B, N, L = 2, 4, cfg.seq_len
    item_vocab = cfg.layout.fields[-1].vocab_size
    hist = jnp.asarray(rng.integers(0, item_vocab, (B, L)).astype(np.int32))
    hmask = jnp.asarray((rng.random((B, L)) > 0.2).astype(np.float32))
    q = {
        "context_ids": jnp.asarray(rng.integers(0, 16, (B, 3)).astype(np.int32)),
        "context_weights": jnp.ones((B, 3), jnp.float32),
        "hist_ids": hist, "hist_mask": hmask,
        "item_ids": jnp.asarray(rng.integers(0, item_vocab, (B, N, 1)).astype(np.int32)),
    }
    s = bst.rank_items(p, cfg, q)
    refs = []
    for j in range(N):
        ids = jnp.concatenate([q["context_ids"], q["item_ids"][:, j]], -1)
        refs.append(bst.apply(p, cfg, {
            "ids": ids, "weights": jnp.ones_like(ids, jnp.float32),
            "hist_ids": hist, "hist_mask": hmask}))
    np.testing.assert_allclose(s, jnp.stack(refs, 1), rtol=1e-4, atol=1e-4)

    mspec = REGISTRY["mind"]
    mcfg = mspec.make_smoke()
    mp = mind.init(jax.random.PRNGKey(6), mcfg)
    item_vocab = mcfg.layout.fields[-1].vocab_size
    histm = jnp.asarray(rng.integers(0, item_vocab, (B, mcfg.seq_len)).astype(np.int32))
    hm = jnp.ones((B, mcfg.seq_len), jnp.float32)
    prof = jnp.asarray(rng.integers(0, 16, (B, mcfg.layout.n_context)).astype(np.int32))
    qm = {"profile_ids": prof, "hist_ids": histm, "hist_mask": hm,
          "item_ids": jnp.asarray(rng.integers(0, item_vocab, (B, N, 1)).astype(np.int32))}
    sm = mind.rank_items(mp, mcfg, qm)
    refm = jnp.stack([
        mind.apply(mp, mcfg, {"profile_ids": prof, "hist_ids": histm,
                              "hist_mask": hm,
                              "target_id": qm["item_ids"][:, j, 0]})
        for j in range(N)], 1)
    np.testing.assert_allclose(sm, refm, rtol=1e-4, atol=1e-4)
